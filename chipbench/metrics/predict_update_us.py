"""Device time of the protocol step's ``predict_update`` scope (predict
and update, or the fused round, with the error terms) per protocol
round completed in the traced window, on the busiest chip
(chipbench/scopes.py; layer: engine step)."""

from chipbench import scopes


def read(r):
    ns = scopes.busiest_scope_ns(r, "predict_update")
    return None if ns is None else ns / 1e3 / r.rounds
