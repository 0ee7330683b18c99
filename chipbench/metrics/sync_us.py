"""Device time of the protocol step's ``sync`` scope (the sync
``lax.cond``: on a sync the average, compression and byte ledger, with
the all_gather on a mesh; else the branch that keeps the models) per
sync in the traced window, on the busiest chip (chipbench/scopes.py;
layer: engine step)."""

from chipbench import scopes


def read(r):
    ns = scopes.busiest_scope_ns(r, "sync")
    return None if ns is None or not r.syncs else ns / 1e3 / r.syncs
