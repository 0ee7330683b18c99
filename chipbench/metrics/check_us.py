"""Device time of the protocol step's ``check`` scope (the dynamic
protocol's local-condition check, with its violation psum on a mesh)
per check round (rounds / ``mini_batch``) completed in the traced
window, on the busiest chip (chipbench/scopes.py; layer: engine step)."""

from chipbench import scopes


def read(r):
    protocol = r.traffic["protocol"]
    if protocol["kind"] != "dynamic":
        return None
    ns = scopes.busiest_scope_ns(r, "check")
    checks = r.rounds // protocol.get("mini_batch", 1)
    return None if ns is None or not checks else ns / 1e3 / checks
