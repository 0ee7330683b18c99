"""Share of the busiest chip's busy time spent in collectives
(all-gather, all-reduce and the like; chipbench/trace.py names them)
(layer: mesh)."""


def read(r):
    d = max(r.summary.devices, key=lambda x: x.busy_ns)
    if d.busy_ns == 0 or d.collective_ns == 0:
        return None
    return 100.0 * d.collective_ns / d.busy_ns
