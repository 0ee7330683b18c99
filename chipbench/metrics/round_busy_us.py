"""Device busy time per protocol round completed in the traced window,
on the busiest chip (layer: engine step)."""


def read(r):
    busiest = max(d.busy_ns for d in r.summary.devices)
    return busiest / 1e3 / r.rounds
