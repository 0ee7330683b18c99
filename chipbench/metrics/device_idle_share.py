"""Share of the traced window in which no operation ran on the device,
mean over the chips used (layer: device)."""


def read(r):
    return 100.0 * (1.0 - r.busy_s / r.window_s)
