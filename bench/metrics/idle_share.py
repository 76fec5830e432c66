"""Device: the share of the traced window in which no operation ran, in %."""


def read(r):
    if r.busy_s <= 0 or r.window_s <= 0:
        return None
    return (1.0 - r.busy_s / r.window_s) * 100.0
