"""Whole call: the least time the chip could take for the traced calls'
compulsory work as a share of their wall time (the traced window), in %."""


def read(r):
    if r.busy_s <= 0 or r.peak_bytes_per_s <= 0 or r.window_s <= 0:
        return None
    return r.min_time_s() / r.window_s * 100.0
