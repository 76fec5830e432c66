"""Whole call: the median call latency of the timed window, in ms (host
clock, profiler off).  A single stalled call moves ``elems_per_s`` but not
this, so it is the steadier view of the same calls."""


def read(r):
    return r.call_median_s * 1e3 if r.call_median_s > 0 else None
