"""Pipeline entry (capture, fingerprint, plan lookup, dispatch): device-idle
time that falls inside ``bench.call`` spans, per call, in milliseconds."""


def read(r):
    if r.busy_s <= 0 or r.calls == 0:
        return None
    return r.idle_in_calls_s / r.calls * 1e3
