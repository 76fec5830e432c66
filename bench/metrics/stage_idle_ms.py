"""Stage executors and kernels: device-idle time inside ``bench.call``
while the innermost open program span is ``mozart.stage`` or one of its
children (``.inputs``, ``.layout``, ``.drive``, ``.merge``), per call, in
ms."""

from bench import spans


def read(r):
    if r.busy_s <= 0 or r.calls == 0:
        return None
    t = spans.of_reading(r)
    return None if t is None else t.idle_s(spans.STAGE) / r.calls * 1e3
