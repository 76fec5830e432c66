"""Pipeline entry: device-idle time inside ``bench.call`` while the
innermost open program span is an entry span (``mozart.call``, ``.capture``,
``.plan`` and its children, ``.evaluate``, ``.force``), per call, in ms."""

from bench import spans


def read(r):
    if r.busy_s <= 0 or r.calls == 0:
        return None
    t = spans.of_reading(r)
    return None if t is None else t.idle_s(spans.ENTRY) / r.calls * 1e3
