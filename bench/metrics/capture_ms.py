"""Pipeline entry: the self time of the program's ``mozart.capture`` span
(running the user's function lazily, one graph node per annotated call),
per call, in milliseconds."""

from bench import spans


def read(r):
    t = spans.of_reading(r)
    t = t.by_name.get("mozart.capture") if t else None
    if t is None or r.calls == 0:
        return None
    return t.self_s / r.calls * 1e3
