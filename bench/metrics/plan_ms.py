"""Pipeline entry: the duration of the program's ``mozart.plan`` span
(rewrite, fingerprint, plan-cache lookup and stage instantiation), per
call, in milliseconds."""

from bench import spans


def read(r):
    t = spans.of_reading(r)
    t = t.by_name.get("mozart.plan") if t else None
    if t is None or r.calls == 0:
        return None
    return t.total_s / r.calls * 1e3
