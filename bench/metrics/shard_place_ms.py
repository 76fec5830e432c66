"""Pipeline entry: the self time of the ``mozart.layout`` span of the
program's stages, per call, in ms.  In a cell of one sharded stage it is
placing that stage's inputs on the mesh on every call (streams regrouped
onto the shard grid, each input put on its sharding), which the sharded
executor opens ``mozart.layout`` around."""

from bench import spans


def read(r):
    t = spans.of_reading(r)
    t = t.by_name.get("mozart.layout") if t else None
    if t is None or r.calls == 0:
        return None
    return t.self_s / r.calls * 1e3
