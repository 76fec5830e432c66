"""Stage executors and kernels: the bytes of the layout copies the program
asks for outside its drivers, as a share of the traced calls' compulsory
bytes, in %.  The count is the program's ``layout_bytes`` counter, which
each ``mozart.stage`` span carries for its stage."""

from bench import spans


def read(r):
    if r.bytes <= 0:
        return None
    t = spans.of_reading(r)
    stage = t.by_name.get("mozart.stage") if t else None
    if stage is None or "layout_bytes" not in stage.attrs:
        return None
    return stage.attrs["layout_bytes"] / r.bytes * 100.0
