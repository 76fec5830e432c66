"""Stage executors and kernels: the least time the chip could take for the
traced calls' compulsory work (the larger of FLOPs over peak FLOP/s and
bytes over peak HBM bandwidth) as a share of the device-busy time, in %.
It reads the same work whichever executor or kernel ran the stage."""


def read(r):
    if r.busy_s <= 0 or r.peak_bytes_per_s <= 0:
        return None
    return r.min_time_s() / r.busy_s * 100.0
