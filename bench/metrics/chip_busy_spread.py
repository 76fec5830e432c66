"""Device mesh: how far the busiest and the idlest chip of the cell lie
apart over the traced window, (max - min) of each chip's own busy time
over their mean, in %.  The straggling chip, which exists only across
chips; a one-chip cell has none."""


def read(r):
    busy = r.chip_busy_s
    if len(busy) < 2 or min(busy) <= 0:
        return None
    return (max(busy) - min(busy)) / (sum(busy) / len(busy)) * 100.0
