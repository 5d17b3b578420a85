"""Chip 0's busy time in the window over the mean busy time of the other
chips (times as ``trace_reduce`` unions each device's op intervals).
Chip 0 runs every thread actor's unroll and holds every publish, so a
ratio above 1 is the work it carries beyond its shard of the step."""


def compute(ctx):
    tr = ctx.trace
    if 0 not in tr.devices or len(tr.devices) < 2:
        return None
    others = [tr.busy_s(d) for d in tr.devices if d != 0]
    mean = sum(others) / len(others)
    if mean <= 0:
        return None
    return tr.busy_s(0) / mean
