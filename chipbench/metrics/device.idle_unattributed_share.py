"""Share of chip 0's idle time in the window that no program span
covers, in percent: what the program's spans cannot yet explain (GIL
waits, garbage collection, code outside any span). ``learner.wait`` is
left out of the cover: the learner waiting says the producer was busy,
not at what."""
from chipbench import host_spans

PATTERN = host_spans.pattern(
    "acting.step", "acting.env_step", "acting.assemble", "acting.emit",
    "acting.unroll", "infer.flush", "learner.stage", "learner.step",
    "learner.publish")


def compute(ctx):
    tr = ctx.trace
    if not tr.devices:
        return None
    w0, w1 = tr.window
    idle, t = [], w0
    for s, e in tr.busy_intervals(tr.devices[0]):
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if w1 > t:
        idle.append((t, w1))
    idle_s = sum(e - s for s, e in idle)
    cover = host_spans.union(host_spans.matching(tr, PATTERN))
    if idle_s <= 0 or not cover:
        return None
    return 100.0 * (idle_s - host_spans.covered(idle, cover)) / idle_s
