"""Share of the window the learner spent waiting in ``queue.get`` for a
trajectory (the union of its ``learner.wait`` spans, the parts inside
the window), in percent."""
from chipbench import host_spans

PATTERN = host_spans.pattern("learner.wait")


def compute(ctx):
    tr = ctx.trace
    spans = host_spans.matching(tr, PATTERN)
    if not spans:
        return None
    waited = sum(e - s for s, e in host_spans.union(spans))
    return 100.0 * waited * 1e-9 / tr.window_s
