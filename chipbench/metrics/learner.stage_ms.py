"""Host wall time from the learner's first trajectory in hand to its
batch staged (the ``learner.stage`` span: collect, lag and episode
bookkeeping, replay sampling, stacking and its ``device_put``), in
milliseconds per update, averaged over the spans that lie wholly inside
the window."""
from chipbench import host_spans

PATTERN = host_spans.pattern("learner.stage")


def compute(ctx):
    return host_spans.mean_ms(ctx.trace, PATTERN)
