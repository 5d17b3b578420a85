"""Host wall time of one inference flush (``InferenceService._run_flush``
in the ``infer.flush`` span: params pull, dispatch, the wait for the
device, the replies handed out), in milliseconds, averaged over the
flushes that lie wholly inside the window. ``infer.flush_ms`` is the
device's share of it."""
from chipbench import host_spans

PATTERN = host_spans.pattern("infer.flush")


def compute(ctx):
    return host_spans.mean_ms(ctx.trace, PATTERN)
