"""Host wall time of handing one trajectory to the transport (the
``acting.emit`` span, backpressure retries included), in milliseconds
per trajectory, averaged over the spans that lie wholly inside the
window."""
from chipbench import host_spans

PATTERN = host_spans.pattern("acting.emit")


def compute(ctx):
    return host_spans.mean_ms(ctx.trace, PATTERN)
