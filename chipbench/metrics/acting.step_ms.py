"""Host wall time of one step of the thread-mode inference driver
(``runner.run_inference_driver_loop``'s ``acting.step`` span: every
logical actor's submit, the flush, every env-step dispatch), in
milliseconds, averaged over the steps that lie wholly inside the
window."""
from chipbench import host_spans

PATTERN = host_spans.pattern("acting.step")


def compute(ctx):
    return host_spans.mean_ms(ctx.trace, PATTERN)
