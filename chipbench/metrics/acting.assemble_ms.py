"""Host wall time of packing one inference-mode trajectory
(``runner.assemble_inference_traj`` in the ``acting.assemble`` span:
100 steps' outputs forced to the host and stacked), in milliseconds per
trajectory, averaged over the spans that lie wholly inside the
window."""
from chipbench import host_spans

PATTERN = host_spans.pattern("acting.assemble")


def compute(ctx):
    return host_spans.mean_ms(ctx.trace, PATTERN)
