"""Host wall time of moving one batch of device arrays onto the SPMD
learner's mesh (the ``learner.reshard`` span, nested in
``learner.stage``: the trajectories concatenated on the chip that holds
them and the resharding ``device_put``, both dispatched), in
milliseconds per update, averaged over the spans that lie wholly
inside the window."""
from chipbench import host_spans

PATTERN = host_spans.pattern("learner.reshard")


def compute(ctx):
    return host_spans.mean_ms(ctx.trace, PATTERN)
