"""Device time of the learner's train-step program per update, per chip,
in milliseconds. The program is found by the name JAX gives the jitted
step: ``train_step`` on one device, ``local_step`` (the ``shard_map``
body) in SPMD mode."""

PATTERN = r"^jit_(train_step|local_step)\b"


def compute(ctx):
    tr = ctx.trace
    total = sum(tr.modules[d].total_s(PATTERN) for d in tr.devices)
    if total <= 0 or ctx.updates <= 0:
        return None
    return 1e3 * total / len(tr.devices) / ctx.updates
