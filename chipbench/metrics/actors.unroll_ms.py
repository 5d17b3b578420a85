"""Device time of one actor unroll (``core/actor.py``'s jitted
``unroll``: T steps of the policy and the env for one actor's envs), in
milliseconds."""

PATTERN = r"^jit_unroll\b"


def compute(ctx):
    tr = ctx.trace
    n = sum(tr.modules[d].count(PATTERN) for d in tr.devices)
    if not n:
        return None
    return 1e3 * sum(tr.modules[d].total_s(PATTERN)
                     for d in tr.devices) / n
