"""The fused loss/V-trace kernel's time against its floor, in percent.

The floor of one call is the larger of its bytes over the HBM rate and
its operations over the peak rate, counted from the algorithm's unpadded
operands: logits and one-hot actions (T, B, A) and five (T, B) rows in,
four (T, B) rows out, float32, with B the rows one chip holds. Padding
the kernel does counts as waste. The kernel's launch is found by the
name JAX gives it today: the custom call that the ``jax.custom_vjp``
forward lowers to, ``jvp__``.
"""

PATTERN = r"^%jvp__[.0-9]* = .*custom-call\("


def call_bytes(t, b, a):
    return 4 * (2 * t * b * a + 9 * t * b)


def call_flops(t, b, a):
    # log-softmax, target log-prob, entropy: about 10 per action; the
    # importance weights and the V-trace recursion: about 20 per step
    return t * b * (10 * a + 20)


def compute(ctx):
    tr = ctx.trace
    calls = sum(tr.ops[d].count(PATTERN) for d in tr.devices)
    seconds = sum(tr.ops[d].total_s(PATTERN) for d in tr.devices)
    if not calls or seconds <= 0 or not ctx.updates_by_trajs:
        return None
    t, a = ctx.config["unroll_length"], ctx.config["num_actions"]
    bw = ctx.peaks["hbm_bytes_per_s"]
    peak = ctx.peaks["bf16_flops_per_s"]
    floors, n = 0.0, 0
    for trajs, updates in ctx.updates_by_trajs.items():
        b = trajs * ctx.traffic["num_envs"] // ctx.chips
        floors += updates * max(call_bytes(t, b, a) / bw,
                                call_flops(t, b, a) / peak)
        n += updates
    return 100.0 * (floors / n) / (seconds / calls)
