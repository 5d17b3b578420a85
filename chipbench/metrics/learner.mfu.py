"""The learner's share of the chips' peak, in percent: the train step's
operations per observation (``chipbench/flops/<config>.py``) times the
observations the window's updates trained on (T+1 per row), over the
window's seconds, the chips and the peak bf16 rate of one chip. The
actors' forward passes are not counted."""


def compute(ctx):
    rows = ctx.rows_trained
    if rows <= 0:
        return None
    obs = rows * (ctx.config["unroll_length"] + 1)
    flops = obs * ctx.flops.train_flops(ctx.config)
    return 100.0 * flops / (ctx.window_s * ctx.chips *
                            ctx.peaks["bf16_flops_per_s"])
