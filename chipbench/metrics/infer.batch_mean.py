"""Requests per inference flush in the window: the service's request and
flush counters, differenced across the window's edges."""


def compute(ctx):
    if "infer_flushes" not in ctx.start:
        return None
    flushes = ctx.end["infer_flushes"] - ctx.start["infer_flushes"]
    if flushes <= 0:
        return None
    return (ctx.end["infer_requests"] - ctx.start["infer_requests"]) / flushes
