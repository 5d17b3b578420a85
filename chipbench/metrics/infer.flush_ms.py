"""Device time of the inference service's batched forward per flush, in
milliseconds. The program is found by the name JAX gives it: ``flush``
(``distributed/inference.py``)."""

PATTERN = r"^jit_flush\b"


def compute(ctx):
    tr = ctx.trace
    n = sum(tr.modules[d].count(PATTERN) for d in tr.devices)
    if not n:
        return None
    return 1e3 * sum(tr.modules[d].total_s(PATTERN)
                     for d in tr.devices) / n
