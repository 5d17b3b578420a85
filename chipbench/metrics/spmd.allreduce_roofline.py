"""The gradient all-reduce's floor over its measured time per call, in
percent.

The floor of one call is the bytes a ring all-reduce must move through
each chip, 2 (n - 1) / n times the gradient's bytes, over one chip's
interconnect rate. The gradient is one float32 per parameter
(``chipbench/params_count.py``; 1,581,382 parameters, 6,325,528 bytes
for the deep agent); the scalar metrics that ride in the same op are
left out. ``ICI_BYTES_PER_S`` is from Google Cloud's "TPU v5e"
documentation: 1,600 Gbit/s of interchip interconnect per chip. The op
is the one ``spmd.collective_ms`` reads.

The time per call is that of the chip that waits least, the least over
the chips of its mean time per call: the others reach the synchronous
all-reduce early and wait there for the last chip (chip 0, which also
runs every actor's unroll), and that wait is no part of the transfer.
"""
from chipbench import params_count, run

ICI_BYTES_PER_S = 1600e9 / 8
_OP = run.load_module("metrics", "spmd.collective_ms.py")
PATTERN, CALL = _OP.PATTERN, _OP.CALL


def floor_s(cfg, chips: int) -> float:
    grad_bytes = 4 * params_count.count(cfg)      # float32
    moved = 2 * (chips - 1) / chips * grad_bytes
    return moved / ICI_BYTES_PER_S


def compute(ctx):
    tr = ctx.trace
    if len(tr.devices) < 2:
        return None
    per_call = [tr.ops[d].total_s(PATTERN) / tr.ops[d].count(CALL)
                for d in tr.devices if tr.ops[d].count(CALL)]
    if len(per_call) < len(tr.devices) or min(per_call) <= 0:
        return None
    return 100.0 * floor_s(ctx.config, ctx.chips) / min(per_call)
