"""Device time of the SPMD learner's gradient all-reduce per update per
chip, in milliseconds: the ``lax.pmean`` of ``build_spmd_train_step``,
which XLA combines with the scalar metrics' pmean into one all-reduce
over a tuple of every gradient leaf. This is the exchange's exposed
time, the mean over the chips: on a chip that reaches the all-reduce
before the others, its time includes the wait for the last one (chip 0,
which also runs every actor's unroll), so it moves with that skew as
well as with the transfer. ``spmd.allreduce_roofline`` takes the chip
that waits least instead.

The op is found by the opcode in its name, as the ``XLA Ops`` line
names each op by its HLO text. The step compiled for a TPU v5e 2x2
holds one synchronous all-reduce over the tuple of 41 gradient leaves
and 6 metrics, ``%all-reduce.5 = (f32[256,1024]{1,0:T(8,128)S(1)}, ...)
all-reduce(%custom-call.22, ...)``, and no ``all-reduce-start`` /
``all-reduce-done`` pair; an async pair would match too (the ``XLA
Ops`` line lists async ops as well)."""

PATTERN = r"^%[\w.-]+ = .*? all-reduce(-start|-done)?\("
# one per call: an async pair's ``-done`` half is no call of its own
CALL = r"^%[\w.-]+ = .*? all-reduce(-start)?\("


def compute(ctx):
    tr = ctx.trace
    if len(tr.devices) < 2 or ctx.updates <= 0:
        return None
    total = sum(tr.ops[d].total_s(PATTERN) for d in tr.devices)
    if total <= 0:
        return None
    return 1e3 * total / len(tr.devices) / ctx.updates
