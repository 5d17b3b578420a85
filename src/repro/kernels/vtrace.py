"""Pallas TPU kernel for the V-trace reverse scan (paper Eq. 1 / Remark 1).

The recurrence is inherently sequential in time, so the kernel puts the
batch on lanes and iterates *time chunks in reverse* as sequential TPU
grid steps, carrying ``acc_{s+1} = v_{s+1} - V(x_{s+1})`` in a VMEM
scratch accumulator across grid steps — the TPU-idiomatic analogue of the
paper's fused-recurrence optimisation (§3.1).

Layout: all tensors time-major (T, B) float32. Grid = (B blocks, reversed
T chunks); T chunks iterate fastest so each batch block completes its full
reverse sweep before the next begins. One fused pass emits both the
targets v_s and the policy-gradient advantages.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_T_CHUNK = 256
DEFAULT_B_BLOCK = 128

INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Decide whether a Pallas kernel runs interpreted or compiled.

    Resolution order: explicit caller argument (``True``/``False``) >
    ``REPRO_PALLAS_INTERPRET`` env override ("1"/"0") > backend
    auto-detect — the real kernel on TPU, the interpreter everywhere
    else (CPU has no Mosaic lowering). The env override exists so a TPU
    run can be flipped to interpret mode for debugging (and a test rig
    can pin either mode) without touching call sites.
    """
    if interpret is not None:
        return bool(interpret)
    env = os.environ.get(INTERPRET_ENV)
    if env is not None and env != "":
        return env != "0"
    return jax.default_backend() != "tpu"


def _vtrace_kernel(rho_ref, c_ref, disc_ref, rew_ref, v_ref, vtp1_ref,
                   vs_ref, pg_ref, acc_ref, *, t_chunk: int):
    tj = pl.program_id(1)

    @pl.when(tj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # every loop value is a (1, b_block) row — batch on lanes. Mosaic
    # refuses a rank-1 carry (its layouts are at least 2-D), so rows
    # are sliced with pl.ds(s, 1) rather than indexed with a scalar
    def body(i, acc):
        s = pl.ds(t_chunk - 1 - i, 1)
        rho = rho_ref[s, :]
        disc = disc_ref[s, :]
        rew = rew_ref[s, :]
        v = v_ref[s, :]
        vtp1 = vtp1_ref[s, :]
        pg_ref[s, :] = rho * (rew + disc * (vtp1 + acc) - v)
        delta = rho * (rew + disc * vtp1 - v)
        acc = delta + disc * c_ref[s, :] * acc
        vs_ref[s, :] = v + acc
        return acc

    acc_ref[...] = jax.lax.fori_loop(0, t_chunk, body, acc_ref[...])


def vtrace_pallas(rho, c, discounts, rewards, values, values_tp1,
                  t_chunk: int = DEFAULT_T_CHUNK,
                  b_block: int = DEFAULT_B_BLOCK,
                  interpret: Optional[bool] = None):
    """All inputs (T, B) float32. Returns (vs, pg_adv), each (T, B).

    ``interpret=None`` (the default) auto-detects: compiled kernel on
    TPU, interpreter fallback elsewhere; see ``resolve_interpret``.
    """
    interpret = resolve_interpret(interpret)
    t, b = rho.shape
    t_chunk = min(t_chunk, t)
    b_block = min(b_block, b)
    # pad to multiples
    tp = (-t) % t_chunk
    bp = (-b) % b_block
    args = (rho, c, discounts, rewards, values, values_tp1)
    if tp or bp:
        args = tuple(jnp.pad(x, ((0, tp), (0, bp))) for x in args)
    tt, bb = t + tp, b + bp
    nt, nb = tt // t_chunk, bb // b_block

    in_spec = pl.BlockSpec((t_chunk, b_block),
                           lambda i, j: (nt - 1 - j, i))
    out_spec = pl.BlockSpec((t_chunk, b_block),
                            lambda i, j: (nt - 1 - j, i))
    vs, pg = pl.pallas_call(
        functools.partial(_vtrace_kernel, t_chunk=t_chunk),
        grid=(nb, nt),
        in_specs=[in_spec] * 6,
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((tt, bb), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((1, b_block), jnp.float32)],
        interpret=interpret,
    )(*args)
    return vs[:t, :b], pg[:t, :b]


# ---------------------------------------------------------------------------
# fused loss + V-trace: one kernel launch computes everything the IMPALA
# loss needs between the logits and the final reductions — log-softmax,
# target log-probs, entropy terms, the clipped importance weights, and
# the V-trace reverse scan — instead of ~10 separate XLA ops feeding the
# recurrence. Same layout discipline as ``vtrace_pallas`` (time-major,
# batch on lanes, reversed T chunks with a VMEM-carried accumulator).
# The action dimension rides whole in each block ON SUBLANES — the
# kernel sees logits as (T, A, B) — so the softmax reductions over
# actions land as (1, b_block) rows with the batch still on lanes, the
# layout every output is stored in. Actions pad to the 8-sublane
# multiple (not the 128-lane one) with a large negative logit so softmax
# ignores the pad: chase's 5 actions cost 8 rows, not 128 lanes, and a
# (100, 8, 128) f32 block is 400 KiB, far inside the default scoped VMEM.

_NEG_PAD = -1e30     # pad logit: exp underflows to exactly 0 in f32
SUBLANE = 8


def _loss_vtrace_kernel(logits_ref, onehot_ref, blp_ref, disc_ref,
                        rew_ref, v_ref, vtp1_ref,
                        tlp_ref, ne_ref, vs_ref, pg_ref, acc_ref, *,
                        t_chunk: int, rho_bar, c_bar, lambda_: float):
    tj = pl.program_id(1)

    @pl.when(tj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(i, acc):
        t = t_chunk - 1 - i
        s = pl.ds(t, 1)
        lg = logits_ref[t]                           # (A, b_block)
        m = jnp.max(lg, axis=0, keepdims=True)
        logp = lg - m - jnp.log(
            jnp.sum(jnp.exp(lg - m), axis=0, keepdims=True))
        tlp = jnp.sum(logp * onehot_ref[t], axis=0, keepdims=True)
        p = jnp.exp(logp)
        tlp_ref[s, :] = tlp
        ne_ref[s, :] = jnp.sum(p * logp, axis=0, keepdims=True)
        rho_raw = jnp.exp(tlp - blp_ref[s, :])
        rho = (jnp.minimum(rho_bar, rho_raw)
               if rho_bar is not None else rho_raw)
        c = lambda_ * (jnp.minimum(c_bar, rho_raw)
                       if c_bar is not None else rho_raw)
        disc = disc_ref[s, :]
        rew = rew_ref[s, :]
        v = v_ref[s, :]
        vtp1 = vtp1_ref[s, :]
        pg_ref[s, :] = rho * (rew + disc * (vtp1 + acc) - v)
        delta = rho * (rew + disc * vtp1 - v)
        acc = delta + disc * c * acc
        vs_ref[s, :] = v + acc
        return acc

    acc_ref[...] = jax.lax.fori_loop(0, t_chunk, body, acc_ref[...])


def loss_vtrace_pallas(logits, onehot, behaviour_logprob, discounts,
                       rewards, values, values_tp1,
                       rho_bar=1.0, c_bar=1.0, lambda_: float = 1.0,
                       t_chunk: int = DEFAULT_T_CHUNK,
                       b_block: int = DEFAULT_B_BLOCK,
                       interpret: Optional[bool] = None):
    """Forward-only fused pass. ``logits``/``onehot`` are (T, B, A)
    float32, everything else (T, B) float32. Returns
    (target_logprob, neg_entropy, vs, pg_adv), each (T, B).

    The onehot action encoding is an *input* (rather than int actions)
    so every argument of the differentiable wrapper is a float tensor —
    and so the in-kernel gather is a lane-friendly multiply-reduce."""
    interpret = resolve_interpret(interpret)
    t, b = behaviour_logprob.shape
    a = logits.shape[-1]
    t_chunk = min(t_chunk, t)
    b_block = min(b_block, b)
    tp = (-t) % t_chunk
    bp = (-b) % b_block
    ap = (-a) % SUBLANE
    flat = (behaviour_logprob, discounts, rewards, values, values_tp1)
    if tp or bp:
        flat = tuple(jnp.pad(x, ((0, tp), (0, bp))) for x in flat)
    if tp or bp or ap:
        # pad rows get uniform log-probs over real actions (tlp = onehot
        # sum = 0 against a zero onehot), zero rewards/discounts/values:
        # the carried accumulator stays exactly zero through them
        logits = jnp.pad(logits, ((0, tp), (0, bp), (0, ap)),
                         constant_values=_NEG_PAD)
        onehot = jnp.pad(onehot, ((0, tp), (0, bp), (0, ap)))
    # actions to sublanes, batch to lanes (see the layout note above)
    logits = jnp.swapaxes(logits, 1, 2)
    onehot = jnp.swapaxes(onehot, 1, 2)
    tt, bb, aa = t + tp, b + bp, a + ap
    nt, nb = tt // t_chunk, bb // b_block

    spec2d = pl.BlockSpec((t_chunk, b_block), lambda i, j: (nt - 1 - j, i))
    spec3d = pl.BlockSpec((t_chunk, aa, b_block),
                          lambda i, j: (nt - 1 - j, 0, i))
    tlp, ne, vs, pg = pl.pallas_call(
        functools.partial(_loss_vtrace_kernel, t_chunk=t_chunk,
                          rho_bar=rho_bar, c_bar=c_bar, lambda_=lambda_),
        grid=(nb, nt),
        in_specs=[spec3d, spec3d] + [spec2d] * 5,
        out_specs=[spec2d] * 4,
        out_shape=[jax.ShapeDtypeStruct((tt, bb), jnp.float32)] * 4,
        scratch_shapes=[pltpu.VMEM((1, b_block), jnp.float32)],
        interpret=interpret,
    )(logits, onehot, *flat)
    return tuple(x[:t, :b] for x in (tlp, ne, vs, pg))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def fused_loss_vtrace(logits, onehot, behaviour_logprob, discounts,
                      rewards, values, values_tp1, rho_bar=1.0,
                      c_bar=1.0, lambda_: float = 1.0):
    """Differentiable wrapper over ``loss_vtrace_pallas``.

    Gradients flow ONLY into ``logits`` (through the target log-probs
    and the entropy terms, both closed-form — no scan in the backward);
    ``vs``/``pg_adv`` are V-trace *targets* and implicitly
    stop-gradient, exactly like the scan implementation's contract."""
    return loss_vtrace_pallas(logits, onehot, behaviour_logprob,
                              discounts, rewards, values, values_tp1,
                              rho_bar=rho_bar, c_bar=c_bar,
                              lambda_=lambda_)


def _fused_fwd(logits, onehot, behaviour_logprob, discounts, rewards,
               values, values_tp1, rho_bar, c_bar, lambda_):
    outs = fused_loss_vtrace(logits, onehot, behaviour_logprob,
                             discounts, rewards, values, values_tp1,
                             rho_bar, c_bar, lambda_)
    tlp, ne, vs, pg = outs
    return outs, (logits, onehot, ne)


def _fused_bwd(rho_bar, c_bar, lambda_, res, cts):
    logits, onehot, ne = res
    g_tlp, g_ne, _g_vs, _g_pg = cts       # vs/pg_adv: stop-gradient
    logp = jax.nn.log_softmax(logits, axis=-1)
    p = jnp.exp(logp)
    # d tlp / d logits = onehot - p ;  d ne / d logits = p (logp - ne)
    d_logits = (g_tlp[..., None] * (onehot - p) +
                g_ne[..., None] * p * (logp - ne[..., None]))
    zeros_tb = jnp.zeros_like(ne)
    return (d_logits, jnp.zeros_like(onehot), zeros_tb, zeros_tb,
            zeros_tb, zeros_tb, zeros_tb)


fused_loss_vtrace.defvjp(_fused_fwd, _fused_bwd)
