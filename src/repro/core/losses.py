"""V-trace actor-critic losses (paper §4.2).

Total = pg_loss + baseline_cost * baseline_loss + entropy_cost * entropy_loss,
*summed* over batch and time (paper Table D.1 note: "the loss is summed
across the batch and time dimensions").
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ImpalaConfig
from repro.core import corrections, vtrace as vtrace_lib
from repro.kernels import vtrace as vtrace_kernels


def resolve_vtrace_impl(impl: str = "auto") -> str:
    """Map the ``auto`` V-trace implementation choice to a concrete one:
    the fused loss/V-trace Pallas kernel where it compiles for real
    (TPU), the ``lax.scan`` path everywhere else. Explicit choices pass
    through, so ablations and tests can still pin any implementation
    (``fused`` / ``pallas`` / ``scan`` / ``reference``)."""
    if impl != "auto":
        return impl
    return "fused" if jax.default_backend() == "tpu" else "scan"


def resolve_loss_impl(cfg: ImpalaConfig, impl: str = "auto",
                      replay: bool = False) -> str:
    """The V-trace implementation ``impala_loss`` actually runs for
    ``cfg``. The fused kernel computes only the plain V-trace loss: the
    ablation variants and the replay path (target-network baseline,
    per-trajectory advantages) keep their dedicated math and drop to
    the plain V-trace kernel on TPU, the scan elsewhere."""
    impl = resolve_vtrace_impl(impl)
    if impl == "fused" and (
            replay or cfg.correction != "vtrace" or
            getattr(cfg, "pg_q_estimate", "vtrace") == "baseline_v"):
        impl = "pallas" if jax.default_backend() == "tpu" else "scan"
    return impl


def reward_clip(rewards: jax.Array, mode: str) -> jax.Array:
    if mode == "abs_one":
        return jnp.clip(rewards, -1.0, 1.0)
    if mode == "soft_asymmetric":
        # Optimistic Asymmetric Clipping (Fig. D.1):
        # 0.3 * min(tanh(r), 0) + 5.0 * max(tanh(r), 0)
        t = jnp.tanh(rewards)
        return 0.3 * jnp.minimum(t, 0.0) + 5.0 * jnp.maximum(t, 0.0)
    if mode == "none":
        return rewards
    raise ValueError(mode)


def policy_gradient_loss(logits, actions, advantages, eps: float = 0.0):
    """-(sum) adv * log pi(a|x); advantages are already stop-gradient."""
    if eps:
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        logp_all = jnp.log(probs + eps)
        logp = jnp.take_along_axis(logp_all, actions[..., None], axis=-1)[..., 0]
    else:
        logp = vtrace_lib.action_log_probs(logits, actions)
    return -jnp.sum(jax.lax.stop_gradient(advantages) * logp)


def baseline_loss(values, vs):
    """0.5 * sum (v_s - V(x_s))^2."""
    return 0.5 * jnp.sum(jnp.square(jax.lax.stop_gradient(vs) -
                                    values.astype(jnp.float32)))


def entropy_loss(logits):
    """Negative entropy summed (so that adding it *with positive coef*
    maximizes entropy): sum_s sum_a pi log pi."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    p = jnp.exp(logp)
    return jnp.sum(p * logp)


def impala_loss(cfg: ImpalaConfig, target_logits, values, batch: Dict,
                impl: str = "auto", corr_values=None,
                corr_bootstrap=None, per_traj: bool = False
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The full IMPALA learner loss on a batch of trajectories.

    batch: actions (B,T) int32, rewards (B,T) f32, discounts (B,T) f32,
           behaviour_logprob (B,T) f32.
    target_logits: (B,T,A) f32; values: (B,T) f32 — note the trained
    values cover steps 0..T-1 and the *bootstrap* V(x_T) must be provided
    as batch['bootstrap_value'] (B,), produced by evaluating the learner
    network on x_T (we evaluate on T+1 steps and split outside).

    ``corr_values``/``corr_bootstrap`` (replay path) substitute the
    V(x_s) the V-trace recursion reads — e.g. ``corrections.
    replay_baseline_mix``'s target-network baseline on replayed rows —
    while the baseline loss keeps training the online ``values`` toward
    the resulting vs. The fused kernel assumes the correction baseline
    IS the trained values, so this path pins the scan/pallas impl.
    ``per_traj=True`` adds ``vtrace/traj_adv_mag`` (B,), the
    per-trajectory |pg advantage| mean — the replay priority signal.
    """
    impl = resolve_loss_impl(
        cfg, impl, replay=corr_values is not None or per_traj)
    rewards = reward_clip(batch["rewards"], cfg.reward_clip)
    if impl == "fused":
        return _impala_loss_fused(cfg, target_logits, values, batch,
                                  rewards)
    vs, pg_adv = corrections.compute_correction(
        cfg, batch["behaviour_logprob"], target_logits, batch["actions"],
        batch["discounts"], rewards,
        values if corr_values is None else corr_values,
        (batch["bootstrap_value"] if corr_bootstrap is None
         else corr_bootstrap),
        impl=impl)
    eps = cfg.eps_correction if cfg.correction == "eps" else 0.0
    pg = policy_gradient_loss(target_logits, batch["actions"], pg_adv, eps)
    bl = baseline_loss(values, vs)
    ent = entropy_loss(target_logits)
    total = pg + cfg.baseline_cost * bl + cfg.entropy_cost * ent
    metrics = {
        "loss/total": total,
        "loss/pg": pg,
        "loss/baseline": bl,
        "loss/entropy": ent,
        "vtrace/mean_vs": jnp.mean(vs),
        "vtrace/mean_pg_adv": jnp.mean(pg_adv),
    }
    if per_traj:
        metrics["vtrace/traj_adv_mag"] = jnp.mean(jnp.abs(pg_adv), axis=1)
    return total, metrics


def _impala_loss_fused(cfg: ImpalaConfig, target_logits, values, batch,
                       rewards) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Fused-kernel assembly of the same total as ``impala_loss``: one
    Pallas launch produces target log-probs, per-step negative entropy,
    v_s targets and pg advantages; only the final reductions stay in
    XLA. Batch-major inputs are transposed to the kernel's time-major
    layout here."""
    num_actions = target_logits.shape[-1]
    logits = jnp.moveaxis(target_logits.astype(jnp.float32), 1, 0)
    onehot = jax.nn.one_hot(
        jnp.moveaxis(batch["actions"], 1, 0), num_actions,
        dtype=jnp.float32)
    values_f = values.astype(jnp.float32)
    v_tp1 = jnp.concatenate(
        [values_f[:, 1:],
         batch["bootstrap_value"].astype(jnp.float32)[:, None]], axis=1)
    tm = lambda x: jnp.moveaxis(x.astype(jnp.float32), 1, 0)  # noqa: E731
    tlp, ne, vs, pg_adv = vtrace_kernels.fused_loss_vtrace(
        logits, onehot, tm(batch["behaviour_logprob"]),
        tm(batch["discounts"]), tm(rewards), tm(values_f), tm(v_tp1),
        cfg.rho_bar, cfg.c_bar, cfg.lambda_)
    vs = jax.lax.stop_gradient(vs)
    pg_adv = jax.lax.stop_gradient(pg_adv)
    pg = -jnp.sum(pg_adv * tlp)
    bl = 0.5 * jnp.sum(jnp.square(vs - jnp.moveaxis(values_f, 1, 0)))
    ent = jnp.sum(ne)
    total = pg + cfg.baseline_cost * bl + cfg.entropy_cost * ent
    metrics = {
        "loss/total": total,
        "loss/pg": pg,
        "loss/baseline": bl,
        "loss/entropy": ent,
        "vtrace/mean_vs": jnp.mean(vs),
        "vtrace/mean_pg_adv": jnp.mean(pg_adv),
    }
    return total, metrics
