"""The IMPALA learner: batched V-trace actor-critic updates (paper §3, §4.2).

``build_train_step`` closes over the architecture + IMPALA configs and the
optimizer and returns a pure ``train_step(params, opt_state, step, batch)``
suitable for ``jax.jit`` with pjit shardings (see ``repro.launch``). The
same builder serves the CPU examples and the 512-device dry-run.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ImpalaConfig
from repro.core import losses as losses_lib
from repro.models import backbone as bb
from repro.models.common import cast as common_cast
from repro.optim import optimizer as opt_lib

PyTree = Any


def forward_trajectory(params, batch: Dict, arch_cfg: ArchConfig,
                       num_actions: int):
    """Run the backbone over the T+1 trajectory observations.

    Returns (logits (B,T+1,A), values (B,T+1), aux)."""
    if arch_cfg.family == "impala_cnn":
        model_batch = {
            "image": batch["obs_image"],
            "last_action": batch["last_action"],
            "last_reward": batch["last_reward"],
            "done": batch["done_in"],
            "lstm_state": batch.get("lstm_state"),
        }
    else:
        model_batch = {"tokens": batch["obs_token"]}
        for k in ("enc_embed", "image_embed"):
            if k in batch:
                model_batch[k] = batch[k]
    out = bb.apply_train(params, model_batch, arch_cfg, num_actions)
    return out.policy_logits, out.values, out.aux_loss


def build_loss_fn(arch_cfg: ArchConfig, cfg: ImpalaConfig,
                  num_actions: int, vtrace_impl: str = "auto",
                  aux_coef: float = 0.01):
    def loss_fn(params, batch):
        logits, values, aux = forward_trajectory(params, batch, arch_cfg,
                                                 num_actions)
        loss_batch = {
            "actions": batch["actions"],
            "rewards": batch["rewards"],
            "discounts": batch["discounts"],
            "behaviour_logprob": batch["behaviour_logprob"],
            "bootstrap_value": values[:, -1],
        }
        total, metrics = losses_lib.impala_loss(
            cfg, logits[:, :-1], values[:, :-1], loss_batch,
            impl=vtrace_impl)
        if arch_cfg.moe is not None:
            total = total + aux_coef * aux * (
                batch["actions"].shape[0] * batch["actions"].shape[1])
            metrics["loss/moe_aux"] = aux
        return total, metrics

    return loss_fn


def build_train_step(arch_cfg: ArchConfig, cfg: ImpalaConfig,
                     num_actions: int,
                     optimizer: opt_lib.Optimizer = None,
                     vtrace_impl: str = "auto",
                     mixed_precision: bool = False,
                     ) -> Callable[..., Tuple[PyTree, PyTree, Dict]]:
    """vtrace_impl: 'auto' picks the Pallas kernel on TPU and the scan
    path elsewhere (``losses.resolve_vtrace_impl``); 'scan' / 'pallas' /
    'reference' pin an implementation.

    mixed_precision: the *live* params are bf16 leaves and the f32
    master copy lives in the optimizer state — so the autodiff cotangents
    (and the cross-device gradient reduction GSPMD inserts on them) are
    bf16, halving grad-sync bytes (§Perf B2). RMSProp accumulates on the
    f32 master. Note: casting to bf16 *inside* the step does NOT work —
    GSPMD places the reduction after the upcast (measured, §Perf B2).

    In this mode train_step expects ``params`` bf16 and
    ``opt_state = {"opt": <optimizer state>, "master": <f32 params>}``.
    """
    if optimizer is None:
        optimizer = opt_lib.rmsprop(decay=cfg.rmsprop_decay,
                                    eps=cfg.rmsprop_eps,
                                    momentum=cfg.rmsprop_momentum)
    lr_fn = opt_lib.linear_schedule(cfg.learning_rate, 0.0,
                                    cfg.lr_anneal_steps)
    loss_fn = build_loss_fn(arch_cfg, cfg, num_actions, vtrace_impl)

    def train_step(params, opt_state, step, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        lr = lr_fn(step)
        if mixed_precision:
            grads = common_cast(grads, jnp.float32)
            grads, grad_norm = opt_lib.clip_by_global_norm(
                grads, cfg.grad_clip_norm)
            master = opt_state["master"]
            updates, inner = optimizer.update(grads, opt_state["opt"],
                                              master, lr)
            master = opt_lib.apply_updates(master, updates)
            params = common_cast(master, jnp.bfloat16)
            opt_state = {"opt": inner, "master": master}
        else:
            grads, grad_norm = opt_lib.clip_by_global_norm(
                grads, cfg.grad_clip_norm)
            updates, opt_state = optimizer.update(grads, opt_state, params,
                                                  lr)
            params = opt_lib.apply_updates(params, updates)
        metrics["opt/grad_norm"] = grad_norm
        metrics["opt/lr"] = lr
        return params, opt_state, metrics

    return train_step, optimizer


def build_replay_loss_fn(arch_cfg: ArchConfig, cfg: ImpalaConfig,
                         num_actions: int, vtrace_impl: str = "auto",
                         aux_coef: float = 0.01):
    """Replay-aware loss: ``loss_fn(params, target_params, batch)``.

    ``batch['replay_mask']`` (B,) flags replayed rows. The IMPACT
    recipe: replayed rows take the *target network's* values as the
    V-trace correction baseline (``corrections.replay_baseline_mix``),
    so K repeated consumptions chase a fixed target; online rows are
    the exact standard loss. The per-trajectory |pg advantage| metric
    (``vtrace/traj_adv_mag``) doubles as the replay priority signal.
    """
    from repro.core import corrections

    def loss_fn(params, target_params, batch):
        logits, values, aux = forward_trajectory(params, batch, arch_cfg,
                                                 num_actions)
        _, tvalues, _ = forward_trajectory(target_params, batch, arch_cfg,
                                           num_actions)
        mask = batch["replay_mask"]
        corr_values = corrections.replay_baseline_mix(
            values[:, :-1], tvalues[:, :-1], mask)
        corr_bootstrap = corrections.replay_baseline_mix(
            values[:, -1], tvalues[:, -1], mask)
        loss_batch = {
            "actions": batch["actions"],
            "rewards": batch["rewards"],
            "discounts": batch["discounts"],
            "behaviour_logprob": batch["behaviour_logprob"],
            "bootstrap_value": values[:, -1],
        }
        total, metrics = losses_lib.impala_loss(
            cfg, logits[:, :-1], values[:, :-1], loss_batch,
            impl=vtrace_impl, corr_values=corr_values,
            corr_bootstrap=corr_bootstrap, per_traj=True)
        if arch_cfg.moe is not None:
            total = total + aux_coef * aux * (
                batch["actions"].shape[0] * batch["actions"].shape[1])
            metrics["loss/moe_aux"] = aux
        return total, metrics

    return loss_fn


def build_replay_train_step(arch_cfg: ArchConfig, cfg: ImpalaConfig,
                            num_actions: int,
                            optimizer: opt_lib.Optimizer = None,
                            vtrace_impl: str = "auto",
                            ) -> Callable[..., Tuple[PyTree, PyTree, Dict]]:
    """``train_step(params, target_params, opt_state, step, batch)`` —
    the fused update for the replay path. Gradients flow only through
    ``params`` (argnum 0); ``target_params`` is a read-only periodic
    snapshot, so callers jit with ``donate_argnums=(0, 2)`` and keep
    the target buffer alive across steps."""
    if optimizer is None:
        optimizer = opt_lib.rmsprop(decay=cfg.rmsprop_decay,
                                    eps=cfg.rmsprop_eps,
                                    momentum=cfg.rmsprop_momentum)
    lr_fn = opt_lib.linear_schedule(cfg.learning_rate, 0.0,
                                    cfg.lr_anneal_steps)
    loss_fn = build_replay_loss_fn(arch_cfg, cfg, num_actions, vtrace_impl)

    def train_step(params, target_params, opt_state, step, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, target_params, batch)
        grads, grad_norm = opt_lib.clip_by_global_norm(
            grads, cfg.grad_clip_norm)
        lr = lr_fn(step)
        updates, opt_state = optimizer.update(grads, opt_state, params, lr)
        params = opt_lib.apply_updates(params, updates)
        metrics["opt/grad_norm"] = grad_norm
        metrics["opt/lr"] = lr
        return params, opt_state, metrics

    return train_step, optimizer


def build_replay_grad_apply_steps(arch_cfg: ArchConfig, cfg: ImpalaConfig,
                                  num_actions: int,
                                  optimizer: opt_lib.Optimizer = None,
                                  vtrace_impl: str = "auto"):
    """Replay-aware split of ``build_grad_apply_steps``:
    ``grad_step(params, target_params, batch)`` plus the unchanged
    ``apply_step`` (clipping on the exchanged mean, identical update
    math so group replicas stay digest-identical)."""
    if optimizer is None:
        optimizer = opt_lib.rmsprop(decay=cfg.rmsprop_decay,
                                    eps=cfg.rmsprop_eps,
                                    momentum=cfg.rmsprop_momentum)
    lr_fn = opt_lib.linear_schedule(cfg.learning_rate, 0.0,
                                    cfg.lr_anneal_steps)
    loss_fn = build_replay_loss_fn(arch_cfg, cfg, num_actions, vtrace_impl)

    def grad_step(params, target_params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, target_params, batch)
        return grads, metrics

    def apply_step(params, opt_state, step, grads):
        grads, grad_norm = opt_lib.clip_by_global_norm(
            grads, cfg.grad_clip_norm)
        lr = lr_fn(step)
        updates, opt_state = optimizer.update(grads, opt_state, params,
                                              lr)
        params = opt_lib.apply_updates(params, updates)
        return params, opt_state, {"opt/grad_norm": grad_norm,
                                   "opt/lr": lr}

    return grad_step, apply_step, optimizer


def build_grad_apply_steps(arch_cfg: ArchConfig, cfg: ImpalaConfig,
                           num_actions: int,
                           optimizer: opt_lib.Optimizer = None,
                           vtrace_impl: str = "auto"):
    """``train_step`` split at the gradient: ``grad_step(params, batch)
    -> (grads, metrics)`` and ``apply_step(params, opt_state, step,
    grads) -> (params, opt_state, metrics)`` — the shape a
    data-parallel learner group needs, with a gradient exchange (mean
    over the group) between the two halves.

    Clipping happens in ``apply_step``, i.e. on the *exchanged mean*:
    clip-after-average is the data-parallel-faithful choice (it equals
    clipping the global-batch gradient a single learner with the
    concatenated batch would have computed, up to the averaging
    order), and it keeps every replica applying bit-identical updates
    because they all clip the same broadcast buffer.

    Composing the halves locally (``apply_step(params, opt_state, step,
    grad_step(params, batch)[0])``) is mathematically the fused
    ``train_step``; the fused path stays the single-learner default
    because one jit program fuses better than two.
    """
    if optimizer is None:
        optimizer = opt_lib.rmsprop(decay=cfg.rmsprop_decay,
                                    eps=cfg.rmsprop_eps,
                                    momentum=cfg.rmsprop_momentum)
    lr_fn = opt_lib.linear_schedule(cfg.learning_rate, 0.0,
                                    cfg.lr_anneal_steps)
    loss_fn = build_loss_fn(arch_cfg, cfg, num_actions, vtrace_impl)

    def grad_step(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return grads, metrics

    def apply_step(params, opt_state, step, grads):
        grads, grad_norm = opt_lib.clip_by_global_norm(
            grads, cfg.grad_clip_norm)
        lr = lr_fn(step)
        updates, opt_state = optimizer.update(grads, opt_state, params,
                                              lr)
        params = opt_lib.apply_updates(params, updates)
        return params, opt_state, {"opt/grad_norm": grad_norm,
                                   "opt/lr": lr}

    return grad_step, apply_step, optimizer


def build_spmd_train_step(arch_cfg: ArchConfig, cfg: ImpalaConfig,
                          num_actions: int, mesh,
                          optimizer: opt_lib.Optimizer = None,
                          vtrace_impl: str = "auto",
                          batch_replicated: bool = False,
                          ) -> Callable[..., Tuple[PyTree, PyTree, Dict]]:
    """Single-process data-parallel ``train_step`` over a ``('data',)``
    mesh: ``shard_map`` shards the batch on the leading trajectory axis,
    every device runs the backward pass on its shard, and the gradients
    are mean-reduced in-XLA (``lax.pmean`` — one fused collective, no
    host round-trip) before the replicated clip/update.

    Clip-after-average matches ``build_grad_apply_steps``: with N
    devices and per-shard sum-losses, the applied update is exactly
    what an N-learner hub/spoke group computes from the same shards —
    bit-identical on CPU, pinned by the digest-triangle test. Scalar
    metrics are pmean'd (each shard's loss is a local sum, so the
    reported loss is the per-shard mean, like a group member's).

    ``batch_replicated=True`` builds the divisibility-fallback variant
    (``sharding/rules.py`` replicates a leading dim the mesh cannot
    split): every device sees the full batch, the pmean is an identity
    over identical gradients, and the update equals the single-device
    fused step. Callers jit the result with ``donate_argnums=(0, 1)``.
    """
    from jax.sharding import PartitionSpec as P

    from repro.sharding.compat import shard_map

    if optimizer is None:
        optimizer = opt_lib.rmsprop(decay=cfg.rmsprop_decay,
                                    eps=cfg.rmsprop_eps,
                                    momentum=cfg.rmsprop_momentum)
    lr_fn = opt_lib.linear_schedule(cfg.learning_rate, 0.0,
                                    cfg.lr_anneal_steps)
    loss_fn = build_loss_fn(arch_cfg, cfg, num_actions, vtrace_impl)

    def local_step(params, opt_state, step, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        grads = jax.lax.pmean(grads, "data")
        metrics = jax.lax.pmean(metrics, "data")
        grads, grad_norm = opt_lib.clip_by_global_norm(
            grads, cfg.grad_clip_norm)
        lr = lr_fn(step)
        updates, opt_state = optimizer.update(grads, opt_state, params,
                                              lr)
        params = opt_lib.apply_updates(params, updates)
        metrics["opt/grad_norm"] = grad_norm
        metrics["opt/lr"] = lr
        return params, opt_state, metrics

    bspec = P() if batch_replicated else P("data")
    train_step = shard_map(local_step, mesh=mesh,
                           in_specs=(P(), P(), P(), bspec),
                           out_specs=(P(), P(), P()), check_vma=False)
    return train_step, optimizer


def build_spmd_replay_train_step(arch_cfg: ArchConfig, cfg: ImpalaConfig,
                                 num_actions: int, mesh,
                                 optimizer: opt_lib.Optimizer = None,
                                 vtrace_impl: str = "auto",
                                 batch_replicated: bool = False,
                                 ) -> Callable[..., Tuple[PyTree, PyTree,
                                                          Dict]]:
    """SPMD variant of ``build_replay_train_step``:
    ``train_step(params, target_params, opt_state, step, batch)`` with
    the batch (``replay_mask`` included — it is per-row data, so it
    shards with the rows) split over the ``('data',)`` mesh and the
    gradients pmean'd in-XLA. The per-trajectory ``vtrace/traj_adv_mag``
    metric is (B,)-shaped: each shard emits its local rows and the
    shard_map output spec reassembles the global vector, so replay
    re-prioritization sees every trajectory. Callers jit with
    ``donate_argnums=(0, 2)`` (the target is a long-lived snapshot)."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.compat import shard_map

    if optimizer is None:
        optimizer = opt_lib.rmsprop(decay=cfg.rmsprop_decay,
                                    eps=cfg.rmsprop_eps,
                                    momentum=cfg.rmsprop_momentum)
    lr_fn = opt_lib.linear_schedule(cfg.learning_rate, 0.0,
                                    cfg.lr_anneal_steps)
    loss_fn = build_replay_loss_fn(arch_cfg, cfg, num_actions, vtrace_impl)

    def local_step(params, target_params, opt_state, step, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, target_params, batch)
        metrics = dict(metrics)
        traj_adv = metrics.pop("vtrace/traj_adv_mag")
        grads = jax.lax.pmean(grads, "data")
        metrics = jax.lax.pmean(metrics, "data")
        grads, grad_norm = opt_lib.clip_by_global_norm(
            grads, cfg.grad_clip_norm)
        lr = lr_fn(step)
        updates, opt_state = optimizer.update(grads, opt_state, params,
                                              lr)
        params = opt_lib.apply_updates(params, updates)
        metrics["opt/grad_norm"] = grad_norm
        metrics["opt/lr"] = lr
        return params, opt_state, metrics, traj_adv

    bspec = P() if batch_replicated else P("data")
    smapped = shard_map(local_step, mesh=mesh,
                        in_specs=(P(), P(), P(), P(), bspec),
                        out_specs=(P(), P(), P(), bspec), check_vma=False)

    def train_step(params, target_params, opt_state, step, batch):
        params, opt_state, metrics, traj_adv = smapped(
            params, target_params, opt_state, step, batch)
        metrics = dict(metrics)
        metrics["vtrace/traj_adv_mag"] = traj_adv
        return params, opt_state, metrics

    return train_step, optimizer


def opt_state_specs(param_specs: PyTree, cfg: ImpalaConfig,
                    mixed_precision: bool = False) -> PyTree:
    """Spec tree for the optimizer state (mirrors param specs)."""
    inner = ({"ms": param_specs, "mom": param_specs}
             if cfg.rmsprop_momentum else {"ms": param_specs})
    if mixed_precision:
        return {"opt": inner, "master": param_specs}
    return inner
