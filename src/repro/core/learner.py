"""The IMPALA learner: batched V-trace actor-critic updates (paper §3, §4.2).

One update is the loss's gradient (``_grad_fn``), an optional mean over
the learners, then the learning rate, clip, optimizer and apply
(``_apply_fn``). ``build_train_step`` composes the halves into one
program, on one device or over a ``('data',)`` mesh with an in-XLA
``lax.pmean``; ``build_grad_apply_steps`` leaves a host exchange between.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ImpalaConfig
from repro.core import corrections
from repro.core import losses as losses_lib
from repro.models import backbone as bb
from repro.models.common import cast as common_cast
from repro.optim import optimizer as opt_lib
from repro.sharding.compat import shard_map
from repro.sharding.rules import Rules

PyTree = Any

# metrics with one value per batch row, kept out of the mesh's mean
_PER_ROW_METRICS = ("vtrace/traj_adv_mag",)


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
    """``loss_fn(params, batch, target_params=None) -> (total, metrics)``.

    With ``target_params``, the replay loss (IMPACT): rows that
    ``batch['replay_mask']`` (B,) flags take the target network's values
    as the V-trace baseline (``corrections.replay_baseline_mix``), online
    rows the standard loss, and the per-trajectory |pg advantage|
    ``vtrace/traj_adv_mag`` (B,) comes back as the replay priority."""
    def loss_fn(params, batch, target_params=None):
        logits, values, aux = forward_trajectory(params, batch, arch_cfg,
                                                 num_actions)
        loss_batch = {
            "actions": batch["actions"],
            "rewards": batch["rewards"],
            "discounts": batch["discounts"],
            "behaviour_logprob": batch["behaviour_logprob"],
            "bootstrap_value": values[:, -1],
        }
        replay = {}
        if target_params is not None:
            _, tvalues, _ = forward_trajectory(target_params, batch,
                                               arch_cfg, num_actions)
            mask = batch["replay_mask"]
            replay = {
                "corr_values": corrections.replay_baseline_mix(
                    values[:, :-1], tvalues[:, :-1], mask),
                "corr_bootstrap": corrections.replay_baseline_mix(
                    values[:, -1], tvalues[:, -1], mask),
                "per_traj": True}
        total, metrics = losses_lib.impala_loss(
            cfg, logits[:, :-1], values[:, :-1], loss_batch,
            impl=vtrace_impl, **replay)
        if arch_cfg.moe is not None:
            total = total + aux_coef * aux * (
                batch["actions"].shape[0] * batch["actions"].shape[1])
            metrics["loss/moe_aux"] = aux
        return total, metrics

    return loss_fn


def _grad_fn(loss_fn):
    """``grad_step(params, batch, target_params=None) -> (grads, metrics)``:
    gradients flow through ``params`` only, never the target."""
    def grad_step(params, batch, target_params=None):
        (_, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch, target_params)
        return grads, metrics

    return grad_step


def _apply_fn(cfg: ImpalaConfig, optimizer: opt_lib.Optimizer,
              mixed_precision: bool = False):
    """``apply_step(params, opt_state, step, grads) -> (params, opt_state,
    metrics)``: the learning rate, clip by global norm, optimizer, apply.

    mixed_precision: the live params are bf16 and ``opt_state = {"opt":
    <optimizer state>, "master": <f32 params>}``, so the cotangents (and
    their cross-device reduction) are bf16 while RMSProp runs on the f32
    master (§Perf B2: a cast inside the step reduces after the upcast)."""
    lr_fn = opt_lib.linear_schedule(cfg.learning_rate, 0.0,
                                    cfg.lr_anneal_steps)

    def apply_step(params, opt_state, step, grads):
        lr = lr_fn(step)
        if mixed_precision:     # the update runs on the f32 master
            grads = common_cast(grads, jnp.float32)
            params, opt_state = opt_state["master"], opt_state["opt"]
        grads, grad_norm = opt_lib.clip_by_global_norm(
            grads, cfg.grad_clip_norm)
        updates, opt_state = optimizer.update(grads, opt_state, params, lr)
        params = opt_lib.apply_updates(params, updates)
        if mixed_precision:
            params, opt_state = (common_cast(params, jnp.bfloat16),
                                 {"opt": opt_state, "master": params})
        return params, opt_state, {"opt/grad_norm": grad_norm,
                                   "opt/lr": lr}

    return apply_step


def _optimizer(cfg: ImpalaConfig, optimizer):
    return optimizer if optimizer is not None else opt_lib.rmsprop(
        decay=cfg.rmsprop_decay, eps=cfg.rmsprop_eps,
        momentum=cfg.rmsprop_momentum)


def batch_splits(mesh, batch: PyTree) -> bool:
    """Whether the ``('data',)`` mesh splits ``batch``'s rows: every leaf
    has as many, and ``sharding.rules.Rules`` shards a ``batch`` axis of
    that size (its divisibility fallback replicates one it cannot)."""
    leaves = jax.tree.leaves(batch)
    rows = leaves[0].shape[0]
    return all(x.shape[0] == rows for x in leaves) and \
        Rules(mesh).spec(("batch",), (rows,))[0] is not None


def build_train_step(arch_cfg: ArchConfig, cfg: ImpalaConfig,
                     num_actions: int,
                     optimizer: opt_lib.Optimizer = None,
                     vtrace_impl: str = "auto", *, mesh=None,
                     mixed_precision: bool = False,
                     ) -> Tuple[Callable, opt_lib.Optimizer]:
    """The fused update ``train_step(params, opt_state, step, batch,
    target_params=None) -> (params, opt_state, metrics)`` and its
    optimizer (RMSProp from ``cfg`` unless given). ``target_params``
    selects the replay loss and is never donated: jit with
    ``donate_argnums=(0, 1)``. vtrace_impl: 'auto' picks the Pallas
    kernel on TPU and the scan elsewhere (``losses.resolve_vtrace_impl``).

    ``mesh``: data parallelism over a ``('data',)`` mesh in one program.
    ``shard_map`` splits the batch's rows, each device takes the
    gradient of its shard, and gradients and scalar metrics are
    ``lax.pmean``-ed in XLA before the replicated clip and update: the
    update an N-learner group computes from the same shards (the
    digest-triangle test). Rows the mesh cannot split (``batch_splits``,
    at trace time) run replicated, where the pmean is an identity.
    """
    optimizer = _optimizer(cfg, optimizer)
    grad_fn = _grad_fn(build_loss_fn(arch_cfg, cfg, num_actions,
                                     vtrace_impl))
    apply_fn = _apply_fn(cfg, optimizer, mixed_precision)

    def update(params, opt_state, step, batch, target_params):
        grads, metrics = grad_fn(params, batch, target_params)
        per_row = {k: metrics.pop(k) for k in _PER_ROW_METRICS
                   if k in metrics}
        if mesh is not None:
            grads = jax.lax.pmean(grads, "data")
            metrics = jax.lax.pmean(metrics, "data")
        params, opt_state, opt_metrics = apply_fn(params, opt_state, step,
                                                  grads)
        return params, opt_state, {**metrics, **opt_metrics}, per_row

    if mesh is None:
        def train_step(params, opt_state, step, batch, target_params=None):
            params, opt_state, metrics, per_row = update(
                params, opt_state, step, batch, target_params)
            return params, opt_state, {**metrics, **per_row}

        return train_step, optimizer

    def local_step(params, opt_state, step, batch, target_params=None):
        bspec = P("data") if batch_splits(mesh, batch) else P()
        params, opt_state, metrics, per_row = shard_map(
            update, mesh=mesh, in_specs=(P(), P(), P(), bspec, P()),
            out_specs=(P(), P(), P(), bspec), check_vma=False)(
                params, opt_state, step, batch, target_params)
        return params, opt_state, {**metrics, **per_row}

    return local_step, optimizer


def build_grad_apply_steps(arch_cfg: ArchConfig, cfg: ImpalaConfig,
                           num_actions: int,
                           optimizer: opt_lib.Optimizer = None,
                           vtrace_impl: str = "auto"):
    """``train_step`` split at the gradient, for a host gradient exchange
    (mean over the group) between the halves: ``(grad_step, apply_step,
    optimizer)`` as ``_grad_fn`` and ``_apply_fn`` describe them.
    ``apply_step`` clips the *exchanged mean*, as one learner with the
    concatenated batch would, and every replica applies bit-identical
    updates because all clip the same broadcast buffer."""
    optimizer = _optimizer(cfg, optimizer)
    grad_fn = _grad_fn(build_loss_fn(arch_cfg, cfg, num_actions,
                                     vtrace_impl))
    return grad_fn, _apply_fn(cfg, optimizer), optimizer


def opt_state_specs(param_specs: PyTree, cfg: ImpalaConfig,
                    mixed_precision: bool = False) -> PyTree:
    """Spec tree for the optimizer state (mirrors param specs)."""
    inner = ({"ms": param_specs, "mom": param_specs}
             if cfg.rmsprop_momentum else {"ms": param_specs})
    if mixed_precision:
        return {"opt": inner, "master": param_specs}
    return inner
