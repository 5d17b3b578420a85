"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — only ``launch/dryrun.py`` sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.configs.base import MeshConfig
from repro.sharding.rules import Rules


def auto_mesh(shape, axes, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``. The installed jax
    defaults to ``Explicit`` axes, which ``with_sharding_constraint``
    (``sharding/rules.py``) and the mesh-wide device assignment of
    ``device_put`` refuse; every mesh of this repo is built here.
    ``devices`` (e.g. a described topology's) defaults to the local
    pool."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_mesh_2d_tp(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """§Perf variant: split the 16-way model axis into 4x4 so head counts
    divisible by 4 (qwen 20H, recurrentgemma/whisper) shard on model_a
    while ffn/vocab use the full 16 = model_a x model_b."""
    shape = (2, 16, 4, 4) if multi_pod else (16, 4, 4)
    axes = (("pod", "data", "model_a", "model_b") if multi_pod
            else ("data", "model_a", "model_b"))
    return auto_mesh(shape, axes)


def make_mesh(cfg: MeshConfig) -> jax.sharding.Mesh:
    return auto_mesh(cfg.shape, cfg.axis_names)


def make_data_mesh(num_devices: int) -> jax.sharding.Mesh:
    """1-D ``('data',)`` mesh over the first ``num_devices`` local
    devices — the SPMD data-parallel learner topology (batch sharded on
    the trajectory axis, params/opt replicated, gradients psum'd).
    On CPU the device pool is grown with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set before
    the first jax import (the ``launch/dryrun.py`` precedent)."""
    avail = len(jax.devices())
    if num_devices < 1 or num_devices > avail:
        raise ValueError(
            f"spmd mesh needs 1..{avail} devices, got {num_devices} "
            f"(on CPU, set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={num_devices} before the first jax import)")
    return auto_mesh((num_devices,), ("data",))


def make_rules(mesh: jax.sharding.Mesh, overrides=None) -> Rules:
    return Rules(mesh, overrides)


# TPU v5e hardware constants used by the roofline analysis.
HW = {
    "peak_flops_bf16": 197e12,   # FLOP/s per chip
    "hbm_bw": 819e9,             # B/s per chip
    "ici_bw": 50e9,              # B/s per link
}
