"""The one call site of ``jax.shard_map``.

Every ``shard_map`` of this repo (the SPMD learner steps in
``core/learner.py``, the expert-parallel MoE in ``models/moe.py``) goes
through :func:`shard_map` below, so a change of the API or of its
defaults lands in one place.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None):
    """``jax.shard_map``; ``check_vma`` is forwarded only when given, so
    jax keeps its own default otherwise."""
    kwargs = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)
