"""The agent's parameter count, from the configuration file alone: the
sizes of the leaves ``reference.init_params`` builds."""
from __future__ import annotations

import math
from typing import Dict

import jax

from chipbench import reference


def count(cfg: Dict) -> int:
    shapes = jax.tree.leaves(reference.param_shapes(cfg),
                             is_leaf=reference._is_shape)
    return sum(math.prod(s) for s in shapes)
