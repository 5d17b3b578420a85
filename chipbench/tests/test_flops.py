"""Each configuration's operation count against XLA's own count of the
program's forward and backward passes, on the CPU at the published
72x96 frame: one observation per row, so the LSTM's scan runs one step
and XLA, which counts a loop body once, counts it all."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from chipbench import reference, run


@pytest.mark.parametrize("config", ["impala-shallow-72x96",
                                    "impala-deep-72x96"])
def test_flops_agree_with_xla(config):
    from repro.core import learner as learner_lib

    cfg = run.load_json("configs", config + ".json")
    flops = run.load_module("flops", config + ".py")
    arch, _icfg = run.program_configs({"config": cfg})
    rows, a, w = 2, cfg["num_actions"], cfg["lstm_width"]
    batch = {"obs_image": jnp.zeros((rows, 1, *cfg["frame"]), jnp.uint8),
             "last_action": jnp.zeros((rows, 1), jnp.int32),
             "last_reward": jnp.zeros((rows, 1)),
             "done_in": jnp.zeros((rows, 1), bool),
             "lstm_state": (jnp.zeros((rows, w)), jnp.zeros((rows, w)))}
    params = reference.init_params(cfg, 0)

    def forward(p, b):
        logits, values, _ = learner_lib.forward_trajectory(p, b, arch, a)
        return jnp.sum(logits) + jnp.sum(values)

    def xla_flops(fn):
        cost = jax.jit(fn).lower(params, batch).compile().cost_analysis()
        return cost["flops"] / rows

    # XLA also counts the elementwise work the analytic count leaves out
    assert flops.forward_flops(cfg) == pytest.approx(
        xla_flops(forward), rel=0.05)
    assert flops.train_flops(cfg) == pytest.approx(
        xla_flops(jax.grad(forward)), rel=0.05)
