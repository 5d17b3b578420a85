"""The harness end to end on the CPU: it refuses to measure without a
TPU, and with the device check skipped it drives a cell cut to a tiny
size through the program's runtime and decides ``correct`` — true for
the program as it is, false with the timed path broken underneath."""
from __future__ import annotations

import os
import subprocess
import sys

from chipbench import run
from chipbench.tests import tiny

SEED = 2 ** 31 + 977


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         "shallow-infer", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def run_tiny(limits=None):
    """A run of the tiny cell; faults are judged by the committed cell's
    own limits (``limits="cell"``), sound runs by the tiny size's."""
    f = tiny.files(*tiny.SHALLOW)
    if limits == "cell":
        f["limits"] = run.load_json("limits", "shallow-infer.json")
    return run.run_cell(f, SEED, 0.5, False, tiny.DEVICE)


def test_sound_run_is_correct():
    res = run_tiny()
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"frames_per_s", "publish_gap_ms_p95",
                                   "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0


def _wrap_train_step(monkeypatch, wrap):
    from repro.core import learner as learner_lib

    real = learner_lib.build_train_step

    def build(*a, **kw):
        step, opt = real(*a, **kw)
        return wrap(step), opt

    monkeypatch.setattr(learner_lib, "build_train_step", build)


def test_step_that_returns_its_state_unchanged_is_caught(monkeypatch):
    def wrap(step):
        def unchanged(params, opt_state, i, batch):
            _p, _o, metrics = step(params, opt_state, i, batch)
            return params, opt_state, metrics
        return unchanged

    _wrap_train_step(monkeypatch, wrap)
    res = run_tiny("cell")
    assert not res["correct"]
    assert res["checks"]["update_gap"]["value"] > 0.5


def test_half_the_batch_left_out_is_caught(monkeypatch):
    import jax
    import jax.numpy as jnp

    def wrap(step):
        def half(params, opt_state, i, batch):
            n = batch["actions"].shape[0] // 2
            batch = jax.tree.map(
                lambda x: jnp.concatenate([x[:n], x[:n]]), batch)
            return step(params, opt_state, i, batch)
        return half

    # judged by the tiny size's limits: the cell's own do not catch it
    # on the chip yet (PERF.md, Open questions 1)
    _wrap_train_step(monkeypatch, wrap)
    res = run_tiny()
    assert not res["correct"]
    assert res["checks"]["update_diff"]["value"] > \
        res["checks"]["update_diff"]["limit"]


def test_action_altered_where_it_is_produced_is_caught(monkeypatch):
    from repro.distributed.inference import InferenceService

    real = InferenceService._build_flush

    def build(self, k):
        flush = real(self, k)

        def altered(params, seq, reqs):
            action, logp, h, c = flush(params, seq, reqs)
            action = action.at[0].set((action[0] + 1) % self._num_actions)
            return action, logp, h, c
        return altered

    monkeypatch.setattr(InferenceService, "_build_flush", build)
    res = run_tiny("cell")
    assert not res["correct"]
    assert res["checks"]["act_gap"]["value"] > \
        res["checks"]["act_gap"]["limit"]
