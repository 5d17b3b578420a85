"""The training CLI as a library call: ``main(argv, on_update)`` runs in
the caller's process (the way ``chip_smoke.py`` drives a chip) and the
telemetry names the V-trace implementation that ran; the compile cache
honours ``JAX_COMPILATION_CACHE_DIR`` and stays off the CPU backend."""
import json

import jax
import numpy as np
import pytest

from repro.launch import train


@pytest.mark.timeout_s(300)
def test_main_runs_in_process_and_reports_vtrace_impl(tmp_path):
    seen = []

    def on_update(step, params, metrics, snapshot_fn):
        seen.append((step, float(metrics["loss/total"])))

    tel_path = tmp_path / "tel.json"
    rc = train.main(["--runtime", "async", "--env", "bandit", "--smoke",
                     "--steps", "3", "--unroll", "4", "--num-envs", "4",
                     "--actor-threads", "2", "--vtrace-impl", "fused",
                     "--telemetry-json", str(tel_path)],
                    on_update=on_update)
    assert rc == 0
    assert [s for s, _ in seen] == [1, 2, 3]
    assert all(np.isfinite(loss) for _, loss in seen)
    tel = json.loads(tel_path.read_text())
    assert tel["learner_updates"] == 3
    # off a TPU the fused kernel runs in the Pallas interpreter, and
    # the telemetry says so
    assert tel["vtrace"] == {"impl": "fused",
                             "interpret": jax.default_backend() != "tpu"}


def test_compile_cache_honours_env_and_stays_off_cpu(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert train.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before   # JAX's to use
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    if jax.default_backend() == "tpu":
        assert train.enable_compile_cache() == train.CACHE_DIR
    else:
        assert train.enable_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == before
