"""Process-backend actors end to end: the same runtime, loop body, and
telemetry as the thread backend, with trajectories crossing a real
serialized boundary — plus the serialized parameter subscribe path and
the backend/transport validation rules."""
import multiprocessing as mp
import time

import numpy as np
import pytest

from repro.configs.base import ImpalaConfig
from repro.distributed import ParameterStore, run_async_training
from repro.distributed import serde


def _icfg(**kw):
    base = dict(num_actions=3, unroll_length=8, learning_rate=1e-3,
                entropy_cost=0.003, rmsprop_eps=0.01)
    base.update(kw)
    return ImpalaConfig(**base)


# ---------------------------------------------------------------------------
# ParameterStore serialized pub/sub (no processes needed)


def test_paramstore_pull_serialized_is_version_gated_and_cached():
    store = ParameterStore({"w": np.arange(4, dtype=np.float32)})
    got = store.pull_serialized(have_version=-1)
    assert got is not None
    buf, version = got
    assert version == 0
    tree, _ = serde.decode_tree(buf)
    assert tree["w"].tobytes() == np.arange(4, dtype=np.float32).tobytes()
    # current subscriber: nothing newer -> cheap None, no re-encode
    assert store.pull_serialized(have_version=0) is None
    n_encodes = store.serialized_encodes
    # second stale subscriber hits the per-version cache
    buf2, v2 = store.pull_serialized(have_version=-1)
    assert v2 == 0 and buf2 == buf
    assert store.serialized_encodes == n_encodes
    # publish invalidates: next pull re-encodes exactly once
    store.publish({"w": np.zeros(4, np.float32)})
    buf3, v3 = store.pull_serialized(have_version=0)
    assert v3 == 1 and buf3 != buf
    assert store.serialized_encodes == n_encodes + 1


# ---------------------------------------------------------------------------
# validation


def test_process_backend_requires_serializing_transport():
    with pytest.raises(ValueError, match="shm"):
        run_async_training("bandit", _icfg(), num_envs=4, steps=1,
                           actor_backend="process", transport="inproc")
    with pytest.raises(ValueError, match="actor_backend"):
        run_async_training("bandit", _icfg(), num_envs=4, steps=1,
                           actor_backend="fiber")


# ---------------------------------------------------------------------------
# thread backend over the serialized transport: every byte of the serde
# boundary without process startup cost


@pytest.mark.timeout_s(300)
def test_thread_actors_over_shm_transport_train():
    tracker, metrics, tel = run_async_training(
        "bandit", _icfg(), num_envs=4, steps=8, num_actors=2,
        actor_backend="thread", transport="shm",
        queue_capacity=4, queue_policy="block", max_batch_trajs=2, seed=3)
    assert tel["learner_updates"] == 8
    assert np.isfinite(float(metrics["loss/total"]))
    q = tel["queue"]
    assert q["transport"] == "shm"
    assert q["wire_received"] >= 8 and q["wire_bytes"] > 0
    assert tel["lag"]["measured"] >= 8


# ---------------------------------------------------------------------------
# process backend


@pytest.mark.timeout_s(300)
def test_process_actors_train_and_close_cleanly():
    t0 = time.monotonic()
    tracker, metrics, tel = run_async_training(
        "bandit", _icfg(), num_envs=4, steps=6, num_actors=2,
        actor_backend="process", transport="shm",
        queue_capacity=4, queue_policy="block", max_batch_trajs=2, seed=0)
    assert tel["learner_updates"] == 6
    assert tel["param_version"] == 6
    assert np.isfinite(float(metrics["loss/total"]))
    assert tel["actors"]["backend"] == "process"
    assert tel["actors"]["trajectories"] >= 6
    assert tel["queue"]["wire_received"] >= 6
    assert tel["lag"]["measured"] >= 6
    # clean shutdown: no orphaned actor process may outlive the run
    deadline = time.monotonic() + 30
    while mp.active_children() and time.monotonic() < deadline:
        time.sleep(0.2)
    assert mp.active_children() == [], (
        f"orphans after {time.monotonic() - t0:.0f}s")


@pytest.mark.timeout_s(540)
def test_thread_and_process_backends_both_learn_on_catch():
    """Acceptance: the same catch run through both backends. Each must
    show real learning — the late-episode return far above the early
    (near-random) window — and identical learner-side accounting."""
    from repro.configs.registry import get_smoke_config
    from repro.data.envs import make_catch

    env = make_catch()
    arch = get_smoke_config("impala-shallow").replace(image_hw=env.image_hw)
    cfg = ImpalaConfig(num_actions=env.num_actions, unroll_length=20,
                       learning_rate=6e-4, entropy_cost=0.003,
                       rmsprop_eps=0.01)
    results = {}
    for backend, transport in (("thread", "inproc"), ("process", "shm")):
        tracker, metrics, tel = run_async_training(
            "catch", cfg, num_envs=32, steps=400, num_actors=2,
            actor_backend=backend, transport=transport,
            queue_capacity=8, queue_policy="block", max_batch_trajs=4,
            seed=0, arch=arch)
        returns = tracker.completed
        early = float(np.mean(returns[:500]))
        late = float(np.mean(returns[-100:]))
        results[backend] = (early, late, tel)
        assert tel["learner_updates"] == 400, backend
        assert tel["param_version"] == 400, backend
        assert np.isfinite(float(metrics["loss/total"])), backend
        assert tel["lag"]["max"] > 0, (backend, tel["lag"])

    for backend, (early, late, tel) in results.items():
        # random play on catch is ~-0.6; require a decisive climb
        assert late > early + 0.15, (backend, early, late)
        assert late > -0.3, (backend, early, late)
    # the serialized run really crossed the wire
    assert results["process"][2]["queue"]["wire_received"] > 0


def test_use_host_cpu_overrides_the_inherited_platform():
    """Spawned actor children inherit the learner's environment, which
    on a chip host names the accelerator; ``use_host_cpu`` (the first
    thing every child runs) must still put the child's JAX on the CPU,
    leaving the chip to the learner."""
    import os
    import subprocess
    import sys

    code = ("from repro.distributed.runner import use_host_cpu\n"
            "use_host_cpu()\n"
            "import jax\n"
            "print(jax.devices()[0].platform)\n")
    env = dict(os.environ, JAX_PLATFORMS="tpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "cpu"
