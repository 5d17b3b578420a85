"""The dynamic-batching inference service (paper §3.1): bucketing and
flush-reason mechanics, thread- and process-backend training end to end,
service telemetry, and the acceptance bar — both backends must *learn*
catch through the service with measured policy lag still populated."""
import multiprocessing as mp
import time

import numpy as np
import pytest

from repro.configs.base import ImpalaConfig
from repro.core.driver import small_arch
from repro.data.envs import make_bandit, make_catch
from repro.distributed import ParameterStore, run_async_training
from repro.distributed.inference import InferenceService, _pow2_floor
from repro.models import common as pcommon
from repro.models import backbone as bb


def _icfg(**kw):
    base = dict(num_actions=3, unroll_length=8, learning_rate=1e-3,
                entropy_cost=0.003, rmsprop_eps=0.01)
    base.update(kw)
    return ImpalaConfig(**base)


# ---------------------------------------------------------------------------
# service unit behaviour (no runtime)


def test_pow2_floor():
    assert [_pow2_floor(n) for n in (1, 2, 3, 4, 5, 7, 8, 9)] == \
        [1, 2, 2, 4, 4, 4, 8, 8]


def _make_service(num_clients=2, flush_timeout_s=0.5, num_envs=3):
    env = make_bandit()
    arch = small_arch(env)
    icfg = _icfg(num_actions=env.num_actions)
    specs = bb.backbone_specs(arch, env.num_actions)
    import jax
    params = pcommon.init_params(specs, jax.random.key(0))
    store = ParameterStore(params)
    svc = InferenceService(env, arch, icfg, store,
                           num_clients=num_clients,
                           flush_timeout_s=flush_timeout_s, seed=0)
    return svc, arch, num_envs


def _request(num_envs, width, hw):
    return {
        "obs_image": np.zeros((num_envs,) + hw, np.uint8),
        "last_action": np.zeros((num_envs,), np.int32),
        "last_reward": np.zeros((num_envs,), np.float32),
        "done": np.zeros((num_envs,), bool),
        "lstm_h": np.zeros((num_envs, width), np.float32),
        "lstm_c": np.zeros((num_envs, width), np.float32),
    }


def test_service_rejects_token_backbones():
    env = make_bandit()
    from repro.configs.registry import get_smoke_config
    arch = get_smoke_config("stablelm-1.6b")
    store = ParameterStore({"w": np.zeros(1, np.float32)})
    with pytest.raises(ValueError, match="unroll"):
        InferenceService(env, arch, _icfg(), store, num_clients=1)


@pytest.mark.timeout_s(120)
def test_service_full_bucket_flush_and_reply_slicing():
    """Two clients, long flush timeout: replies must arrive via a *full*
    (or all-clients-ready) flush, not the timeout path, and each client
    must get exactly its own slice back."""
    svc, arch, n = _make_service(num_clients=2, flush_timeout_s=10.0)
    svc.start()
    try:
        c1, c2 = svc.connect(), svc.connect()
        req = _request(n, arch.lstm_width, make_bandit().image_hw)
        import threading
        out = {}

        def call(name, client):
            out[name] = client.infer(req)

        t1 = threading.Thread(target=call, args=("a", c1))
        t2 = threading.Thread(target=call, args=("b", c2))
        t1.start(); t2.start(); t1.join(30); t2.join(30)
        ra, rb = out["a"], out["b"]
        assert ra is not None and rb is not None
        assert np.asarray(ra.action).shape == (n,)
        assert np.asarray(ra.logprob).dtype == np.float32
        assert np.asarray(ra.lstm_state[0]).shape == (n, arch.lstm_width)
        assert ra.param_version == 0 and rb.param_version == 0
        snap = svc.snapshot()
        assert snap["flush_timeout"] == 0
        assert snap["flush_full"] + snap["flush_ready"] >= 1
        assert snap["batch_size_hist"].get(2) == 1
        assert snap["requests"] == 2 and snap["frames"] == 2 * n
    finally:
        svc.stop()


@pytest.mark.timeout_s(120)
def test_service_single_straggler_flushes_without_timeout_stall():
    """One connected client: its lone request is a 'ready' flush (every
    possible requester is in) — it must not wait out a long timeout."""
    svc, arch, n = _make_service(num_clients=4, flush_timeout_s=30.0)
    svc.start()
    try:
        c = svc.connect()
        req = _request(n, arch.lstm_width, make_bandit().image_hw)
        t0 = time.monotonic()
        r = c.infer(req)
        dt = time.monotonic() - t0
        assert r is not None
        assert dt < 10.0, f"lone request stalled {dt:.1f}s behind timeout"
        assert svc.snapshot()["flush_ready"] >= 1
    finally:
        svc.stop()


@pytest.mark.timeout_s(120)
def test_service_stop_unblocks_clients():
    svc, arch, n = _make_service(num_clients=8, flush_timeout_s=30.0)
    svc.start()
    c = svc.connect()
    c2 = svc.connect()          # 2 connected, so 1 pending is not "ready"
    del c2
    req = _request(n, arch.lstm_width, make_bandit().image_hw)
    import threading
    got = []
    t = threading.Thread(target=lambda: got.append(c.infer(req)))
    t.start()
    time.sleep(0.3)
    svc.stop()
    t.join(15)
    assert not t.is_alive()
    assert got == [None]
    # submits after shutdown are refused outright
    assert c.infer(req) is None


# ---------------------------------------------------------------------------
# where a reply lives: device rows for a request of device arrays, numpy
# rows for a numpy request, the same numbers either way


def _random_request(rng, n, width, hw):
    return {
        "obs_image": rng.integers(0, 256, (n,) + hw).astype(np.uint8),
        "last_action": rng.integers(0, 3, n).astype(np.int32),
        "last_reward": rng.standard_normal(n).astype(np.float32),
        "done": rng.random(n) < 0.3,
        "lstm_h": rng.standard_normal((n, width)).astype(np.float32),
        "lstm_c": rng.standard_normal((n, width)).astype(np.float32),
    }


def _flush_once(svc, reqs):
    """Submit ``reqs`` and flush them all in one program, as the thread
    driver does; the replies in submission order."""
    waiters = [svc.submit_async(r) for r in reqs]
    svc.drive_flushes()
    assert all(w.event.is_set() for w in waiters)
    return [w.slot[0] for w in waiters]


def _reply_leaves(r):
    return [r.action, r.logprob, r.lstm_state[0], r.lstm_state[1]]


def _assert_same_reply(got, want):
    assert got.param_version == want.param_version
    for x, y in zip(_reply_leaves(got), _reply_leaves(want)):
        x, y = np.asarray(x), np.asarray(y)
        assert (x.shape, x.dtype) == (y.shape, y.dtype)
        assert x.tobytes() == y.tobytes()


@pytest.mark.timeout_s(180)
@pytest.mark.parametrize("k", [1, 2, 8, 3],
                         ids=["bucket1", "bucket2", "bucket8", "padded3of4"])
def test_device_requests_get_device_replies_equal_to_numpy_replies(k):
    """The same requests, once as numpy and once as device arrays, to
    two services with the same seed and params, each in its first
    flush (so the same ``seq``): the device replies are device arrays,
    the numpy replies numpy, and they agree bit for bit."""
    import jax

    host_svc, arch, n = _make_service(num_clients=8)
    dev_svc, _, _ = _make_service(num_clients=8)
    rng = np.random.default_rng(k)
    reqs = [_random_request(rng, n, arch.lstm_width, make_bandit().image_hw)
            for _ in range(k)]
    try:
        host = _flush_once(host_svc, reqs)
        dev = _flush_once(dev_svc, [jax.device_put(r) for r in reqs])
    finally:
        host_svc.stop()
        dev_svc.stop()
    for h, d in zip(host, dev):
        assert all(isinstance(x, np.ndarray) for x in _reply_leaves(h))
        assert all(isinstance(x, jax.Array) for x in _reply_leaves(d))
        assert np.asarray(d.action).shape == (n,)
        assert np.asarray(d.lstm_state[1]).shape == (n, arch.lstm_width)
        _assert_same_reply(d, h)
    # every request its own rows: no two clients were handed the same
    assert len({np.asarray(d.lstm_state[0]).tobytes() for d in dev}) == k
    bucket = 4 if k == 3 else k
    for svc, device_replies in ((host_svc, 0), (dev_svc, k)):
        snap = svc.snapshot()
        assert snap["device_replies"] == device_replies
        assert snap["host_replies"] == k - device_replies
        assert snap["device_replies"] + snap["host_replies"] == \
            snap["requests"] == k
        assert snap["padded_requests"] == bucket - k
        assert snap["batch_size_hist"] == {k: 1}


@pytest.mark.timeout_s(180)
def test_mixed_flush_gives_each_client_its_own_kind_of_reply():
    """One flush holding numpy and device requests: each client gets
    its reply where its request lived, with the numbers an all-numpy
    flush gives, and the counters split the requests between them."""
    import jax

    mixed_svc, arch, n = _make_service(num_clients=8)
    host_svc, _, _ = _make_service(num_clients=8)
    rng = np.random.default_rng(5)
    reqs = [_random_request(rng, n, arch.lstm_width, make_bandit().image_hw)
            for _ in range(4)]
    on_device = [False, True, True, False]
    try:
        mixed = _flush_once(mixed_svc, [jax.device_put(r) if d else r
                                        for r, d in zip(reqs, on_device)])
        host = _flush_once(host_svc, reqs)
    finally:
        mixed_svc.stop()
        host_svc.stop()
    for m, h, d in zip(mixed, host, on_device):
        kind = jax.Array if d else np.ndarray
        assert all(isinstance(x, kind) for x in _reply_leaves(m)), d
        _assert_same_reply(m, h)
    snap = mixed_svc.snapshot()
    assert (snap["device_replies"], snap["host_replies"]) == (2, 2)
    assert snap["requests"] == 4


# ---------------------------------------------------------------------------
# end to end through the runtime, both backends


@pytest.mark.timeout_s(300)
def test_thread_inference_actors_train():
    tracker, metrics, tel = run_async_training(
        "bandit", _icfg(), num_envs=4, steps=8, num_actors=2,
        actor_mode="inference", queue_capacity=4, queue_policy="block",
        max_batch_trajs=2, seed=3)
    assert tel["learner_updates"] == 8
    assert np.isfinite(float(metrics["loss/total"]))
    assert tel["actor_mode"] == "inference"
    inf = tel["inference"]
    assert inf["flushes"] > 0
    assert sum(inf["batch_size_hist"].values()) == inf["flushes"]
    assert inf["requests"] >= 8 * _icfg().unroll_length
    assert inf["queue_wait_ms_p95"] >= inf["queue_wait_ms_p50"] >= 0.0
    assert tel["lag"]["measured"] >= 8


@pytest.mark.timeout_s(300)
def test_inference_mode_requires_cnn_family():
    from repro.configs.registry import get_smoke_config
    arch = get_smoke_config("stablelm-1.6b")
    with pytest.raises(ValueError, match="unroll"):
        run_async_training("bandit", _icfg(), num_envs=4, steps=1,
                           actor_mode="inference", arch=arch)
    with pytest.raises(ValueError, match="actor_mode"):
        run_async_training("bandit", _icfg(), num_envs=4, steps=1,
                           actor_mode="batched")


@pytest.mark.timeout_s(300)
def test_process_inference_actors_train_and_close_cleanly():
    t0 = time.monotonic()
    tracker, metrics, tel = run_async_training(
        "bandit", _icfg(), num_envs=4, steps=6, num_actors=2,
        actor_backend="process", actor_mode="inference", transport="shm",
        queue_capacity=4, queue_policy="block", max_batch_trajs=2, seed=0)
    assert tel["learner_updates"] == 6
    assert np.isfinite(float(metrics["loss/total"]))
    assert tel["actors"]["backend"] == "process"
    assert tel["queue"]["wire_received"] >= 6
    # the children's leaves are numpy: every trajectory host-assembled
    assert tel["actors"]["assembled_on_device"] == 0
    assert tel["actors"]["assembled_on_host"] == \
        tel["actors"]["trajectories"] >= 6
    assert tel["inference"]["flushes"] > 0
    assert tel["lag"]["measured"] >= 6
    # clean shutdown: no orphaned actor process may outlive the run
    deadline = time.monotonic() + 30
    while mp.active_children() and time.monotonic() < deadline:
        time.sleep(0.2)
    assert mp.active_children() == [], (
        f"orphans after {time.monotonic() - t0:.0f}s")


@pytest.mark.timeout_s(540)
def test_inference_mode_learns_on_catch_both_backends():
    """Acceptance: the same catch run through the inference service with
    thread and with process clients. Each must show real learning (the
    bar of test_process_actors.py) and still-measured policy lag."""
    env = make_catch()
    arch = small_arch(env)
    cfg = ImpalaConfig(num_actions=env.num_actions, unroll_length=20,
                       learning_rate=6e-4, entropy_cost=0.003,
                       rmsprop_eps=0.01)
    results = {}
    for backend, transport in (("thread", "inproc"), ("process", "shm")):
        tracker, metrics, tel = run_async_training(
            "catch", cfg, num_envs=32, steps=400, num_actors=2,
            actor_backend=backend, actor_mode="inference",
            transport=transport, queue_capacity=8, queue_policy="block",
            max_batch_trajs=4, seed=0, arch=arch)
        returns = tracker.completed
        early = float(np.mean(returns[:500]))
        late = float(np.mean(returns[-100:]))
        results[backend] = (early, late, tel)
        assert tel["learner_updates"] == 400, backend
        assert np.isfinite(float(metrics["loss/total"])), backend
        assert tel["lag"]["measured"] > 0, (backend, tel["lag"])
        assert tel["inference"]["flushes"] > 0, backend

    for backend, (early, late, tel) in results.items():
        # random play on catch is ~-0.6; require a decisive climb
        assert late > early + 0.15, (backend, early, late)
        assert late > -0.3, (backend, early, late)
    # the serialized run really crossed both wires
    assert results["process"][2]["queue"]["wire_received"] > 0
    assert results["process"][2]["inference"]["requests"] > 0
