"""Inference-trajectory assembly on both paths: where the env outputs are
device arrays, ``assemble_inference_traj`` stacks them on the device in
one program; where they are numpy, on the host. The two must give the
same tree — keys in order, shapes, dtypes and every bit — and the
actor pools count which path each trajectory took. The thread driver,
whose replies stay on the device, must act out the trajectories of a
host round trip per step, and a warm acting cycle of it must copy
nothing to the host."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ImpalaConfig
from repro.core.driver import small_arch
from repro.data.envs import make_catch
from repro.distributed import run_async_training, runner, serde
from repro.distributed.runner import assemble_inference_traj

B, T, HW = 4, 6, (5, 7, 3)
DEVICE_KEYS = ("rewards", "discounts", "done", "obs_image", "last_reward",
               "done_in")


def _icfg(**kw):
    base = dict(num_actions=3, unroll_length=8, learning_rate=1e-3,
                entropy_cost=0.003, rmsprop_eps=0.01)
    base.update(kw)
    return ImpalaConfig(**base)


def _records(seed, reply_leaves_on_device):
    """One unroll's records as the acting loops write them: each step's
    reward/done/action become the next step's inputs. Frames, rewards
    and dones are device arrays (the thread driver's env outputs); the
    reply leaves are numpy unless ``reply_leaves_on_device``. The
    carried-in inputs are numpy, as on an actor's first unroll, but hold
    what a previous unroll would leave. Env 0 ends an episode at step 2,
    env 1 at steps 2 and 3, env 2 ended one in the previous unroll."""
    rng = np.random.default_rng(seed)
    reply = jnp.asarray if reply_leaves_on_device else (lambda x: x)
    dones = rng.random((T, B)) < 0.3
    dones[2, :2] = True
    dones[3, 1] = True
    obs = jnp.asarray(rng.integers(0, 256, (B,) + HW).astype(np.uint8))
    last_action = rng.integers(0, 3, B).astype(np.int32)
    last_reward = rng.standard_normal(B).astype(np.float32)
    done = np.arange(B) == 2
    steps = []
    for t in range(T):
        action = reply(rng.integers(0, 3, B).astype(np.int32))
        reward = jnp.asarray(rng.standard_normal(B).astype(np.float32))
        step_done = jnp.asarray(dones[t])
        steps.append({
            "obs_image": obs, "last_action": last_action,
            "last_reward": last_reward, "done_in": done,
            "action": action, "reward": reward, "done": step_done,
            "behaviour_logprob": reply(
                rng.standard_normal(B).astype(np.float32))})
        obs = jnp.asarray(rng.integers(0, 256, (B,) + HW).astype(np.uint8))
        last_action, last_reward, done = action, reward, step_done
    boot = {"obs_image": obs, "last_action": last_action,
            "last_reward": last_reward, "done": done}
    lstm = (reply(rng.standard_normal((B, 8)).astype(np.float32)),
            reply(rng.standard_normal((B, 8)).astype(np.float32)))
    return steps, boot, lstm


def _assert_bit_identical(a, b, path="$"):
    if isinstance(a, dict):
        assert list(a) == list(b), path
        for k in a:
            _assert_bit_identical(a[k], b[k], f"{path}/{k}")
        return
    if isinstance(a, tuple):
        assert isinstance(b, tuple) and len(a) == len(b), path
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_bit_identical(x, y, f"{path}[{i}]")
        return
    x, y = np.asarray(a), np.asarray(b)
    assert (x.shape, x.dtype) == (y.shape, y.dtype), (path, x.shape,
                                                      x.dtype, y.shape,
                                                      y.dtype)
    assert x.tobytes() == y.tobytes(), path


@pytest.mark.parametrize("reply_leaves_on_device", [False, True],
                         ids=["numpy-replies", "device-replies"])
def test_device_assembly_matches_host_assembly(reply_leaves_on_device):
    steps, boot, lstm = _records(7, reply_leaves_on_device)
    icfg = _icfg()
    dev = assemble_inference_traj(steps, boot, lstm, icfg)
    host = assemble_inference_traj(jax.tree.map(np.asarray, steps),
                                   jax.tree.map(np.asarray, boot),
                                   jax.tree.map(np.asarray, lstm), icfg)
    _assert_bit_identical(dev, host)
    for k in DEVICE_KEYS:
        assert isinstance(dev[k], jax.Array), k
    assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(host))

    # the layout itself, across the episode boundaries of envs 0 and 1
    assert dev["obs_image"].shape == (B, T + 1) + HW
    rewards, done = np.asarray(host["rewards"]), np.asarray(host["done"])
    assert done[0, 2] and done[1, 2] and done[1, 3]
    np.testing.assert_array_equal(host["last_reward"][:, 0],
                                  steps[0]["last_reward"])
    np.testing.assert_array_equal(host["last_reward"][:, 1:], rewards)
    np.testing.assert_array_equal(host["done_in"][:, 0],
                                  np.arange(B) == 2)
    np.testing.assert_array_equal(host["done_in"][:, 1:], done)
    assert host["done_in"][0, 3] and host["done_in"][1, 4]
    np.testing.assert_array_equal(
        host["discounts"],
        np.where(done, np.float32(0.0), np.float32(icfg.discount)))
    np.testing.assert_array_equal(host["last_action"][:, 1:],
                                  host["actions"])


def test_device_assembly_forces_no_env_output_to_host(monkeypatch):
    """The thread driver's case (env outputs and replies on the
    device): no device leaf goes through ``np.asarray``, and every
    column stays on the device."""
    steps, boot, lstm = _records(11, reply_leaves_on_device=True)
    converted = []
    real = np.asarray

    def tattle(x, *a, **kw):
        if isinstance(x, jax.Array):
            converted.append(x.shape)
        return real(x, *a, **kw)

    monkeypatch.setattr(np, "asarray", tattle)
    traj = assemble_inference_traj(steps, boot, lstm, _icfg())
    assert converted == [], converted
    np.asarray(steps[0]["reward"])      # the spy does see a conversion
    monkeypatch.undo()
    assert converted == [(B,)]
    assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(traj))


def test_device_assembled_trajectory_crosses_the_wire_like_a_host_one():
    """A thread inference actor over the ``shm`` transport hands device
    trajectories to serde: the encoded bytes are those of the host
    assembly, and the receiver gets numpy."""
    steps, boot, lstm = _records(13, reply_leaves_on_device=False)
    icfg = _icfg()
    dev = assemble_inference_traj(steps, boot, lstm, icfg)
    host = assemble_inference_traj(jax.tree.map(np.asarray, steps),
                                   jax.tree.map(np.asarray, boot), lstm,
                                   icfg)
    enc_dev = serde.encode_item(serde.TrajectoryItem(dev, 3, 1, 5.0))
    enc_host = serde.encode_item(serde.TrajectoryItem(host, 3, 1, 5.0))
    assert enc_dev == enc_host
    out = serde.decode_item(enc_dev)
    _assert_bit_identical(out.data, host)
    assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(out.data))


@pytest.mark.timeout_s(300)
def test_thread_inference_run_assembles_on_device(monkeypatch):
    """A thread-mode inference run on catch (episodes end inside
    unrolls) with learner batches of 2, so the learner concatenates
    device trajectories: it trains and completes episodes, every
    trajectory is assembled on the device, and each equals the host
    assembly of the same records."""
    pairs = []
    real = runner.assemble_inference_traj

    def both(steps, boot, init_lstm, icfg):
        pairs.append((real(steps, boot, init_lstm, icfg),
                      real(jax.tree.map(np.asarray, steps),
                           jax.tree.map(np.asarray, boot), init_lstm,
                           icfg)))
        return pairs[-1][0]

    monkeypatch.setattr(runner, "assemble_inference_traj", both)
    env = make_catch()
    tracker, metrics, tel = run_async_training(
        "catch", _icfg(num_actions=env.num_actions, unroll_length=12),
        num_envs=4, steps=8, num_actors=2, actor_backend="thread",
        actor_mode="inference", transport="inproc", queue_capacity=4,
        queue_policy="block", max_batch_trajs=2, batch_linger_s=5.0,
        seed=5, arch=small_arch(env))
    assert tel["learner_updates"] == 8
    assert np.isfinite(float(metrics["loss/total"]))
    assert 2 in tel["batch_size_hist"], tel["batch_size_hist"]
    assert tracker.completed, "no episode completed"
    actors = tel["actors"]
    assert actors["assembled_on_host"] == 0
    assert actors["assembled_on_device"] >= actors["trajectories"] >= 8
    assert pairs
    for dev, host in pairs:
        _assert_bit_identical(dev, host)
        for k in DEVICE_KEYS:
            assert isinstance(dev[k], jax.Array), k
    assert any(np.asarray(host["done"]).any() for _, host in pairs)


@pytest.mark.timeout_s(300)
def test_serialized_inference_loop_emits_numpy():
    """The loop process and remote inference actors run, driven here
    in-process against the service: every leaf it emits is numpy."""
    from repro.data.envs import make_bandit
    from repro.distributed import ParameterStore
    from repro.distributed.inference import InferenceService
    from repro.models import backbone as bb
    from repro.models import common as pcommon

    env = make_bandit()
    arch = small_arch(env)
    icfg = _icfg(num_actions=env.num_actions, unroll_length=5)
    params = pcommon.init_params(bb.backbone_specs(arch, env.num_actions),
                                 jax.random.key(0))
    svc = InferenceService(env, arch, icfg, ParameterStore(params),
                           num_clients=2, flush_timeout_s=0.05, seed=0)
    svc.start()
    items = []

    def emit(item):
        items.append(item)
        return len(items) < 2

    try:
        runner.run_inference_actor_loop(
            actor_id=0, env=env, arch_cfg=arch, icfg=icfg, num_envs=4,
            seed=0, clients=[svc.connect(), svc.connect()], emit=emit,
            should_stop=lambda: False)
    finally:
        svc.stop()
    assert len(items) == 2
    for it in items:
        leaves = jax.tree.leaves(it.data)
        assert all(isinstance(x, np.ndarray) for x in leaves)
        assert it.data["obs_image"].shape == (4, 6) + env.image_hw


# ---------------------------------------------------------------------------
# the thread driver: replies on the device, the same trajectories as a
# host round trip per step, and no copy to the host


class _HostRoundTrip:
    """The service as the thread driver met it with a host round trip per
    step: each request crosses to the host, so each reply comes back
    numpy, and the next step sends it back."""

    def __init__(self, service):
        self.service = service

    def submit_async(self, data):
        return self.service.submit_async(jax.tree.map(np.asarray, data))

    def drive_flushes(self):
        self.service.drive_flushes()

    def wait(self, w):
        return self.service.wait(w)


def _host_index_env_fns(make):
    """``_make_inference_env_fns`` with the step index a host integer,
    passed as ``np.int32`` at every step, as before it stayed on the
    device."""
    def fns(env, n):
        reset_batch, step_batch = make(env, n)

        def step(state, action, key, t):
            t = int(t)
            state, _, out = step_batch(state, action, key, np.int32(t))
            return state, t + 1, out
        return reset_batch, step
    return fns


def _driver_service(env, arch, icfg, num_actors):
    from repro.distributed import ParameterStore
    from repro.distributed.inference import InferenceService
    from repro.models import backbone as bb
    from repro.models import common as pcommon

    params = pcommon.init_params(bb.backbone_specs(arch, env.num_actions),
                                 jax.random.key(0))
    return InferenceService(env, arch, icfg, ParameterStore(params),
                            num_clients=num_actors, seed=3)


def _drive(service, env, arch, icfg, unrolls, on_emit=None):
    """Two logical actors, ``unrolls`` unrolls each; the items in emit
    order."""
    items = []

    def emit(uid, item):
        items.append(item)
        if on_emit is not None:
            on_emit(items)
        return True

    runner.run_inference_driver_loop(
        actor_ids=[0, 1], env=env, arch_cfg=arch, icfg=icfg, num_envs=4,
        seed=9, service=service, emit=emit,
        should_stop=lambda: len(items) >= 2 * unrolls)
    return items


@pytest.mark.timeout_s(300)
def test_driver_trajectories_equal_the_host_round_trip(monkeypatch):
    """For a fixed seed on catch (episodes end inside unrolls), the
    driver's trajectories equal key by key — values, dtypes and the
    initial LSTM state — those of the same driver with a host round
    trip per step: numpy requests and replies, a host step index, and
    every column stacked on the host. So the sampled actions and
    log-probs are those the round trip gave."""
    env = make_catch()
    arch = small_arch(env)
    icfg = _icfg(num_actions=env.num_actions, unroll_length=5)

    dev_svc = _driver_service(env, arch, icfg, 2)
    try:
        dev = _drive(dev_svc, env, arch, icfg, unrolls=3)
    finally:
        dev_svc.stop()

    real = runner.assemble_inference_traj

    def on_host(steps, boot, init_lstm, icfg):
        return real(jax.tree.map(np.asarray, steps),
                    jax.tree.map(np.asarray, boot),
                    jax.tree.map(np.asarray, init_lstm), icfg)

    monkeypatch.setattr(runner, "assemble_inference_traj", on_host)
    monkeypatch.setattr(runner, "_make_inference_env_fns",
                        _host_index_env_fns(runner._make_inference_env_fns))
    host_svc = _driver_service(env, arch, icfg, 2)
    try:
        host = _drive(_HostRoundTrip(host_svc), env, arch, icfg, unrolls=3)
    finally:
        host_svc.stop()

    assert len(dev) == len(host) == 6
    for d, h in zip(dev, host):
        assert (d.actor_id, d.param_version) == (h.actor_id, h.param_version)
        assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(d.data))
        assert all(isinstance(x, np.ndarray)
                   for x in jax.tree.leaves(h.data))
        _assert_bit_identical(d.data, h.data)
    assert any(np.asarray(h.data["done"]).any() for h in host)
    # the LSTM state carried across unrolls is not the zero state
    assert np.asarray(dev[-1].data["lstm_state"][0]).any()
    dev_snap, host_snap = dev_svc.snapshot(), host_svc.snapshot()
    requests = 3 * 2 * icfg.unroll_length
    assert dev_snap["requests"] == host_snap["requests"] == requests
    assert (dev_snap["device_replies"], dev_snap["host_replies"]) == \
        (requests, 0)
    assert (host_snap["device_replies"], host_snap["host_replies"]) == \
        (0, requests)


@pytest.mark.timeout_s(300)
def test_warm_driver_cycle_copies_nothing_to_the_host(monkeypatch):
    """After one acting cycle to warm up, a second runs under
    ``jax.transfer_guard_device_to_host("disallow")`` and completes.
    The guard catches copies on an accelerator; on the CPU a device
    array is already host memory and the guard lets every fetch pass,
    so spies on the two ways out count them too: the buffer protocol
    (``np.asarray``, ``np.stack``) and ``ArrayImpl._value`` (``int``,
    ``tolist``, ``device_get``, ``__array__``)."""
    from jax._src.array import ArrayImpl

    env = make_catch()
    arch = small_arch(env)
    icfg = _icfg(num_actions=env.num_actions, unroll_length=5)
    fetched = []
    armed = [False]
    real_value, real_buffer = ArrayImpl._value, ArrayImpl.__buffer__

    def value(self):
        if armed[0]:
            fetched.append(("value", self.shape))
        return real_value.fget(self)

    def buffer(self, flags):
        if armed[0]:
            fetched.append(("buffer", self.shape))
        return real_buffer(self, flags)

    monkeypatch.setattr(ArrayImpl, "_value", property(value))
    monkeypatch.setattr(ArrayImpl, "__buffer__", buffer)
    guard = contextlib.ExitStack()

    def on_emit(items):
        if len(items) == 2:         # the warm cycle has emitted
            guard.enter_context(
                jax.transfer_guard_device_to_host("disallow"))
            armed[0] = True

    svc = _driver_service(env, arch, icfg, 2)
    try:
        with guard:
            items = _drive(svc, env, arch, icfg, unrolls=2,
                           on_emit=on_emit)
            armed[0] = False
    finally:
        svc.stop()
    assert len(items) == 4
    assert fetched == [], fetched
    snap = svc.snapshot()
    assert snap["host_replies"] == 0
    assert snap["device_replies"] == snap["requests"] == \
        2 * 2 * icfg.unroll_length
    # the spies do see a fetch
    armed[0] = True
    actions = items[-1].data["actions"]
    np.asarray(actions)
    int(actions[0, 0])
    assert fetched == [("buffer", (4, icfg.unroll_length)), ("value", ())]
