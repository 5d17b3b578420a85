"""Learner-group refactor, end to end: the extracted ``Learner`` is
behavior-identical for one learner (first-train-step bit-match against
``run_async_training``), the gradient exchange really mean-reduces
over the framed channel (stale contributions dropped, laggards kept on
the group trajectory), sharding leaves per-actor randomness untouched,
merged telemetry aggregates without key collisions, and a 2-learner
group learns catch to the same bar as the thread/process backends with
bit-identical replicas and one monotonic version stream."""
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from repro.configs.base import ImpalaConfig
from repro.distributed import (GradHub, GroupTracker, MultiTracker,
                               NullExchange, ParameterStore,
                               SpokeExchange, merge_telemetry,
                               run_async_training, run_group_training,
                               shard_slots)

BENCH_FAST = os.environ.get("BENCH_FAST", "") == "1"


def _icfg(**kw):
    base = dict(num_actions=3, unroll_length=8, learning_rate=1e-3,
                entropy_cost=0.003, rmsprop_eps=0.01)
    base.update(kw)
    return ImpalaConfig(**base)


# ---------------------------------------------------------------------------
# sharding


def test_shard_slots_contiguous_disjoint_cover():
    assert shard_slots(4, 2) == [(0, 2), (2, 2)]
    assert shard_slots(5, 2) == [(0, 3), (3, 2)]     # remainder first
    assert shard_slots(3, 3) == [(0, 1), (1, 1), (2, 1)]
    assert shard_slots(7, 1) == [(0, 7)]
    # disjoint + covering for a spread of shapes
    for n, k in ((8, 3), (9, 4), (16, 5)):
        shards = shard_slots(n, k)
        ids = [b + i for b, c in shards for i in range(c)]
        assert ids == list(range(n))
    with pytest.raises(ValueError, match="at least one actor"):
        shard_slots(1, 2)
    with pytest.raises(ValueError, match="num_learners"):
        shard_slots(4, 0)


# ---------------------------------------------------------------------------
# MultiTracker (direct unit test — previously only exercised indirectly)


def test_multitracker_mean_return_direct():
    t = MultiTracker(num_actors=2, num_envs=1)
    assert np.isnan(t.mean_return())
    # (B, T) streams, one env per actor: reward accumulates until a
    # done flushes the episode
    t.update(0, rewards=[[1.0]], dones=[[False]])
    t.update(0, rewards=[[2.0]], dones=[[True]])    # episode return 3.0
    assert t.completed == [3.0]
    assert t.mean_return() == 3.0
    t.update(1, rewards=[[5.0]], dones=[[True]])    # return 5.0
    # chronological merge order, not actor-grouped
    assert t.completed == [3.0, 5.0]
    assert t.mean_return() == 4.0
    # the last-n window really windows
    t.update(0, rewards=[[7.0]], dones=[[True]])
    assert t.mean_return(last_n=2) == 6.0
    assert t.mean_return(last_n=1) == 7.0
    # completion times are monotone and attached 1:1
    timed = t.completed_timed
    assert [r for _t, r in timed] == [3.0, 5.0, 7.0]
    assert all(b >= a for (a, _), (b, _) in zip(timed, timed[1:]))


def test_multitracker_slot_base_maps_global_ids():
    t = MultiTracker(num_actors=2, num_envs=1, slot_base=4)
    t.update(4, rewards=[[1.0]], dones=[[True]])
    t.update(5, rewards=[[2.0]], dones=[[True]])
    assert t.completed == [1.0, 2.0]
    with pytest.raises(IndexError):
        t.update(9, rewards=[[1.0]], dones=[[True]])


def test_group_tracker_merges_chronologically():
    g = GroupTracker([(3.0, 30.0), (1.0, 10.0), (2.0, 20.0)])
    assert g.completed == [10.0, 20.0, 30.0]
    assert g.mean_return() == 20.0
    assert g.mean_return(last_n=1) == 30.0
    assert np.isnan(GroupTracker([]).mean_return())


# ---------------------------------------------------------------------------
# ParameterStore publish delegation


def test_paramstore_publish_at_is_monotonic_delegation():
    store = ParameterStore({"w": np.zeros(2, np.float32)}, version=3)
    assert store.publish_at({"w": np.ones(2, np.float32)}, 7) == 7
    assert store.version == 7
    params, version = store.pull()
    assert version == 7 and params["w"][0] == 1.0
    with pytest.raises(ValueError, match="monotonic"):
        store.publish_at({"w": np.zeros(2, np.float32)}, 7)
    with pytest.raises(ValueError, match="monotonic"):
        store.publish_at({"w": np.zeros(2, np.float32)}, 5)
    # plain publish continues from the delegated version
    assert store.publish({"w": np.zeros(2, np.float32)}) == 8


# ---------------------------------------------------------------------------
# gradient exchange (pure numpy over loopback TCP; no jax anywhere)


def test_null_exchange_identity_and_version():
    ex = NullExchange()
    leaves = [np.arange(4, dtype=np.float32)]
    out, version = ex.allreduce(leaves, round_idx=5)
    assert version == 6
    np.testing.assert_array_equal(out[0], leaves[0])
    assert ex.snapshot()["rounds"] == 1


def _leaves(scale):
    return [np.full((3,), scale, np.float32),
            np.full((2, 2), 10.0 * scale, np.float32)]


@pytest.mark.timeout_s(120)
def test_hub_spoke_allreduce_means_and_versions():
    hub = GradHub(2, stale_after_s=30.0)
    try:
        spoke = SpokeExchange(hub.address, 1, 2, dial_timeout_s=20.0)
        try:
            results = {}

            def spoke_rounds():
                for rnd in range(3):
                    results[rnd] = spoke.allreduce(_leaves(1.0 + rnd),
                                                   round_idx=rnd)

            t = threading.Thread(target=spoke_rounds, daemon=True)
            t.start()
            for rnd in range(3):
                mean, version = hub.allreduce(_leaves(3.0 + rnd),
                                              round_idx=rnd)
                assert version == rnd + 1
                # mean of (1+r) and (3+r) = 2+r, exactly
                np.testing.assert_allclose(mean[0],
                                           np.full((3,), 2.0 + rnd))
                np.testing.assert_allclose(mean[1],
                                           np.full((2, 2),
                                                   10 * (2.0 + rnd)))
            t.join(timeout=20)
            assert not t.is_alive()
            for rnd in range(3):
                s_mean, s_version = results[rnd]
                assert s_version == rnd + 1
                # the spoke applies the hub's broadcast BYTES: identical
                np.testing.assert_array_equal(s_mean[0],
                                              np.full((3,), 2.0 + rnd,
                                                      np.float32))
            assert hub.snapshot()["stale_dropped"] == 0
            assert spoke.snapshot()["rounds"] == 3
        finally:
            spoke.close()
    finally:
        hub.close()


@pytest.mark.timeout_s(120)
def test_quantized_exchange_replicas_apply_identical_means():
    """Under a lossy grad codec the hub must apply the same
    round-tripped mean the spokes decode — bit-identical results on
    both sides, or the replicas fork."""
    from repro.distributed import serde
    hub = GradHub(2, stale_after_s=30.0, wire_codec="bf16")
    try:
        spoke = SpokeExchange(hub.address, 1, 2, dial_timeout_s=20.0,
                              wire_codec="bf16")
        try:
            results = {}

            def spoke_round():
                results[0] = spoke.allreduce(_leaves(1.0), round_idx=0)

            t = threading.Thread(target=spoke_round, daemon=True)
            t.start()
            mean, version = hub.allreduce(_leaves(3.0), round_idx=0)
            t.join(timeout=20)
            assert not t.is_alive()
            s_mean, s_version = results[0]
            assert version == s_version == 1
            for h, s in zip(mean, s_mean):
                assert h.tobytes() == s.tobytes()
            # and the mean really is bf16-rounded, i.e. re-encoding is
            # a fixed point of the codec
            buf = serde.encode_grads(mean, round_idx=0, learner_id=0,
                                     codec="bf16")
            rt, _ = serde.decode_grads(buf)
            for h, r in zip(mean, rt):
                assert h.tobytes() == r.tobytes()
            assert hub.snapshot()["wire_codec"] == "bf16"
        finally:
            spoke.close()
    finally:
        hub.close()


@pytest.mark.timeout_s(120)
def test_spoke_codec_mismatch_refused_distinctly():
    """A spoke announcing a different grad codec is refused by name —
    it raises CodecMismatchError, not a generic hub-connection error
    (and never averages mixed-precision gradients)."""
    from repro.distributed import serde
    hub = GradHub(2, stale_after_s=30.0, wire_codec="int8")
    try:
        spoke = SpokeExchange(hub.address, 1, 2, dial_timeout_s=20.0,
                              wire_codec="none")
        try:
            with pytest.raises(serde.CodecMismatchError,
                               match="wire_codec mismatch"):
                spoke.allreduce(_leaves(1.0), round_idx=0)
        finally:
            spoke.close()
    finally:
        hub.close()


@pytest.mark.timeout_s(120)
def test_hub_stale_drop_rule_keeps_laggard_on_trajectory():
    """A spoke that misses the deadline is excluded from the round's
    mean (counted stale) but still receives the broadcast mean — the
    laggard's replica follows the group trajectory, late."""
    hub = GradHub(2, stale_after_s=0.5)
    try:
        spoke = SpokeExchange(hub.address, 1, 2, dial_timeout_s=20.0)
        try:
            # round 0: spoke silent -> hub reduces alone past deadline
            mean, version = hub.allreduce(_leaves(4.0), round_idx=0)
            assert version == 1
            np.testing.assert_allclose(mean[0], np.full((3,), 4.0))
            snap = hub.snapshot()
            assert snap["partial_rounds"] == 1
            # the spoke's late round-0 contribution is dropped, yet its
            # wait for the round-0 mean is served from the broadcast
            late = spoke.allreduce(_leaves(100.0), round_idx=0)
            assert late is not None
            s_mean, s_version = late
            assert s_version == 1
            np.testing.assert_allclose(s_mean[0], np.full((3,), 4.0))
            deadline = time.monotonic() + 10
            while hub.snapshot()["stale_dropped"] == 0 and \
                    time.monotonic() < deadline:
                time.sleep(0.05)
            assert hub.snapshot()["stale_dropped"] == 1
            # round 1: both in time -> full mean again
            got = {}
            t = threading.Thread(
                target=lambda: got.update(
                    r1=spoke.allreduce(_leaves(2.0), round_idx=1)),
                daemon=True)
            t.start()
            mean, version = hub.allreduce(_leaves(6.0), round_idx=1)
            t.join(timeout=20)
            assert version == 2
            np.testing.assert_allclose(mean[0], np.full((3,), 4.0))
            assert got["r1"][1] == 2
        finally:
            spoke.close()
    finally:
        hub.close()


@pytest.mark.timeout_s(120)
def test_spoke_raises_when_hub_dies():
    hub = GradHub(2, stale_after_s=30.0)
    spoke = SpokeExchange(hub.address, 1, 2, dial_timeout_s=20.0)
    try:
        hub.close()
        with pytest.raises(RuntimeError, match="hub"):
            # the close broadcast may serve a None first; a second call
            # must see the dead link either way
            for _ in range(2):
                out = spoke.allreduce(_leaves(1.0), round_idx=0)
                assert out is None
    finally:
        spoke.close()


# ---------------------------------------------------------------------------
# merged telemetry


def _fake_snap(learner_id, updates, frames, trajs, lag_hist):
    return {
        "learner_updates": updates,
        "frames_consumed": frames,
        "updates_per_sec": 2.0,
        "frames_per_sec": 100.0 * (learner_id + 1),
        "batch_size_hist": {1: updates},
        "lag": {"hist": lag_hist,
                "mean": 1.0, "max": max(lag_hist), "measured":
                sum(lag_hist.values())},
        "queue": {"transport": "inproc", "pushed": trajs,
                  "capacity": 8},
        "actors": {"num_actors": 2, "slot_base": 2 * learner_id,
                   "backend": "thread", "frames": frames,
                   "trajectories": trajs, "rejected": learner_id,
                   "actor_fps": 50.0},
        "inference": {"mean_batch": 3.0 + learner_id},
        "param_version": updates,
        "actor_mode": "unroll",
        "donate": True,
        "learner_id": learner_id,
        "slot_base": 2 * learner_id,
        "exchange": {"stale_dropped": learner_id, "rounds": updates},
    }


def test_merge_telemetry_aggregates_without_key_collisions():
    snaps = {0: _fake_snap(0, 10, 1000, 12, {0: 5, 1: 5}),
             1: _fake_snap(1, 10, 800, 9, {1: 4, 2: 6})}
    merged = merge_telemetry(snaps, publisher=0,
                             group_extra={"transport": "inproc"})
    # per-learner sections survive intact under namespaced keys — the
    # queue/inference/loss sections of the two learners cannot collide
    learners = merged["learners"]
    assert sorted(learners) == ["learner_0", "learner_1"]
    assert learners["learner_0"]["queue"]["pushed"] == 12
    assert learners["learner_1"]["queue"]["pushed"] == 9
    assert learners["learner_0"]["inference"]["mean_batch"] == 3.0
    assert learners["learner_1"]["inference"]["mean_batch"] == 4.0
    assert learners["learner_0"]["actors"]["rejected"] == 0
    assert learners["learner_1"]["actors"]["rejected"] == 1
    # aggregates: sums where summing means something, publisher's
    # counters for the synchronized ones
    assert merged["frames_consumed"] == 1800
    assert merged["frames_per_sec"] == 300.0
    assert merged["learner_updates"] == 10
    assert merged["param_version"] == 10
    assert merged["actors"]["num_actors"] == 4
    assert merged["actors"]["trajectories"] == 21
    assert merged["actors"]["rejected"] == 1
    assert merged["actors"]["per_learner_trajectories"] == {
        "learner_0": 12, "learner_1": 9}
    # lag histograms fold together
    assert merged["lag"]["hist"] == {0: 5, 1: 9, 2: 6}
    assert merged["lag"]["measured"] == 20
    assert merged["lag"]["max"] == 2
    assert merged["group"]["num_learners"] == 2
    assert merged["group"]["stale_dropped"] == 1
    assert merged["group"]["transport"] == "inproc"
    with pytest.raises(ValueError):
        merge_telemetry({})


def test_merge_telemetry_three_learners():
    """The merge at fleet width 3: lag histograms fold across all
    members (shared buckets sum, disjoint ones survive), synchronized
    counters come from the publisher while frames/fps sum, and every
    per-learner subtree lands under its own ``learners.learner_<k>``
    key with no collisions."""
    snaps = {0: _fake_snap(0, 20, 1000, 12, {0: 5, 1: 5}),
             1: _fake_snap(1, 20, 800, 9, {1: 4, 2: 6}),
             2: _fake_snap(2, 20, 600, 7, {2: 1, 7: 3})}
    merged = merge_telemetry(snaps, publisher=0)
    # one namespaced subtree per learner, nothing dropped or merged
    assert sorted(merged["learners"]) == ["learner_0", "learner_1",
                                          "learner_2"]
    for k, trajs in ((0, 12), (1, 9), (2, 7)):
        sub = merged["learners"][f"learner_{k}"]
        assert sub["queue"]["pushed"] == trajs
        assert sub["learner_id"] == k
        assert sub["slot_base"] == 2 * k
    # lag histograms fold: bucket 1 from learners 0+1, bucket 2 from
    # 1+2, bucket 7 only from learner 2
    assert merged["lag"]["hist"] == {0: 5, 1: 9, 2: 7, 7: 3}
    assert merged["lag"]["measured"] == 24
    assert merged["lag"]["max"] == 7
    # throughput sums; synchronized counters follow the publisher
    assert merged["frames_consumed"] == 2400
    assert merged["frames_per_sec"] == 600.0
    assert merged["learner_updates"] == 20
    assert merged["param_version"] == 20
    assert merged["actors"]["num_actors"] == 6
    assert merged["actors"]["trajectories"] == 28
    assert merged["actors"]["per_learner_trajectories"] == {
        "learner_0": 12, "learner_1": 9, "learner_2": 7}
    assert merged["group"]["num_learners"] == 3
    assert merged["group"]["stale_dropped"] == 3  # 0 + 1 + 2
    assert merged["group"]["publisher"] == 0


# ---------------------------------------------------------------------------
# determinism: the group-of-one worker IS the single-learner runtime


@pytest.mark.timeout_s(420)
def test_learners_1_bitmatches_single_learner_first_train_step():
    """Shard determinism pin: a group of ONE learner (worker process,
    exchange-free fused step) must produce bit-identical params to
    today's in-process ``run_async_training`` after the first train
    step — same param init (raw seed), same actor RNG
    (fold_in(seed, 0)), same batch, same update. One actor and
    max_batch_trajs=1 make the first batch deterministic."""
    import jax

    icfg = _icfg()
    captured = []
    run_async_training(
        "bandit", icfg, num_envs=4, steps=1, num_actors=1,
        actor_backend="thread", transport="inproc", queue_capacity=4,
        queue_policy="block", max_batch_trajs=1, seed=5,
        on_update=lambda step, params, m, snap: captured.append(
            (jax.tree.map(np.asarray, params), snap())))
    ref_params, ref_tel = captured[0]

    tracker, metrics, tel, params = run_group_training(
        "bandit", icfg, 4, 1, num_learners=1, num_actors=1,
        actor_backend="thread", queue_capacity=4, queue_policy="block",
        max_batch_trajs=1, seed=5, return_final_params=True)

    ref_leaves = jax.tree.leaves(ref_params)
    got_leaves = jax.tree.leaves(params)
    assert len(ref_leaves) == len(got_leaves)
    for a, b in zip(ref_leaves, got_leaves):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()       # BIT match, not allclose
    # the extracted Learner reports exactly the telemetry keys the
    # monolith always reported (no grouped-only keys leak in)
    worker_tel = tel["learners"]["learner_0"]
    assert sorted(worker_tel.keys()) == sorted(ref_tel.keys())
    assert tel["param_version"] == 1


# ---------------------------------------------------------------------------
# 2-learner groups, end to end


@pytest.mark.timeout_s(420)
def test_two_learner_group_trains_with_identical_replicas():
    icfg = _icfg()
    tracker, metrics, tel = run_group_training(
        "bandit", icfg, 4, 6, num_learners=2, num_actors=2,
        actor_backend="thread", queue_capacity=4, queue_policy="block",
        max_batch_trajs=2, seed=0)
    assert np.isfinite(float(metrics["loss/total"]))
    g = tel["group"]
    assert g["num_learners"] == 2 and g["publisher"] == 0
    # one monotonic version stream: every learner's store ends at the
    # round count, by delegation from the hub
    assert g["param_versions"] == [6, 6]
    assert tel["param_version"] == 6
    assert tel["learner_updates"] == 6
    # data-parallel invariant: the replicas are BIT-identical
    assert g["replicas_identical"], g["param_digests"]
    # actor slots verifiably split: both learners consumed trajectories
    # from their own disjoint shard
    per = tel["actors"]["per_learner_trajectories"]
    assert per["learner_0"] > 0 and per["learner_1"] > 0
    assert tel["learners"]["learner_0"]["actors"]["slot_base"] == 0
    assert tel["learners"]["learner_1"]["actors"]["slot_base"] == 1
    assert tel["learners"]["learner_0"]["learner_id"] == 0
    assert tel["learners"]["learner_1"]["learner_id"] == 1
    # the exchange really ran every round
    assert tel["learners"]["learner_0"]["exchange"]["rounds"] == 6
    assert tel["learners"]["learner_1"]["exchange"]["rounds"] == 6
    assert g["stale_dropped"] == 0


@pytest.mark.timeout_s(540)
def test_two_learner_group_over_process_actors():
    """The sharded slot assignment crosses the process boundary too:
    each learner spawns its own actor child with a GLOBAL slot id, and
    the serialized accounting maps it back to the learner's shard."""
    icfg = _icfg()
    tracker, metrics, tel = run_group_training(
        "bandit", icfg, 4, 4, num_learners=2, num_actors=2,
        actor_backend="process", transport="shm",
        queue_capacity=4, queue_policy="block", max_batch_trajs=2,
        seed=1)
    assert np.isfinite(float(metrics["loss/total"]))
    assert tel["group"]["replicas_identical"]
    assert tel["group"]["param_versions"] == [4, 4]
    per = tel["actors"]["per_learner_trajectories"]
    assert per["learner_0"] > 0 and per["learner_1"] > 0
    for k in ("learner_0", "learner_1"):
        q = tel["learners"][k]["queue"]
        assert q["transport"] == "shm" and q["wire_received"] > 0
    assert tel["learners"]["learner_1"]["actors"]["slot_base"] == 1


@pytest.mark.timeout_s(600)
def test_two_learner_group_learns_catch():
    """Acceptance: a 2-learner group on catch reaches the same bar the
    thread/process backends do — real learning (late-episode return far
    above the early near-random window), with the slots split across
    learners and a single monotonic version stream."""
    from repro.configs.registry import get_smoke_config
    from repro.data.envs import make_catch

    env = make_catch()
    arch = get_smoke_config("impala-shallow").replace(
        image_hw=env.image_hw)
    cfg = ImpalaConfig(num_actions=env.num_actions, unroll_length=20,
                       learning_rate=6e-4, entropy_cost=0.003,
                       rmsprop_eps=0.01)
    # each round trains BOTH learners on a batch (the applied mean sees
    # ~2x the trajectories per round), so fewer rounds reach the bar
    steps = 120 if BENCH_FAST else 240
    tracker, metrics, tel = run_group_training(
        "catch", cfg, 32, steps, num_learners=2, num_actors=4,
        actor_backend="thread", queue_capacity=8, queue_policy="block",
        max_batch_trajs=4, seed=0, arch=arch)
    returns = tracker.completed
    early = float(np.mean(returns[:500]))
    late = float(np.mean(returns[-100:]))
    assert tel["learner_updates"] == steps
    assert tel["param_version"] == steps
    assert tel["group"]["param_versions"] == [steps, steps]
    assert tel["group"]["replicas_identical"]
    per = tel["actors"]["per_learner_trajectories"]
    assert per["learner_0"] > 0 and per["learner_1"] > 0
    assert np.isfinite(float(metrics["loss/total"]))
    assert tel["lag"]["max"] > 0
    # random play on catch is ~-0.6; require a decisive climb
    assert late > early + 0.15, (early, late)
    assert late > -0.3, (early, late)


# ---------------------------------------------------------------------------
# SPMD collective exchange


def test_collective_exchange_delegates_versions_and_snapshot():
    """CollectiveExchange keeps the GradientExchange version contract
    (version = round_idx + 1, same as hub/spoke) while doing no wire
    work, and its snapshot reports the collective backend with latency
    telemetry but NO byte counters — the gradient path is in-XLA."""
    from repro.distributed import CollectiveExchange

    ex = CollectiveExchange(4)
    assert ex.in_xla
    leaves, version = ex.allreduce([], round_idx=7)
    assert leaves == [] and version == 8
    ex.observe_round_s(0.004, round_idx=7)
    snap = ex.snapshot()
    assert snap["exchange_backend"] == "collective"
    assert snap["devices"] == 4
    assert snap["rounds"] == 1
    assert "bytes_in" not in snap and "bytes_out" not in snap
    # 4000 us has bit_length 12 -> the [2048, 4096) us bucket
    assert snap["round_us_hist"] == {12: 1}
    assert snap["round_ms_mean"] == pytest.approx(4.0)


SUBPROCESS_TRIANGLE = textwrap.dedent("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import threading
import zlib

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ImpalaConfig
from repro.core import learner as learner_lib
from repro.core.driver import small_arch
from repro.data.envs import make_bandit
from repro.distributed import GradHub, SpokeExchange
from repro.launch.mesh import make_data_mesh
from repro.models import backbone as bb
from repro.models import common as pcommon

env = make_bandit()
arch = small_arch(env)
icfg = ImpalaConfig(num_actions=env.num_actions, unroll_length=4,
                    learning_rate=1e-3, rmsprop_eps=0.01)
A = env.num_actions
params = pcommon.init_params(bb.backbone_specs(arch, A), jax.random.key(0))

K = 3
b, t, hw = 2, 4, env.image_hw
rng = np.random.default_rng(0)


def mk_batch():
    return {
        "obs_image": rng.integers(0, 255, (b, t + 1) + hw).astype(np.uint8),
        "last_action": np.zeros((b, t + 1), np.int32),
        "last_reward": np.zeros((b, t + 1), np.float32),
        "done_in": np.zeros((b, t + 1), bool),
        "lstm_state": tuple(np.zeros((b, arch.lstm_width), np.float32)
                            for _ in range(2)),
        "actions": rng.integers(0, A, (b, t)).astype(np.int32),
        "rewards": rng.standard_normal((b, t)).astype(np.float32),
        "discounts": np.full((b, t), 0.99, np.float32),
        "behaviour_logprob": np.full((b, t), -1.0, np.float32),
        "done": np.zeros((b, t), bool),
    }


rounds = [(mk_batch(), mk_batch()) for _ in range(K)]


def digest(tree):
    crc = 0
    for leaf in jax.tree.leaves(tree):
        crc = zlib.crc32(np.asarray(leaf).tobytes(), crc)
    return crc


# ---- leg A: single fused learner, one half-batch per round
train_step, opt = learner_lib.build_train_step(arch, icfg, A,
                                               vtrace_impl="scan")
fused = jax.jit(train_step)
pA, oA = params, opt.init(params)
for i, (h0, _h1) in enumerate(rounds):
    pA, oA, _ = fused(pA, oA, jnp.int32(i), h0)
jax.block_until_ready(pA)

# ---- leg B: real hub/spoke group over the framed TCP channel
grad_step, apply_step, opt2 = learner_lib.build_grad_apply_steps(
    arch, icfg, A, vtrace_impl="scan")
gs = jax.jit(grad_step)
ap = jax.jit(apply_step)


def run_group(feeds):
    # the hub IS learner 0's exchange; the spoke dials in as learner 1
    hub = GradHub(2, stale_after_s=60.0)
    spoke = SpokeExchange(hub.address, 1, 2, dial_timeout_s=30.0)
    out, versions = {}, {}

    def worker(k, exchange):
        p, o = params, opt2.init(params)
        for i in range(K):
            g, _ = gs(p, feeds[k][i])
            leaves, td = jax.tree.flatten(g)
            mean, version = exchange.allreduce(
                [np.asarray(x) for x in leaves], round_idx=i)
            versions.setdefault(k, []).append(version)
            p, o, _ = ap(p, o, jnp.int32(i),
                         jax.tree.unflatten(td, list(mean)))
        jax.block_until_ready(p)
        out[k] = p

    threads = [threading.Thread(target=worker, args=(k, ex), daemon=True)
               for k, ex in ((0, hub), (1, spoke))]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=180)
    spoke.close()
    hub.close()
    assert set(out) == {0, 1}, "group leg did not finish"
    return out, versions


dup, vdup = run_group({0: [r[0] for r in rounds],
                       1: [r[0] for r in rounds]})
dist, _ = run_group({0: [r[0] for r in rounds],
                     1: [r[1] for r in rounds]})

# ---- leg C: spmd shard_map step on the real ('data',) mesh
mesh = make_data_mesh(2)
spmd_step, opt3 = learner_lib.build_train_step(arch, icfg, A,
                                               vtrace_impl="scan", mesh=mesh)
spmd = jax.jit(spmd_step)
rep = NamedSharding(mesh, P())
devs = list(mesh.devices.flatten())


def shard_concat(h0, h1):
    def leaf(x0, x1):
        x0, x1 = np.asarray(x0), np.asarray(x1)
        pieces = [jax.device_put(x0, devs[0]), jax.device_put(x1, devs[1])]
        return jax.make_array_from_single_device_arrays(
            (x0.shape[0] + x1.shape[0],) + x0.shape[1:],
            NamedSharding(mesh, P("data")), pieces)
    return jax.tree.map(leaf, h0, h1)


def run_spmd(pick):
    p = jax.device_put(params, rep)
    o = jax.device_put(opt3.init(params), rep)
    for i, (h0, h1) in enumerate(rounds):
        p, o, _ = spmd(p, o, jnp.int32(i), shard_concat(*pick(h0, h1)))
    jax.block_until_ready(p)
    return p


pC_dup = run_spmd(lambda h0, h1: (h0, h0))
pC_dist = run_spmd(lambda h0, h1: (h0, h1))


def max_rel_diff(a, b):
    return max(float(np.max(np.abs(np.asarray(x, np.float64) -
                                   np.asarray(y, np.float64))) /
                     max(float(np.max(np.abs(np.asarray(y, np.float64)))),
                         1e-30))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


print(json.dumps({
    "A": digest(pA),
    "A_vs_B_dup": max_rel_diff(pA, dup[0]),
    "B_dup": [digest(dup[0]), digest(dup[1])],
    "B_dist": [digest(dist[0]), digest(dist[1])],
    "C_dup": digest(pC_dup),
    "C_dist": digest(pC_dist),
    "versions": vdup.get(0, []),
}))
""")


@pytest.mark.timeout_s(420)
def test_spmd_group_single_digest_triangle_subprocess():
    """Digest-equivalence triangle at equal global batch (forced 2
    devices): after K=3 update rounds,

    * dup halves (both shards carry the same trajectories): the spmd
      shard_map step == both replicas of a real hub/spoke 2-learner
      group, bit-identical — the in-XLA pmean over identical shards is
      the identity, like the group's wire mean of identical gradients
      — and both equal the single fused learner to float32 rounding
      (one program fusing backward and update may round an element
      differently from the split grad/apply programs);
    * distinct halves: spmd on concat(h0, h1) == the hub/spoke group
      training one learner per half — pmean of per-shard sum-gradients
      is exactly the hub's mean, so swapping the TCP exchange for the
      collective changes no bit of the trained params.
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SUBPROCESS_TRIANGLE],
                       capture_output=True, text=True, env=env, timeout=400)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    # dup: the group and spmd legs collapse to one digest, and the
    # single fused learner agrees to float32 rounding
    assert out["B_dup"][0] == out["B_dup"][1], out
    assert out["B_dup"][0] == out["C_dup"], out
    assert out["A_vs_B_dup"] <= 1e-6, out
    # distinct: group replicas identical, and spmd matches them
    assert out["B_dist"][0] == out["B_dist"][1], out
    assert out["C_dist"] == out["B_dist"][0], out
    # distinct halves genuinely differ from the dup run
    assert out["C_dist"] != out["C_dup"], out
    # hub versions delegate round_idx + 1, matching CollectiveExchange
    assert out["versions"] == [1, 2, 3], out


def test_learner_group_refuses_to_share_an_accelerator(monkeypatch):
    """On an accelerator host N learner processes would all open the
    devices one process owns: the group fails before spawning anything,
    pointing at the single-process SPMD learner."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="--learner-mode spmd"):
        run_group_training("bandit", _icfg(), 4, 2, num_learners=2)
