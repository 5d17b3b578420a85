"""Prioritized trajectory replay (Ape-X / IMPACT hybrid), unit to end
to end: ring FIFO eviction/wraparound, lstm-tuple round-trip through the
serde layout, occupancy starvation, proportional-prioritization math,
reuse-limit retirement, the seed-fold discipline, ``plan_mix`` /
``mix_batches`` edge cases, the target-baseline replay loss (exact
standard-loss match at mask=0), and replay-enabled async / group runs
(telemetry populated, reuse ratio ~1/(1-fraction), digest-identical
replicas)."""
import os

import numpy as np
import pytest

from repro.core.replay import (PRIORITY_MODES, ReplayBuffer,
                               fold_replay_seed, mix_batches, plan_mix)

BENCH_FAST = os.environ.get("BENCH_FAST", "") == "1"


def _traj(i, n_envs=2, t=3):
    """A tiny trajectory batch pytree with an lstm-state tuple leaf."""
    return {
        "x": np.full((n_envs, t), float(i), np.float32),
        "lstm_state": (np.full((n_envs, 4), float(i), np.float32),
                       np.full((n_envs, 4), -float(i), np.float32)),
    }


# ---------------------------------------------------------------------------
# construction / seeding


def test_buffer_requires_explicit_seed_or_rng():
    with pytest.raises(ValueError, match="explicit rng or seed"):
        ReplayBuffer(capacity=4)
    ReplayBuffer(capacity=4, seed=0)                        # ok
    ReplayBuffer(capacity=4, rng=np.random.default_rng(7))  # ok


def test_fold_replay_seed_identity_and_distinct_streams():
    # learner 0 (and the single-learner run) keeps the raw seed
    assert fold_replay_seed(123, 0) == 123
    folded = {fold_replay_seed(123, k) for k in range(4)}
    assert len(folded) == 4
    # deterministic: two buffers with the same (seed, learner_id) draw
    # the identical index stream; different learner_ids do not
    def draws(lid):
        buf = ReplayBuffer(capacity=16, seed=5, learner_id=lid)
        for i in range(8):
            buf.add_batch(_traj(i))
        return [s.uid for s in buf.sample_items(6)]

    assert draws(1) == draws(1)
    assert draws(1) != draws(2)


def test_invalid_priority_mode_rejected():
    with pytest.raises(ValueError, match="priority"):
        ReplayBuffer(capacity=4, seed=0, priority="rank")
    assert set(PRIORITY_MODES) == {"uniform", "pertd"}


# ---------------------------------------------------------------------------
# FIFO ring / round-trip / starvation


def test_fifo_eviction_and_wraparound_at_capacity():
    buf = ReplayBuffer(capacity=4, seed=0, priority="uniform")
    for i in range(6):                      # 6 items of 2 envs = 12 adds
        buf.add_batch(_traj(i))
    assert len(buf) == 4
    assert buf.added == 12
    assert buf.evicted_fifo == 8            # ring wrapped twice
    # only the newest capacity-many survive: items 4 and 5 (stored
    # per-env, so each item's "x" is the (t,) row of one env)
    vals = set()
    for _ in range(10):
        for s in buf.sample_items(4):
            vals.add(float(s.item.data["x"][0]))
    assert vals == {4.0, 5.0}


def test_lstm_state_tuple_roundtrips_through_add_batch_sample():
    buf = ReplayBuffer(capacity=8, seed=0)
    buf.add_batch(_traj(3), param_version=7)
    out = buf.sample(2)
    assert isinstance(out["lstm_state"], tuple)
    np.testing.assert_array_equal(out["x"], np.full((2, 3), 3.0))
    np.testing.assert_array_equal(out["lstm_state"][0],
                                  np.full((2, 4), 3.0))
    np.testing.assert_array_equal(out["lstm_state"][1],
                                  np.full((2, 4), -3.0))
    # host-side all the way: np.stack output, never device arrays
    assert type(out["x"]) is np.ndarray


def test_sample_returns_none_under_occupancy():
    buf = ReplayBuffer(capacity=8, seed=0)
    buf.add_batch(_traj(0))                 # 2 items live
    assert buf.sample(4) is None
    assert buf.sample_items(3) is None
    assert buf.starved == 2
    assert buf.sample_items(0) == []
    assert len(buf.sample_items(2)) == 2


def test_staleness_recorded_at_sample_time():
    buf = ReplayBuffer(capacity=8, seed=0)
    buf.add_batch(_traj(0), param_version=10)
    buf.sample_items(2, version_now=14)
    assert buf.snapshot()["staleness"]["hist"] == {4: 2}
    assert buf.snapshot()["staleness"]["max"] == 4


# ---------------------------------------------------------------------------
# priorities


def test_priority_update_math_and_stale_uid_skip():
    buf = ReplayBuffer(capacity=8, seed=0, priority_eps=0.0)
    uids = buf.add_batch(_traj(0))          # enter at max-priority (1.0)
    probs = buf.sampling_probs()
    assert probs[uids[0]] == pytest.approx(0.5)
    # proportional: 3:1 priorities -> 0.75 / 0.25 draw probability
    assert buf.update_priorities(uids, [3.0, 1.0]) == 2
    probs = buf.sampling_probs()
    assert probs[uids[0]] == pytest.approx(0.75)
    assert probs[uids[1]] == pytest.approx(0.25)
    # a stale uid (never existed / evicted) is skipped, not misapplied
    assert buf.update_priorities([999], [5.0]) == 0
    # new inserts pick up the max seen priority (Ape-X default)
    new = buf.add_item(__import__("repro.distributed.serde",
                                  fromlist=["TrajectoryItem"])
                       .TrajectoryItem(_traj(1), 0, 0, 0.0))
    live = {s.uid: s for s in buf._live_slots()}
    assert live[new].priority == 3.0


def test_uniform_mode_ignores_priorities():
    buf = ReplayBuffer(capacity=8, seed=0, priority="uniform")
    uids = buf.add_batch(_traj(0))
    buf.update_priorities(uids, [100.0, 1e-9])
    probs = buf.sampling_probs()
    assert probs[uids[0]] == pytest.approx(0.5)


def test_reuse_limit_retires_slots():
    buf = ReplayBuffer(capacity=8, seed=0, reuse_limit=2)
    buf.add_batch(_traj(0))                 # 2 items, uses=0
    assert len(buf.sample_items(2)) == 2    # uses -> 1
    assert len(buf) == 2
    assert len(buf.sample_items(2)) == 2    # uses -> 2 == K: retired
    assert len(buf) == 0
    assert buf.evicted_exhausted == 2
    # an item entering with its online pass pre-counted (uses=1) has
    # K-1 replays left; at K=1 it never occupies a slot at all
    from repro.distributed.serde import TrajectoryItem
    buf.add_item(TrajectoryItem(_traj(1), 0, 0, 0.0), uses=1)
    assert len(buf) == 1
    buf1 = ReplayBuffer(capacity=8, seed=0, reuse_limit=1)
    buf1.add_item(TrajectoryItem(_traj(1), 0, 0, 0.0), uses=1)
    assert len(buf1) == 0 and buf1.evicted_exhausted == 1


# ---------------------------------------------------------------------------
# mixing


def test_plan_mix_top_up_math():
    # fresh=2, top bucket 4, fraction 0.5, plenty of stock -> 2 replayed
    assert plan_mix(2, 4, 0.5, 100) == 2
    # stock-limited: 2 fresh + 1 replayed = 3 is not a power-of-two
    # bucket, so the round trains pure online rather than recompiling
    assert plan_mix(2, 4, 0.5, 1) == 0
    assert plan_mix(3, 4, 0.5, 1) == 1      # 3 + 1 -> 4 works
    # fraction 0 / no fresh / empty buffer -> pure online
    assert plan_mix(2, 4, 0.0, 100) == 0
    assert plan_mix(0, 4, 0.5, 100) == 0
    assert plan_mix(2, 4, 0.5, 0) == 0
    # the total stays a power of two <= max_total
    assert plan_mix(3, 4, 0.5, 100) == 1    # 3 fresh + 1 -> 4
    assert plan_mix(1, 8, 0.5, 100) == 1    # 1+1=2 (4 would need 3 > 2)
    assert plan_mix(4, 8, 0.5, 100) == 4    # 4+4=8
    assert plan_mix(4, 4, 0.5, 100) == 0    # bucket already full


def test_mix_batches_edges_and_displaced_counting():
    online = {"x": np.zeros((8, 2), np.float32)}
    rep = {"x": np.ones((8, 2), np.float32)}
    # fraction 0 / missing replay batch: online unchanged
    assert mix_batches(online, rep, 0.0) is online
    assert mix_batches(online, None, 0.5) is online
    # fraction 1 rounds to the whole batch
    assert float(mix_batches(online, rep, 1.0)["x"].sum()) == 16.0
    # n_rep < k: k clips to what the replay batch actually holds
    small = {"x": np.ones((2, 2), np.float32)}
    assert float(mix_batches(online, small, 0.5)["x"].sum()) == 4.0
    # numpy in -> numpy out (no hidden device round-trip)
    assert type(mix_batches(online, rep, 0.5)["x"]) is np.ndarray
    # displaced online rows are counted into the buffer
    buf = ReplayBuffer(capacity=8, seed=0)
    mix_batches(online, rep, 0.5, buffer=buf)
    assert buf.displaced == 4
    assert buf.snapshot()["displaced"] == 4


# ---------------------------------------------------------------------------
# replay loss: target-baseline V-trace


def test_replay_loss_mask_zero_matches_standard_loss():
    """With an all-zero replay mask the IMPACT loss IS the standard
    loss, even against a completely different target network."""
    import jax

    from repro.configs.base import ImpalaConfig
    from repro.configs.registry import get_smoke_config
    from repro.core import learner as learner_lib
    from repro.data.envs import make_env
    from repro.models import backbone as bb
    from repro.models import common as pcommon

    env = make_env("bandit")
    arch = get_smoke_config("impala_shallow").replace(image_hw=env.image_hw)
    icfg = ImpalaConfig(num_actions=env.num_actions, unroll_length=4)
    specs = bb.backbone_specs(arch, env.num_actions)
    params = pcommon.init_params(specs, jax.random.key(0))
    other = pcommon.init_params(specs, jax.random.key(1))

    rng = np.random.default_rng(0)
    b, t = 2, 4
    batch = {
        "obs_image": rng.random((b, t + 1) + env.image_hw
                                ).astype(np.float32),
        "last_action": rng.integers(0, env.num_actions,
                                    (b, t + 1)).astype(np.int32),
        "last_reward": rng.random((b, t + 1)).astype(np.float32),
        "done_in": np.zeros((b, t + 1), np.bool_),
        "actions": rng.integers(0, env.num_actions, (b, t)).astype(np.int32),
        "rewards": rng.random((b, t)).astype(np.float32),
        "discounts": np.full((b, t), 0.99, np.float32),
        "behaviour_logprob": np.log(
            np.full((b, t), 1.0 / env.num_actions, np.float32)),
    }
    loss = learner_lib.build_loss_fn(arch, icfg, env.num_actions)
    total_std, m_std = loss(params, batch)
    rb = dict(batch)
    rb["replay_mask"] = np.zeros(b, np.float32)
    total_rep, m_rep = loss(params, rb, other)
    assert float(total_rep) == pytest.approx(float(total_std), rel=1e-6)
    # the per-trajectory priority signal rides the metrics, (B,)-shaped
    assert m_rep["vtrace/traj_adv_mag"].shape == (b,)
    # mask=1 really routes the target values into the correction
    rb1 = dict(rb)
    rb1["replay_mask"] = np.ones(b, np.float32)
    total_tgt, _ = loss(params, rb1, other)
    assert float(total_tgt) != pytest.approx(float(total_std), rel=1e-6)
    # ... and a target identical to the online params is a no-op
    total_same, _ = loss(params, rb1, params)
    assert float(total_same) == pytest.approx(float(total_std), rel=1e-5)


# ---------------------------------------------------------------------------
# end to end


def test_async_run_with_replay_populates_telemetry():
    from repro.configs.base import ImpalaConfig
    from repro.distributed import run_async_training

    icfg = ImpalaConfig(num_actions=2, unroll_length=8,
                        learning_rate=1e-3, entropy_cost=0.003,
                        rmsprop_eps=0.01, replay_fraction=0.5,
                        replay_reuse=2, replay_capacity=256)
    tracker, metrics, tel = run_async_training(
        "bandit", icfg, 4, 24, num_actors=2, actor_backend="thread",
        queue_capacity=4, queue_policy="block", max_batch_trajs=4,
        seed=0)
    assert np.isfinite(float(metrics["loss/total"]))
    # the (B,)-shaped priority metric never leaks to metric consumers
    assert "vtrace/traj_adv_mag" not in metrics
    rp = tel["replay"]
    assert rp["sampled"] > 0
    assert rp["frames_trained"] > tel["frames_consumed"]
    # steady state trains ~1/(1-fraction) frames per env frame
    assert rp["reuse_ratio"] > 1.3
    assert rp["staleness"]["measured"] == rp["sampled"]
    assert rp["fresh_max"] == 2
    assert sum(rp["priority_hist"].values()) == rp["occupancy"]
    assert rp["reuse_limit"] == 2 and rp["priority_mode"] == "pertd"


def test_async_run_without_replay_keeps_pinned_keys():
    from repro.configs.base import ImpalaConfig
    from repro.distributed import run_async_training

    icfg = ImpalaConfig(num_actions=2, unroll_length=8,
                        learning_rate=1e-3, entropy_cost=0.003,
                        rmsprop_eps=0.01)
    _, metrics, tel = run_async_training(
        "bandit", icfg, 4, 4, num_actors=1, actor_backend="thread",
        queue_capacity=4, queue_policy="block", max_batch_trajs=2,
        seed=0)
    assert "replay" not in tel


@pytest.mark.timeout_s(540)
def test_two_learner_group_with_replay_stays_digest_identical():
    """The digest-identity invariant survives replay: each replica
    samples its own (seed, learner_id)-folded stream, but every one
    applies the same exchanged mean gradient and syncs its target on
    the same update count."""
    from repro.configs.base import ImpalaConfig
    from repro.distributed import run_group_training

    icfg = ImpalaConfig(num_actions=3, unroll_length=8,
                        learning_rate=1e-3, entropy_cost=0.003,
                        rmsprop_eps=0.01, replay_fraction=0.5,
                        replay_reuse=2, replay_capacity=256,
                        replay_target_period=4)
    tracker, metrics, tel = run_group_training(
        "bandit", icfg, 4, 8, num_learners=2, num_actors=2,
        actor_backend="thread", queue_capacity=4, queue_policy="block",
        max_batch_trajs=2, seed=0)
    assert np.isfinite(float(metrics["loss/total"]))
    assert tel["group"]["replicas_identical"], tel["group"]["param_digests"]
    # the merged replay section aggregates both replicas
    rp = tel["replay"]
    assert rp["sampled"] > 0
    assert rp["target_syncs"] >= 2      # both learners synced at update 4+
    assert tel["learners"]["learner_0"]["replay"]["sampled"] > 0
    assert tel["learners"]["learner_1"]["replay"]["sampled"] > 0


@pytest.mark.timeout_s(540)
def test_catch_learns_with_replay_halved_env_frames():
    """The acceptance bar: catch reaches the single-pass improvement
    signal while consuming ~half the env frames per update (fraction
    0.5 tops every 4-batch up from 2 fresh)."""
    from repro.configs.base import ImpalaConfig
    from repro.distributed import run_async_training

    steps = 120 if BENCH_FAST else 240
    icfg = ImpalaConfig(num_actions=3, unroll_length=8,
                        learning_rate=1e-3, entropy_cost=0.003,
                        rmsprop_eps=0.01, replay_fraction=0.5,
                        replay_reuse=2, replay_capacity=512)
    tracker, metrics, tel = run_async_training(
        "catch", icfg, 16, steps, num_actors=2, actor_backend="thread",
        queue_capacity=8, queue_policy="block", max_batch_trajs=4,
        seed=0)
    returns = tracker.completed
    assert len(returns) > 40
    early = float(np.mean(returns[:20]))
    late = float(np.mean(returns[-20:]))
    assert late > early + 0.15, (early, late)
    rp = tel["replay"]
    assert rp["reuse_ratio"] > 1.5      # ~2x optimizer frames per env frame
    assert rp["sampled"] > 0
