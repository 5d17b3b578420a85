"""Core RL machinery: corrections, losses, replay, queue/lag, PBT, optim,
checkpoint, metrics."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ImpalaConfig
from repro.core import corrections, losses, vtrace as vt
from repro.core.pbt import PBTController
from repro.core.queue import LagController, TrajectoryQueue
from repro.core.replay import ReplayBuffer, mix_batches
from repro.core.metrics import EpisodeTracker, capped_normalised_score
from repro.checkpoint import checkpoint as ckpt
from repro.optim import optimizer as opt_lib


def _batch(key, b=3, t=11, a=5):
    ks = jax.random.split(key, 6)
    return {
        "actions": jax.random.randint(ks[0], (b, t), 0, a),
        "rewards": jax.random.normal(ks[1], (b, t)),
        "discounts": jnp.full((b, t), 0.95),
        "behaviour_logprob": -jnp.abs(jax.random.normal(ks[2], (b, t))),
        "bootstrap_value": jax.random.normal(ks[3], (b,)),
    }, jax.random.normal(ks[4], (b, t, a)), jax.random.normal(ks[5], (b, t))


@pytest.mark.parametrize("mode", ["vtrace", "onestep_is", "eps", "none"])
def test_correction_modes_shapes(mode):
    cfg = ImpalaConfig(correction=mode)
    batch, logits, values = _batch(jax.random.key(0))
    vs, adv = corrections.compute_correction(
        cfg, batch["behaviour_logprob"], logits, batch["actions"],
        batch["discounts"], batch["rewards"], values,
        batch["bootstrap_value"])
    assert vs.shape == values.shape and adv.shape == values.shape
    assert np.isfinite(np.asarray(vs)).all()


def test_onpolicy_all_modes_agree_on_value_target():
    """With pi == mu, every mode's value target is the n-step return."""
    batch, logits, values = _batch(jax.random.key(1))
    # make behaviour logprob equal target logprob
    blp = vt.action_log_probs(logits, batch["actions"])
    batch["behaviour_logprob"] = blp
    targets = []
    for mode in ["vtrace", "onestep_is", "none"]:
        cfg = ImpalaConfig(correction=mode)
        vs, _ = corrections.compute_correction(
            cfg, blp, logits, batch["actions"], batch["discounts"],
            batch["rewards"], values, batch["bootstrap_value"])
        targets.append(np.asarray(vs))
    np.testing.assert_allclose(targets[0], targets[1], atol=1e-5)
    np.testing.assert_allclose(targets[0], targets[2], atol=1e-5)


def test_impala_loss_finite_and_entropy_sign():
    cfg = ImpalaConfig(entropy_cost=0.01)
    batch, logits, values = _batch(jax.random.key(2))
    total, metrics = losses.impala_loss(cfg, logits, values, batch)
    assert np.isfinite(float(total))
    # entropy_loss = sum p log p <= 0
    assert float(metrics["loss/entropy"]) <= 0.0


def test_reward_clip_modes():
    r = jnp.array([-10.0, -0.5, 0.0, 0.5, 10.0])
    np.testing.assert_allclose(losses.reward_clip(r, "abs_one"),
                               [-1, -0.5, 0, 0.5, 1])
    soft = np.asarray(losses.reward_clip(r, "soft_asymmetric"))
    assert soft[0] == pytest.approx(0.3 * np.tanh(-10.0), abs=1e-6)
    assert soft[-1] == pytest.approx(5.0 * np.tanh(10.0), abs=1e-6)
    assert (soft >= -0.3).all() and (soft <= 5.0).all()


def test_policy_gradient_direction():
    """Gradient step should raise log-prob of positively-advantaged action."""
    logits = jnp.zeros((1, 1, 3))
    actions = jnp.array([[1]])
    adv = jnp.array([[2.0]])

    def loss(lg):
        return losses.policy_gradient_loss(lg, actions, adv)

    g = jax.grad(loss)(logits)
    assert float(g[0, 0, 1]) < 0  # descending raises logit of action 1


# ---------------------------------------------------------------------------
# replay / queue / lag


def test_replay_fifo_and_sample():
    # the sample-stream identity is now an explicit (seed, learner_id) —
    # the old no-arg default_rng(0) fallback is deliberately gone
    buf = ReplayBuffer(capacity=8, seed=0)
    for i in range(6):
        buf.add_batch({"x": jnp.full((2, 3), i)})
    assert len(buf) == 8
    s = buf.sample(4)
    assert s["x"].shape == (4, 3)
    # FIFO: oldest (i=0) entries were overwritten
    vals = set()
    for i in range(20):
        vals.update(np.asarray(buf.sample(8)["x"][:, 0]).tolist())
    assert 0.0 not in vals


def test_mix_batches_fraction():
    online = {"x": jnp.zeros((8, 2))}
    rep = {"x": jnp.ones((8, 2))}
    mixed = mix_batches(online, rep, 0.5)
    assert float(mixed["x"].sum()) == 8.0  # 4 rows of ones


def test_queue_put_reports_drop_before_eviction():
    q = TrajectoryQueue(capacity=2)
    assert q.put("a") and q.put("b")        # ring always accepts
    assert q.dropped == 0
    assert q.put("c")                       # full: "a" evicted, counted
    assert q.dropped == 1 and q.pushed == 3
    assert q.get() == "b" and q.get() == "c" and q.get() is None


def test_queue_and_lag():
    q = TrajectoryQueue(capacity=2)
    q.put(1), q.put(2), q.put(3)
    assert q.dropped == 1 and q.get() == 2
    lag = LagController(2, "p0")
    lag.on_update("p1")
    lag.on_update("p2")
    assert lag.actor_params() == "p0"
    lag.on_update("p3")
    assert lag.actor_params() == "p1"
    lag0 = LagController(0, "a")
    lag0.on_update("b")
    assert lag0.actor_params() == "b"


# ---------------------------------------------------------------------------
# PBT (Appendix F)


def test_pbt_exploit_copies_better_member():
    c = PBTController(pop_size=2, seed=0, threshold=0.05)
    c.report_fitness(0, 0.1)
    c.report_fitness(1, 0.9)
    weights = ["w0", "w1"]
    hyp_before = dict(c.members[0].hypers)
    copied_any = False
    for _ in range(10):
        h, copied = c.exploit_explore(0, step=100, weights=weights)
        copied_any |= copied
    assert copied_any and weights[0] == "w1"
    assert c.members[0].copied_from == 1
    del hyp_before


def test_pbt_burn_in_blocks_exploit():
    c = PBTController(pop_size=2, seed=0, burn_in_steps=1000)
    c.report_fitness(0, 0.0)
    c.report_fitness(1, 1.0)
    weights = ["w0", "w1"]
    _, copied = c.exploit_explore(0, step=10, weights=weights)
    assert not copied and weights[0] == "w0"


def test_pbt_explore_perturbs_by_factor():
    c = PBTController(pop_size=1, seed=0)
    h0 = dict(c.members[0].hypers)
    for _ in range(50):
        c.exploit_explore(0, step=0, weights=["w"])
    h1 = c.members[0].hypers
    for k in h0:
        ratio = np.log(h1[k] / h0[k]) / np.log(1.2)
        assert abs(ratio - round(ratio)) < 1e-6  # power of 1.2 exactly


# ---------------------------------------------------------------------------
# optimizer


def test_rmsprop_matches_manual():
    opt = opt_lib.rmsprop(decay=0.9, eps=0.1)
    params = {"w": jnp.array([1.0, 2.0])}
    state = opt.init(params)
    g = {"w": jnp.array([0.5, -1.0])}
    upd, state = opt.update(g, state, params, jnp.float32(0.1))
    ms = 0.1 * np.asarray(g["w"]) ** 2
    expect = -0.1 * np.asarray(g["w"]) / np.sqrt(ms + 0.1)
    np.testing.assert_allclose(np.asarray(upd["w"]), expect, rtol=1e-5)


def test_clip_by_global_norm():
    g = {"a": jnp.ones((4,)) * 3, "b": jnp.ones((4,)) * 4}
    clipped, norm = opt_lib.clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(10.0)
    total = np.sqrt(sum(float(jnp.sum(x ** 2))
                        for x in jax.tree.leaves(clipped)))
    assert total == pytest.approx(1.0, rel=1e-5)


def test_linear_schedule():
    fn = opt_lib.linear_schedule(1.0, 0.0, 100)
    assert float(fn(jnp.int32(0))) == 1.0
    assert float(fn(jnp.int32(50))) == pytest.approx(0.5)
    assert float(fn(jnp.int32(200))) == 0.0


# ---------------------------------------------------------------------------
# checkpoint


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    ckpt.save(str(tmp_path), 7, tree, extra={"note": "x"})
    like = jax.tree.map(np.zeros_like, jax.tree.map(np.asarray, tree))
    restored, step = ckpt.restore(str(tmp_path), tree)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
    assert ckpt.latest_step(str(tmp_path)) == 7
    del like


# ---------------------------------------------------------------------------
# metrics


def test_capped_normalised_score_matches_table_b1():
    """IMPALA row of Table B.1: 49.4% mean capped normalised."""
    assert capped_normalised_score([100], [100], [0]) == 1.0
    assert capped_normalised_score([250], [100], [0]) == 1.0  # capped
    assert capped_normalised_score([50], [100], [0]) == 0.5
    assert capped_normalised_score([5.8, 26.9], [10.0, 54.0],
                                   [0.1, 4.1]) == pytest.approx(
        (min(1.0, 5.7 / 9.9) + min(1.0, 22.8 / 49.9)) / 2)


def test_episode_tracker():
    tr = EpisodeTracker(2)
    tr.update(np.array([[1.0, 1.0], [0.5, 0.0]]),
              np.array([[False, True], [False, False]]))
    assert tr.completed == [2.0]
    tr.update(np.array([[0.0], [0.5]]), np.array([[False], [True]]))
    assert tr.completed == [2.0, 1.0]


def test_pg_q_estimate_variants_appendix_e3():
    """Appendix E.3: q_s from v_{s+1} (default) vs from V(x_{s+1}).
    On-policy with a perfect value function both coincide; off-policy
    they differ (the default carries rollout information)."""
    batch, logits, values = _batch(jax.random.key(5))
    base = ImpalaConfig(correction="vtrace")
    e3 = ImpalaConfig(correction="vtrace", pg_q_estimate="baseline_v")
    vs_a, adv_a = corrections.compute_correction(
        base, batch["behaviour_logprob"], logits, batch["actions"],
        batch["discounts"], batch["rewards"], values,
        batch["bootstrap_value"])
    vs_b, adv_b = corrections.compute_correction(
        e3, batch["behaviour_logprob"], logits, batch["actions"],
        batch["discounts"], batch["rewards"], values,
        batch["bootstrap_value"])
    np.testing.assert_allclose(np.asarray(vs_a), np.asarray(vs_b))
    assert not np.allclose(np.asarray(adv_a), np.asarray(adv_b))
    # last step: v_{T} == bootstrap == V(x_T) -> advantages agree there
    np.testing.assert_allclose(np.asarray(adv_a[:, -1]),
                               np.asarray(adv_b[:, -1]), atol=1e-5)


def test_mixed_precision_step_matches_f32():
    from repro.configs.registry import get_smoke_config
    from repro.core import learner as learner_lib
    from repro.models import backbone as bb
    from repro.models import common as pc

    cfg = get_smoke_config("stablelm_1_6b")
    icfg = ImpalaConfig(num_actions=9, learning_rate=1e-3)
    specs = bb.backbone_specs(cfg, 9)
    p32 = pc.init_params(specs, jax.random.key(0))
    key = jax.random.key(1)
    b, t = 2, 12
    batch = {"obs_token": jax.random.randint(key, (b, t + 1), 0,
                                             cfg.vocab_size),
             "actions": jax.random.randint(key, (b, t), 0, 9),
             "rewards": jax.random.normal(key, (b, t)),
             "discounts": jnp.full((b, t), 0.99),
             "behaviour_logprob": -jnp.ones((b, t))}
    ts32, opt = learner_lib.build_train_step(cfg, icfg, 9)
    _, _, m32 = jax.jit(ts32)(p32, opt.init(p32), jnp.int32(0), batch)
    tsmp, opt2 = learner_lib.build_train_step(cfg, icfg, 9,
                                              mixed_precision=True)
    p16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16)
                       if jnp.issubdtype(x.dtype, jnp.floating) else x, p32)
    os_mp = {"opt": opt2.init(p32), "master": p32}
    p16b, os2, mmp = jax.jit(tsmp)(p16, os_mp, jnp.int32(0), batch)
    assert jax.tree.leaves(p16b)[0].dtype == jnp.bfloat16
    assert jax.tree.leaves(os2["master"])[0].dtype == jnp.float32
    assert abs(float(m32["loss/total"]) - float(mmp["loss/total"])) < 0.05


@pytest.mark.parametrize("replay", [False, True])
def test_fused_step_equals_apply_of_grad_step(replay):
    """Both step builders compose the same gradient and apply functions:
    three fused updates and three ``apply_step(grad_step)`` updates land
    on the same params, with and without the replay target."""
    from repro.configs.registry import get_smoke_config
    from repro.core import learner as learner_lib
    from repro.data.envs import make_env
    from repro.models import backbone as bb
    from repro.models import common as pc

    env = make_env("bandit")
    a = env.num_actions
    arch = get_smoke_config("impala_shallow").replace(image_hw=env.image_hw)
    icfg = ImpalaConfig(num_actions=a, unroll_length=4, learning_rate=1e-3,
                        rmsprop_eps=0.01)
    specs = bb.backbone_specs(arch, a)
    params = pc.init_params(specs, jax.random.key(0))
    target = ((pc.init_params(specs, jax.random.key(1)),) if replay
              else ())
    rng = np.random.default_rng(0)
    b, t = 2, 4

    def batch():
        out = {
            "obs_image": rng.random((b, t + 1) + env.image_hw
                                    ).astype(np.float32),
            "last_action": rng.integers(0, a, (b, t + 1)).astype(np.int32),
            "last_reward": rng.random((b, t + 1)).astype(np.float32),
            "done_in": np.zeros((b, t + 1), np.bool_),
            "actions": rng.integers(0, a, (b, t)).astype(np.int32),
            "rewards": rng.standard_normal((b, t)).astype(np.float32),
            "discounts": np.full((b, t), 0.99, np.float32),
            "behaviour_logprob": np.full((b, t), -1.0, np.float32),
        }
        if replay:
            out["replay_mask"] = np.array([1.0, 0.0], np.float32)
        return out

    fused, opt = learner_lib.build_train_step(arch, icfg, a,
                                              vtrace_impl="scan")
    grad_step, apply_step, opt2 = learner_lib.build_grad_apply_steps(
        arch, icfg, a, vtrace_impl="scan")
    fused, grad_step, apply_step = (jax.jit(fused), jax.jit(grad_step),
                                    jax.jit(apply_step))
    pf, of = params, opt.init(params)
    ps, os_ = params, opt2.init(params)
    for i in range(3):
        bt = batch()
        pf, of, mf = fused(pf, of, jnp.int32(i), bt, *target)
        grads, ms = grad_step(ps, bt, *target)
        ps, os_, _ = apply_step(ps, os_, jnp.int32(i), grads)
        assert float(mf["loss/total"]) == pytest.approx(
            float(ms["loss/total"]), rel=1e-6)
        assert ("vtrace/traj_adv_mag" in mf) == replay
    for x, y in zip(jax.tree.leaves(pf), jax.tree.leaves(ps)):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        assert np.max(np.abs(x - y)) <= 1e-6 * max(np.max(np.abs(y)),
                                                   1e-30)
