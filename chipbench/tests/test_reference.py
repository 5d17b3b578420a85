"""The plain reference against the program's forward pass, loss and
gradients, on the reference's weights, at a small size on the CPU."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from chipbench import reference, run
from chipbench.tests import tiny


def random_batch(cfg, rows, seed=0):
    t, a = cfg["unroll_length"], cfg["num_actions"]
    h, w, c = cfg["frame"]
    rng = np.random.default_rng(seed)
    done = rng.random((rows, t + 1)) < 0.1
    return {
        "obs_image": rng.integers(0, 256, (rows, t + 1, h, w, c), np.uint8),
        "last_action": rng.integers(0, a, (rows, t + 1), np.int32),
        "last_reward": rng.normal(size=(rows, t + 1)).astype(np.float32),
        "done_in": done,
        "lstm_state": (rng.normal(size=(rows, cfg["lstm_width"]))
                       .astype(np.float32) * 0.1,
                       rng.normal(size=(rows, cfg["lstm_width"]))
                       .astype(np.float32) * 0.1),
        "actions": rng.integers(0, a, (rows, t), np.int32),
        "rewards": rng.normal(size=(rows, t)).astype(np.float32) * 2,
        "discounts": 0.99 * (1.0 - done[:, 1:]).astype(np.float32),
        "behaviour_logprob": (np.log(1.0 / a) + 0.3 * rng.normal(
            size=(rows, t))).astype(np.float32),
    }


@pytest.fixture(scope="module", params=[tiny.SHALLOW, tiny.DEEP])
def case(request):
    f = tiny.files(*request.param)
    arch, icfg = run.program_configs(f)
    cfg = f["config"]
    return f, arch, icfg, cfg, reference.init_params(cfg, 2 ** 31 + 5)


def test_weights_have_the_programs_layout(case):
    from repro.models import backbone as bb
    from repro.models import common

    _f, arch, _icfg, cfg, params = case
    want = common.abstract_params(bb.backbone_specs(arch,
                                                    cfg["num_actions"]))
    got = jax.eval_shape(lambda: params)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert all(g.shape == w.shape and g.dtype == w.dtype for g, w in
               zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def test_seed_makes_the_weights(case):
    cfg = case[3]
    a = reference.init_params(cfg, 2 ** 31 + 5)
    b = reference.init_params(cfg, 2 ** 31 + 5)
    c = reference.init_params(cfg, 2 ** 31 + 5 + 2 ** 32)
    leaf = lambda t: np.asarray(t["torso"]["fc"]["kernel"])  # noqa: E731
    assert np.array_equal(leaf(a), leaf(b))
    assert not np.array_equal(leaf(a), leaf(c))


def test_forward_loss_and_gradients_match_the_program(case):
    from repro.core import learner as learner_lib

    _f, arch, icfg, cfg, params = case
    batch = random_batch(cfg, 16)
    a = cfg["num_actions"]
    logits, values, _ = jax.jit(lambda p, b: learner_lib.forward_trajectory(
        p, b, arch, a))(params, batch)
    r_logits, r_values = reference.agent(cfg, params, batch)
    np.testing.assert_allclose(logits, r_logits, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(values, r_values, rtol=1e-5, atol=1e-6)

    loss_fn = learner_lib.build_loss_fn(arch, icfg, a, vtrace_impl="scan")
    (p_loss, _), p_grads = jax.jit(jax.value_and_grad(
        loss_fn, has_aux=True))(params, batch)
    r_loss, r_tlp, r_grads = reference.loss_and_grads(cfg, params, batch)
    # a sum of terms of either sign: absolute room for its cancellation
    np.testing.assert_allclose(float(p_loss), r_loss, rtol=1e-5, atol=1e-4)
    logp = jax.nn.log_softmax(np.asarray(logits)[:, :-1], axis=-1)
    np.testing.assert_allclose(
        np.take_along_axis(logp, batch["actions"][..., None], -1)[..., 0],
        r_tlp, rtol=1e-5, atol=1e-6)
    for g, r in zip(jax.tree.leaves(p_grads), jax.tree.leaves(r_grads)):
        np.testing.assert_allclose(g, r, rtol=2e-4,
                                   atol=2e-5 * float(np.max(np.abs(r))))


def test_blocks_of_rows_sum_to_the_whole_batch(case):
    cfg, params = case[3], case[4]
    batch = random_batch(cfg, 16, seed=1)
    whole = jax.jit(jax.grad(lambda p: reference.vtrace_loss(
        cfg["learning"], *reference.agent(cfg, p, batch), batch)[0]))
    _, _, blocked = reference.loss_and_grads(cfg, params, batch)
    for g, r in zip(jax.tree.leaves(blocked),
                    jax.tree.leaves(whole(params))):
        np.testing.assert_allclose(g, r, rtol=1e-4,
                                   atol=1e-5 * float(np.max(np.abs(r))))
