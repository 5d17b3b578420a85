"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels import ops
from repro.kernels.vtrace import vtrace_pallas
from repro.kernels.linear_scan import linear_scan_pallas
from repro.kernels.decode_attention import decode_attention_pallas


# ---------------------------------------------------------------------------
# vtrace kernel


@pytest.mark.parametrize("t,b", [(1, 1), (7, 3), (64, 128), (100, 130),
                                 (257, 64), (512, 8)])
def test_vtrace_kernel_shapes(t, b):
    key = jax.random.key(t * 1000 + b)
    ks = jax.random.split(key, 6)
    rho = jnp.exp(jax.random.normal(ks[0], (t, b)) * 0.3).clip(max=1.0)
    disc = jnp.where(jax.random.uniform(ks[1], (t, b)) < 0.1, 0.0, 0.95)
    rew = jax.random.normal(ks[2], (t, b))
    v = jax.random.normal(ks[3], (t, b))
    vtp1 = jnp.concatenate([v[1:], jax.random.normal(ks[4], (1, b))], 0)
    vs_r, pg_r = ref.vtrace_ref(rho, rho, disc, rew, v, vtp1)
    vs_k, pg_k = vtrace_pallas(rho, rho, disc, rew, v, vtp1,
                               t_chunk=64, b_block=128)
    np.testing.assert_allclose(vs_r, vs_k, atol=1e-5)
    np.testing.assert_allclose(pg_r, pg_k, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 130), st.integers(1, 40),
       st.sampled_from([16, 64, 256]), st.integers(0, 2 ** 31 - 1))
def test_vtrace_kernel_property(t, b, chunk, seed):
    ks = jax.random.split(jax.random.key(seed), 6)
    rho = jnp.exp(jax.random.normal(ks[0], (t, b)) * 0.4).clip(max=2.0)
    c = jnp.minimum(rho, 1.0)
    disc = jnp.where(jax.random.uniform(ks[1], (t, b)) < 0.2, 0.0, 0.9)
    rew = jax.random.normal(ks[2], (t, b))
    v = jax.random.normal(ks[3], (t, b))
    vtp1 = jnp.concatenate([v[1:], jax.random.normal(ks[4], (1, b))], 0)
    vs_r, pg_r = ref.vtrace_ref(rho, c, disc, rew, v, vtp1)
    vs_k, pg_k = vtrace_pallas(rho, c, disc, rew, v, vtp1, t_chunk=chunk)
    np.testing.assert_allclose(vs_r, vs_k, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(pg_r, pg_k, atol=1e-4, rtol=1e-4)


def test_vtrace_interpret_resolution(monkeypatch):
    """Dispatch order: explicit arg > REPRO_PALLAS_INTERPRET env > backend
    auto-detect (interpret everywhere but TPU)."""
    from repro.kernels import vtrace as vk

    monkeypatch.delenv(vk.INTERPRET_ENV, raising=False)
    on_tpu = jax.default_backend() == "tpu"
    assert vk.resolve_interpret(None) is (not on_tpu)
    assert vk.resolve_interpret(True) is True
    assert vk.resolve_interpret(False) is False
    monkeypatch.setenv(vk.INTERPRET_ENV, "0")
    assert vk.resolve_interpret(None) is False
    monkeypatch.setenv(vk.INTERPRET_ENV, "1")
    assert vk.resolve_interpret(None) is True
    # explicit argument still beats the env override
    assert vk.resolve_interpret(False) is False


def test_losses_vtrace_impl_auto_resolution():
    from repro.core.losses import resolve_vtrace_impl

    expected = "fused" if jax.default_backend() == "tpu" else "scan"
    assert resolve_vtrace_impl("auto") == expected
    for explicit in ("fused", "scan", "pallas", "reference"):
        assert resolve_vtrace_impl(explicit) == explicit


def test_resolve_loss_impl_keeps_fused_only_for_the_plain_vtrace_loss():
    from repro.configs.base import ImpalaConfig
    from repro.core.losses import resolve_loss_impl

    cfg = ImpalaConfig(num_actions=5)
    plain = "pallas" if jax.default_backend() == "tpu" else "scan"
    assert resolve_loss_impl(cfg, "fused") == "fused"
    # replay (target baseline, per-trajectory advantages) and the
    # ablation corrections keep their own math
    assert resolve_loss_impl(cfg, "fused", replay=True) == plain
    assert resolve_loss_impl(
        ImpalaConfig(num_actions=5, correction="onestep_is"),
        "fused") == plain
    assert resolve_loss_impl(cfg, "scan", replay=True) == "scan"


# ---------------------------------------------------------------------------
# fused loss/V-trace kernel


def _fused_inputs(t, b, a, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    logits = jax.random.normal(ks[0], (t, b, a)) * 2.0
    actions = jax.random.randint(ks[1], (t, b), 0, a)
    onehot = jax.nn.one_hot(actions, a, dtype=jnp.float32)
    # behaviour log-probs of the taken actions under a perturbed policy
    blogp = jnp.sum(jax.nn.log_softmax(
        logits + jax.random.normal(ks[2], (t, b, a)) * 0.3) * onehot, -1)
    disc = jnp.where(jax.random.uniform(ks[3], (t, b)) < 0.1, 0.0, 0.97)
    rew = jax.random.normal(ks[4], (t, b))
    v = jax.random.normal(ks[5], (t, b))
    vtp1 = jnp.concatenate([v[1:], jnp.zeros((1, b))], 0)
    return logits, onehot, blogp, disc, rew, v, vtp1


def _fused_oracle(logits, onehot, blogp, disc, rew, v, vtp1,
                  rho_bar, c_bar, lambda_):
    """Unfused composition: XLA log-softmax + the ref V-trace scan."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    p = jnp.exp(logp)
    tlp = jnp.sum(logp * onehot, axis=-1)
    ne = jnp.sum(p * logp, axis=-1)
    log_rho = jax.lax.stop_gradient(tlp) - blogp
    rho = jnp.exp(log_rho)
    clip_rho = rho if rho_bar is None else jnp.minimum(rho, rho_bar)
    c = rho if c_bar is None else jnp.minimum(rho, c_bar)
    vs, pg = ref.vtrace_ref(clip_rho, lambda_ * c, disc, rew, v, vtp1)
    return tlp, ne, vs, pg


@pytest.mark.parametrize("t,b,a,chunk", [
    (1, 1, 2, 256), (8, 4, 6, 256), (64, 16, 128, 16),
    (300, 3, 9, 64), (37, 130, 5, 256),
])
def test_fused_loss_vtrace_matches_unfused(t, b, a, chunk):
    from repro.kernels.vtrace import loss_vtrace_pallas

    inp = _fused_inputs(t, b, a, seed=t * 131 + b * 7 + a)
    want = _fused_oracle(*inp, 1.0, 1.0, 1.0)
    got = loss_vtrace_pallas(*inp, rho_bar=1.0, c_bar=1.0, lambda_=1.0,
                             t_chunk=chunk)
    for name, w, g in zip(("tlp", "ne", "vs", "pg_adv"), want, got):
        np.testing.assert_allclose(np.asarray(w), np.asarray(g),
                                   atol=1e-5, rtol=1e-5, err_msg=name)


@pytest.mark.parametrize("rho_bar,c_bar,lambda_", [
    (None, None, 1.0), (2.0, 1.0, 1.0), (1.0, 1.0, 0.9),
])
def test_fused_loss_vtrace_clip_variants(rho_bar, c_bar, lambda_):
    from repro.kernels.vtrace import loss_vtrace_pallas

    inp = _fused_inputs(40, 6, 7, seed=99)
    want = _fused_oracle(*inp, rho_bar, c_bar, lambda_)
    got = loss_vtrace_pallas(*inp, rho_bar=rho_bar, c_bar=c_bar,
                             lambda_=lambda_, t_chunk=16)
    for name, w, g in zip(("tlp", "ne", "vs", "pg_adv"), want, got):
        np.testing.assert_allclose(np.asarray(w), np.asarray(g),
                                   atol=1e-5, rtol=1e-5, err_msg=name)


def test_fused_loss_vtrace_gradients_match_unfused():
    """custom_vjp backward: d(loss)/d(logits) of the assembled IMPALA
    total matches autodiff through the unfused composition. vs/pg_adv
    are stop-gradient targets in both formulations."""
    from repro.kernels.vtrace import fused_loss_vtrace

    inp = _fused_inputs(50, 8, 11, seed=7)
    logits = inp[0]
    rest = inp[1:]

    def total_fused(lg):
        tlp, ne, vs, pg = fused_loss_vtrace(lg, *rest, 1.0, 1.0, 1.0)
        vs = jax.lax.stop_gradient(vs)
        pg = jax.lax.stop_gradient(pg)
        return (-jnp.sum(pg * tlp)
                + 0.5 * jnp.sum(jnp.square(vs - inp[5]))
                + 0.01 * jnp.sum(ne))

    def total_unfused(lg):
        tlp, ne, vs, pg = _fused_oracle(lg, *rest, 1.0, 1.0, 1.0)
        vs = jax.lax.stop_gradient(vs)
        pg = jax.lax.stop_gradient(pg)
        return (-jnp.sum(pg * tlp)
                + 0.5 * jnp.sum(jnp.square(vs - inp[5]))
                + 0.01 * jnp.sum(ne))

    lf, gf = jax.value_and_grad(total_fused)(logits)
    lu, gu = jax.value_and_grad(total_unfused)(logits)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lu),
                               atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gu),
                               atol=1e-5, rtol=1e-5)


def test_impala_loss_fused_impl_matches_scan():
    """End-to-end: the learner loss under impl='fused' equals impl='scan'
    in value and logits/values gradients."""
    from repro.configs.base import ImpalaConfig
    from repro.core.losses import impala_loss

    cfg = ImpalaConfig(num_actions=5, unroll_length=20)
    b, t, a = 6, 20, 5
    ks = jax.random.split(jax.random.key(3), 6)
    logits = jax.random.normal(ks[0], (b, t, a))
    values = jax.random.normal(ks[1], (b, t))
    actions = jax.random.randint(ks[2], (b, t), 0, a)
    onehot = jax.nn.one_hot(actions, a)
    batch = {
        "actions": actions,
        "rewards": jax.random.normal(ks[3], (b, t)),
        "discounts": jnp.full((b, t), 0.99),
        "behaviour_logprob": jnp.sum(jax.nn.log_softmax(
            logits + jax.random.normal(ks[4], (b, t, a)) * 0.2) * onehot,
            -1),
        "bootstrap_value": jax.random.normal(ks[5], (b,)),
    }

    def run(impl):
        def f(lg, vv):
            total, _ = impala_loss(cfg, lg, vv, batch, impl=impl)
            return total
        total, grads = jax.value_and_grad(f, argnums=(0, 1))(logits, values)
        return total, grads

    tf_, (glf, gvf) = run("fused")
    ts_, (gls, gvs) = run("scan")
    np.testing.assert_allclose(np.asarray(tf_), np.asarray(ts_),
                               atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(glf), np.asarray(gls),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gvf), np.asarray(gvs),
                               atol=1e-4, rtol=1e-5)


# ---------------------------------------------------------------------------
# linear scan kernel


@pytest.mark.parametrize("t,n", [(1, 1), (16, 64), (100, 300), (512, 1024),
                                 (33, 7), (257, 129)])
def test_linear_scan_shapes(t, n):
    ks = jax.random.split(jax.random.key(t + n), 3)
    a = jax.random.uniform(ks[0], (t, n), minval=0.5, maxval=1.0)
    b = jax.random.normal(ks[1], (t, n))
    h0 = jax.random.normal(ks[2], (n,))
    r = ref.linear_scan_ref(a, b, h0)
    k = linear_scan_pallas(a, b, h0, t_chunk=64, n_block=128)
    np.testing.assert_allclose(r, k, atol=1e-5, rtol=1e-5)


def test_linear_scan_zero_h0():
    a = jnp.full((20, 32), 0.9)
    b = jnp.ones((20, 32))
    r = ref.linear_scan_ref(a, b)
    k = linear_scan_pallas(a, b)
    np.testing.assert_allclose(r, k, rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 200), st.integers(1, 64), st.integers(0, 2 ** 31 - 1))
def test_linear_scan_property(t, n, seed):
    ks = jax.random.split(jax.random.key(seed), 3)
    a = jax.random.uniform(ks[0], (t, n), minval=0.0, maxval=1.0)
    b = jax.random.normal(ks[1], (t, n))
    r = ref.linear_scan_ref(a, b)
    k = linear_scan_pallas(a, b, t_chunk=32, n_block=64)
    np.testing.assert_allclose(r, k, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# decode attention kernel


@pytest.mark.parametrize("b,h,kh,s,d", [
    (1, 1, 1, 8, 64), (2, 8, 2, 300, 64), (4, 16, 16, 1024, 128),
    (1, 10, 1, 2000, 256), (3, 12, 4, 100, 32),
])
def test_decode_attention_shapes(b, h, kh, s, d):
    ks = jax.random.split(jax.random.key(b * s + h), 4)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kh, d), jnp.float32)
    lens = jax.random.randint(ks[3], (b,), 1, s + 1)
    bias = jnp.where(jnp.arange(s)[None] < lens[:, None], 0.0, -1e30)
    r = ref.decode_attention_ref(q, k, v, bias)
    p = decode_attention_pallas(q, k, v, bias, s_chunk=256)
    np.testing.assert_allclose(np.asarray(r), np.asarray(p),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_bf16():
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (2, 8, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, 128, 4, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, 128, 4, 64), jnp.bfloat16)
    bias = jnp.zeros((2, 128))
    r = ref.decode_attention_ref(q, k, v, bias)
    p = decode_attention_pallas(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(r, np.float32),
                               np.asarray(p, np.float32), atol=3e-2)


# ---------------------------------------------------------------------------
# ops dispatch wrappers


def test_ops_vtrace_dispatch():
    ks = jax.random.split(jax.random.key(0), 5)
    b, t = 4, 37
    log_rhos = jax.random.normal(ks[0], (b, t)) * 0.3
    disc = jnp.full((b, t), 0.95)
    rew = jax.random.normal(ks[1], (b, t))
    v = jax.random.normal(ks[2], (b, t))
    boot = jax.random.normal(ks[3], (b,))
    vs1, pg1 = ops.vtrace(log_rhos, disc, rew, v, boot, impl="ref")
    vs2, pg2 = ops.vtrace(log_rhos, disc, rew, v, boot, impl="pallas")
    np.testing.assert_allclose(vs1, vs2, atol=1e-5)
    np.testing.assert_allclose(pg1, pg2, atol=1e-5)


def test_ops_linear_scan_dispatch():
    a = jnp.full((12, 16), 0.8)
    b = jnp.ones((12, 16))
    r1 = ops.linear_scan(a, b, impl="ref")
    r2 = ops.linear_scan(a, b, impl="pallas")
    np.testing.assert_allclose(r1, r2, rtol=1e-6)


# ---------------------------------------------------------------------------
# flash attention (prefill) kernel


@pytest.mark.parametrize("b,t,h,kh,d,causal,window", [
    (1, 64, 2, 2, 32, True, 0),
    (2, 100, 4, 2, 64, True, 0),
    (1, 128, 4, 1, 32, True, 24),
    (1, 50, 2, 2, 16, False, 0),
    (2, 200, 8, 4, 64, True, 64),
    (1, 33, 3, 1, 8, True, 5),
])
def test_flash_attention_shapes(b, t, h, kh, d, causal, window):
    from repro.kernels.flash_attention import flash_attention_pallas
    ks = jax.random.split(jax.random.key(b * t + h), 3)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, kh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, kh, d), jnp.float32)
    o_ref = ref.flash_attention_ref(q, k, v, causal, window)
    o_ker = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                   q_block=32, kv_block=32)
    np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_ker),
                               atol=3e-5, rtol=3e-5)


@settings(max_examples=12, deadline=None)
@given(st.integers(1, 80), st.sampled_from([(2, 2), (4, 2), (4, 1)]),
       st.integers(0, 30), st.integers(0, 2 ** 31 - 1))
def test_flash_attention_property(t, heads, window, seed):
    from repro.kernels.flash_attention import flash_attention_pallas
    h, kh = heads
    d = 16
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (1, t, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, t, kh, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, t, kh, d), jnp.float32)
    o_ref = ref.flash_attention_ref(q, k, v, True, window)
    o_ker = flash_attention_pallas(q, k, v, causal=True, window=window,
                                   q_block=16, kv_block=16)
    np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_ker),
                               atol=5e-5, rtol=5e-5)


def test_ops_flash_attention_dispatch():
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (1, 40, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 40, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 40, 2, 16), jnp.float32)
    a = ops.flash_attention(q, k, v, impl="ref")
    b = ops.flash_attention(q, k, v, impl="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)
