"""The learner consume-path overhaul: dynamic-batch collection keeps
oldest-first order under partial buckets, donation really retires the
old params/opt_state buffers while everything published stays live, and
the staged host stacking is bit-identical to the np.concatenate it
replaced (ping-pong included)."""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ImpalaConfig
from repro.distributed import TrajectoryItem, TrajectoryQueue
from repro.distributed.runtime import (_buckets, _collect_batch,
                                       _HostStager, _stack)


def _item(i, b=2, t=3):
    rng = np.random.default_rng(i)
    data = {"x": rng.standard_normal((b, t)).astype(np.float32),
            "n": np.full((b,), i, np.int32)}
    return TrajectoryItem(data, param_version=i, actor_id=0,
                          produced_at=float(i))


# ---------------------------------------------------------------------------
# bucket collection / requeue ordering


def test_buckets_descending_powers_of_two():
    assert _buckets(1) == [1]
    assert _buckets(4) == [4, 2, 1]
    assert _buckets(6) == [4, 2, 1]     # non-pow2 max rounds down


def test_collect_batch_partial_bucket_keeps_oldest_first():
    """5 queued with max bucket 4: first batch = the 4 oldest, the 5th
    (popped during the greedy drain) goes back to the *front*; the next
    batch starts with it. No trajectory is reordered or lost."""
    q = TrajectoryQueue(capacity=8, policy="block")
    for i in range(5):
        q.put(_item(i))
    first = q.get_nowait()
    batch = _collect_batch(q, _buckets(4), first)
    assert [it.param_version for it in batch] == [0, 1, 2, 3]
    assert len(q) == 1
    nxt = q.get_nowait()
    assert nxt.param_version == 4


def test_collect_batch_trims_to_pow2_and_requeues_overflow_in_order():
    """3 queued with max bucket 4 -> batch of 2 (largest pow2 <= 3), the
    third requeued at the front in its original position."""
    q = TrajectoryQueue(capacity=8, policy="block")
    for i in range(3):
        q.put(_item(i))
    first = q.get_nowait()
    batch = _collect_batch(q, _buckets(4), first)
    assert [it.param_version for it in batch] == [0, 1]
    # the overflow is next, still ahead of anything newly produced
    q.put(_item(99))
    nxt = q.get_nowait()
    assert nxt.param_version == 2
    batch2 = _collect_batch(q, _buckets(4), nxt)
    assert [it.param_version for it in batch2] == [2, 99]


# ---------------------------------------------------------------------------
# donation safety


def test_donated_train_step_retires_inputs_and_snapshot_survives():
    """The exact discipline the async runtime relies on: after a donated
    call, the input params/opt_state buffers are dead (reuse raises),
    while a jitted pre-call copy — what the runtime publishes — stays
    fully usable. Skips if this backend ignores donation."""
    from repro.core import learner as learner_lib
    from repro.core.driver import small_arch
    from repro.data.envs import make_bandit
    from repro.models import backbone as bb
    from repro.models import common as pcommon

    env = make_bandit()
    arch = small_arch(env)
    icfg = ImpalaConfig(num_actions=env.num_actions, unroll_length=4,
                        learning_rate=1e-3, rmsprop_eps=0.01)
    specs = bb.backbone_specs(arch, env.num_actions)
    params = pcommon.init_params(specs, jax.random.key(0))
    train_step, opt = learner_lib.build_train_step(arch, icfg,
                                                   env.num_actions)
    train_step = jax.jit(train_step, donate_argnums=(0, 1))
    opt_state = opt.init(params)
    snapshot = jax.jit(lambda t: jax.tree.map(jnp.copy, t))

    b, t, hw = 2, 4, env.image_hw
    rng = np.random.default_rng(0)
    batch = {
        "obs_image": rng.integers(0, 255, (b, t + 1) + hw).astype(np.uint8),
        "last_action": np.zeros((b, t + 1), np.int32),
        "last_reward": np.zeros((b, t + 1), np.float32),
        "done_in": np.zeros((b, t + 1), bool),
        "lstm_state": tuple(np.zeros((b, arch.lstm_width), np.float32)
                            for _ in range(2)),
        "actions": np.zeros((b, t), np.int32),
        "rewards": rng.standard_normal((b, t)).astype(np.float32),
        "discounts": np.full((b, t), 0.99, np.float32),
        "behaviour_logprob": np.full((b, t), -1.0, np.float32),
        "done": np.zeros((b, t), bool),
    }
    published = snapshot(params)
    old_leaf = jax.tree.leaves(params)[0]
    old_opt_leaf = jax.tree.leaves(opt_state)[0]
    new_params, new_opt, metrics = train_step(params, opt_state,
                                              jnp.int32(0), batch)
    jax.block_until_ready(new_params)
    if not old_leaf.is_deleted():
        pytest.skip("backend ignores donation; nothing to enforce")
    assert old_opt_leaf.is_deleted()
    # the donated originals must raise on reuse ... (jax raises
    # RuntimeError on its first dispatch of the shape, and the runtime's
    # ValueError once a cached executable meets the dead buffer)
    with pytest.raises((RuntimeError, ValueError), match="deleted"):
        jnp.sum(old_leaf).block_until_ready()
    # ... while the published snapshot and the new trees stay live
    jax.block_until_ready(jax.tree.map(jnp.sum, published))
    jax.block_until_ready(jax.tree.map(jnp.sum, new_params))
    assert np.isfinite(float(metrics["loss/total"]))
    # and a second update over the fresh trees still works (in-place
    # reuse did not corrupt the chain)
    p2, o2, m2 = train_step(new_params, new_opt, jnp.int32(1), batch)
    jax.block_until_ready(p2)
    assert np.isfinite(float(m2["loss/total"]))


@pytest.mark.timeout_s(300)
def test_async_runtime_donate_toggle_trains():
    """donate=False must remain a supported escape hatch, and both
    settings must produce a full run with live telemetry."""
    from repro.distributed import run_async_training

    icfg = ImpalaConfig(num_actions=3, unroll_length=8,
                        learning_rate=1e-3, entropy_cost=0.003,
                        rmsprop_eps=0.01)
    for donate in (True, False):
        tracker, metrics, tel = run_async_training(
            "bandit", icfg, num_envs=4, steps=4, num_actors=2,
            queue_capacity=4, queue_policy="block", max_batch_trajs=2,
            seed=1, donate=donate)
        assert tel["learner_updates"] == 4, donate
        assert tel["donate"] is donate
        assert np.isfinite(float(metrics["loss/total"])), donate


def test_param_mirror_upload_never_aliases_host_buffer():
    """The process-actor subscriber decodes every publish into one
    reused host mirror and uploads with jnp.array. The upload MUST be a
    guaranteed copy: jnp.asarray zero-copy aliases 64-byte-aligned host
    buffers on the CPU backend, and an aliased param leaf would be torn
    by the next publish's in-place decode while the unroll reads it.
    Probes on a deterministically 64-aligned view so the result doesn't
    depend on allocator luck."""
    raw = np.zeros(1024 + 16, np.float32)
    off = (-raw.ctypes.data) % 64 // raw.itemsize
    mirror_leaf = raw[off:off + 1024]
    params = jax.tree.map(jnp.array, {"w": mirror_leaf})
    jax.block_until_ready(params)
    mirror_leaf[:] = 7.0                    # the next publish's decode
    np.testing.assert_array_equal(np.asarray(params["w"]),
                                  np.zeros(1024, np.float32))


# ---------------------------------------------------------------------------
# staged host stacking


def _np_items(k, b=3, shapes=((4,), (2, 5)), dtypes=(np.float32, np.int32),
              seed=0):
    rng = np.random.default_rng(seed)
    items = []
    for i in range(k):
        data = {
            "a": rng.standard_normal((b,) + shapes[0]).astype(dtypes[0]),
            "nest": {"z": rng.integers(0, 9, (b,) + shapes[1])
                     .astype(dtypes[1])},
            "state": tuple(rng.standard_normal((b, 3)).astype(np.float32)
                           for _ in range(2)),
        }
        items.append(TrajectoryItem(data, i, 0, time.monotonic()))
    return items


def _concat_reference(items):
    return jax.tree.map(lambda *xs: np.concatenate(xs, axis=0),
                        *[it.data for it in items])


def test_staged_stack_matches_concatenate_reference():
    stager = _HostStager()
    items = _np_items(4)
    out = _stack(items, stager)
    ref = _concat_reference(items)
    for got, want in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        assert isinstance(got, jax.Array)
        np.testing.assert_array_equal(np.asarray(got), want)


def test_staged_stack_reuse_decision_matches_device_put_semantics():
    """The stager may only reuse staging buffers where device_put
    COPIES; on backends that zero-copy alias host memory (the CPU
    backend aliases 64-byte-aligned buffers) it must allocate fresh
    buffers per stack — an aliased batch has no completion event to
    wait on before a rewrite."""
    from repro.distributed.runtime import _device_put_copies

    stager = _HostStager()
    assert stager._reuse is _device_put_copies()
    _stack(_np_items(2, seed=1), stager)
    _stack(_np_items(2, seed=2), stager)
    if stager._reuse:
        # one (bucket, structure) slot, two ping-ponged buffer sets
        assert len(stager._slots) == 1
    else:
        assert not stager._slots       # fresh buffers every call


def test_staged_stack_sequence_does_not_corrupt_earlier_batches():
    """Three consecutive stacks of the same bucket: the first batch must
    keep its values after later stacks — whether the stager ping-pongs
    preallocated buffers (copying backends) or allocates fresh ones
    (aliasing backends)."""
    stager = _HostStager()
    a = _stack(_np_items(2, seed=1), stager)
    a_host = jax.tree.map(np.asarray, a)
    b = _stack(_np_items(2, seed=2), stager)
    c = _stack(_np_items(2, seed=3), stager)
    jax.block_until_ready((b, c))
    for got, want in zip(jax.tree.leaves(a), jax.tree.leaves(a_host)):
        np.testing.assert_array_equal(np.asarray(got), want)


def test_staged_stack_handles_readonly_views_and_bf16():
    """Serialized transports deliver read-only zero-copy views, and
    params/trajectories may carry bfloat16 — both must stage."""
    import ml_dtypes
    from repro.distributed import serde

    items = []
    for i in range(2):
        data = {"x": np.arange(6, dtype=np.float32).reshape(3, 2) + i,
                "h": (np.ones((3, 2)) * i).astype(ml_dtypes.bfloat16)}
        buf = serde.encode_item(TrajectoryItem(data, i, 0, 0.0))
        items.append(serde.decode_item(buf))    # read-only views
    assert not jax.tree.leaves(items[0].data)[0].flags.writeable
    stager = _HostStager()
    out = _stack(items, stager)
    ref = _concat_reference(items)
    for got, want in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(got), want)


def test_staged_stack_falls_back_on_ragged_batches():
    """Mismatched per-item shapes are not the hot path but must still
    stack correctly via the concatenate fallback."""
    stager = _HostStager()
    i1 = TrajectoryItem({"x": np.ones((2, 3), np.float32)}, 0, 0, 0.0)
    i2 = TrajectoryItem({"x": np.zeros((4, 3), np.float32)}, 1, 0, 0.0)
    out = _stack([i1, i2], stager)
    assert out["x"].shape == (6, 3)
    assert not stager._slots       # staging never engaged


def test_stack_single_item_passthrough_and_device_leaves():
    stager = _HostStager()
    i1 = TrajectoryItem({"x": np.ones((2, 3), np.float32)}, 0, 0, 0.0)
    assert _stack([i1], stager) is i1.data
    d1 = TrajectoryItem({"x": jnp.ones((2, 3))}, 0, 0, 0.0)
    d2 = TrajectoryItem({"x": jnp.zeros((2, 3))}, 1, 0, 0.0)
    out = _stack([d1, d2], stager)
    assert out["x"].shape == (4, 3)
    assert not stager._slots       # device leaves keep the jnp path
