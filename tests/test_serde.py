"""The serialization boundary: a TrajectoryItem flattened to one
contiguous buffer must come back *exactly* — same nesting, same dict key
order, same dtypes (bfloat16 included), same bits (NaN payloads too).
No jax at module level: this is the layer actor processes import."""
import sys

import ml_dtypes
import numpy as np
import pytest

from hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from repro.distributed import serde

DTYPES = [np.float32, np.float64, np.float16, np.int32, np.int64,
          np.uint8, np.bool_, ml_dtypes.bfloat16]


def _rand(rng, shape, dtype):
    dt = np.dtype(dtype)
    if dt == np.bool_:
        return rng.integers(0, 2, shape).astype(bool)
    if dt.kind in "iu":
        return rng.integers(0, 100, shape).astype(dt)
    return rng.standard_normal(shape).astype(dt)


def _assert_same_tree(a, b, path="$"):
    assert type(a) is type(b), (path, type(a), type(b))
    if a is None:
        return
    if isinstance(a, dict):
        assert list(a.keys()) == list(b.keys()), path  # order, not just set
        for k in a:
            _assert_same_tree(a[k], b[k], f"{path}/{k}")
        return
    if isinstance(a, (list, tuple)):
        assert len(a) == len(b), path
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_same_tree(x, y, f"{path}[{i}]")
        return
    a, b = np.asarray(a), np.asarray(b)     # leaf: same dtype and shape
    assert a.dtype == b.dtype and a.shape == b.shape, path


def _assert_leaves_bitexact(a, b, path="$"):
    if isinstance(a, dict):
        for k in a:
            _assert_leaves_bitexact(a[k], b[k], f"{path}/{k}")
    elif isinstance(a, (list, tuple)):
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_leaves_bitexact(x, y, f"{path}[{i}]")
    elif a is not None:
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype, path
        assert a.shape == b.shape, path
        assert a.tobytes() == b.tobytes(), f"bits differ at {path}"


def _roundtrip(tree):
    out, _meta = serde.decode_tree(serde.encode_tree(tree))
    _assert_leaves_bitexact(tree, out)
    return out


# ---------------------------------------------------------------------------
# plain tests


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: np.dtype(d).name)
def test_roundtrip_each_dtype(dtype):
    rng = np.random.default_rng(0)
    tree = {"x": _rand(rng, (3, 4), dtype), "y": _rand(rng, (7,), dtype)}
    out = _roundtrip(tree)
    assert out["x"].dtype == np.dtype(dtype)


def test_roundtrip_nested_structure_and_key_order():
    rng = np.random.default_rng(1)
    tree = {
        "zulu": _rand(rng, (2, 3), np.float32),          # deliberately not
        "alpha": {"m": _rand(rng, (4,), np.int32),        # sorted: insertion
                  "a": _rand(rng, (1,), np.float64)},     # order must hold
        "mid": [_rand(rng, (2,), np.uint8),
                (_rand(rng, (5,), ml_dtypes.bfloat16), None)],
        "none": None,
    }
    out = _roundtrip(tree)
    _assert_same_tree(tree, out)
    assert list(out.keys()) == ["zulu", "alpha", "mid", "none"]
    assert list(out["alpha"].keys()) == ["m", "a"]
    assert isinstance(out["mid"], list)
    assert isinstance(out["mid"][1], tuple)
    assert out["mid"][1][1] is None


def test_roundtrip_empty_leaves_and_scalars():
    tree = {"empty_f": np.zeros((0, 5), np.float32),
            "empty_b": np.zeros((3, 0), bool),
            "scalar": np.float32(1.5),
            "pyint": 7,                       # encoded as 0-d int array
            "zerod": np.array(2.5, np.float64)}
    out = _roundtrip(tree)
    assert out["empty_f"].shape == (0, 5)
    assert out["empty_b"].shape == (3, 0)
    assert out["scalar"].shape == ()
    assert int(out["pyint"]) == 7


def test_roundtrip_nan_and_inf_bit_patterns():
    weird = np.array([np.nan, -np.nan, np.inf, -np.inf, -0.0], np.float32)
    _roundtrip({"w": weird, "bf": weird.astype(ml_dtypes.bfloat16)})


def test_noncontiguous_input_roundtrips():
    base = np.arange(24, dtype=np.float32).reshape(4, 6)
    view = base[::2, ::3]                    # strided, non-contiguous
    out = _roundtrip({"v": view})
    assert np.array_equal(out["v"], view)


def test_item_provenance_roundtrip():
    item = serde.TrajectoryItem({"r": np.ones(3, np.float32)},
                                param_version=42, actor_id=3,
                                produced_at=123.456)
    out = serde.decode_item(serde.encode_item(item))
    assert (out.param_version, out.actor_id) == (42, 3)
    assert out.produced_at == pytest.approx(123.456)
    assert out.data["r"].tobytes() == item.data["r"].tobytes()


def test_decode_is_zero_copy_and_copy_flag_writable():
    buf = serde.encode_tree({"x": np.arange(5, dtype=np.int32)})
    view, _ = serde.decode_tree(buf)
    assert not view["x"].flags.writeable    # view into the buffer
    owned, _ = serde.decode_tree(buf, copy=True)
    owned["x"][0] = 99                      # writable copy
    assert owned["x"][0] == 99


def test_decode_tree_into_reuses_buffers_and_matches_fresh_decode():
    """The subscriber's steady-state path: repeated payloads land in the
    same preallocated leaves (no per-pull tree alloc), bit-identical to
    a fresh copying decode."""
    rng = np.random.default_rng(0)
    make = lambda: {  # noqa: E731
        "w": rng.standard_normal((3, 4)).astype(np.float32),
        "nest": {"b": rng.integers(0, 99, (5,)).astype(np.int64)},
        "state": (rng.standard_normal(2).astype(ml_dtypes.bfloat16), None),
    }
    first = make()
    dst, _ = serde.decode_tree(serde.encode_tree(first), copy=True)
    leaves_before = [dst["w"], dst["nest"]["b"], dst["state"][0]]
    for _ in range(3):
        tree = make()
        meta = serde.decode_tree_into(
            serde.encode_tree(tree, meta={"v": 7}), dst)
        assert meta == {"v": 7}
        fresh, _ = serde.decode_tree(serde.encode_tree(tree))
        _assert_same_tree(fresh, dst)
        _assert_leaves_bitexact(fresh, dst)
    # same ndarray objects throughout: filled in place, never replaced
    assert dst["w"] is leaves_before[0]
    assert dst["nest"]["b"] is leaves_before[1]
    assert dst["state"][0] is leaves_before[2]


def test_decode_tree_into_rejects_mismatches():
    buf = serde.encode_tree({"w": np.zeros((2, 2), np.float32)})
    with pytest.raises(serde.SerdeError, match="dict keys"):
        serde.decode_tree_into(buf, {"v": np.zeros((2, 2), np.float32)})
    with pytest.raises(serde.SerdeError, match="leaf mismatch"):
        serde.decode_tree_into(buf, {"w": np.zeros((2, 3), np.float32)})
    with pytest.raises(serde.SerdeError, match="leaf mismatch"):
        serde.decode_tree_into(buf, {"w": np.zeros((2, 2), np.float64)})
    with pytest.raises(serde.SerdeError, match="arity"):
        serde.decode_tree_into(
            serde.encode_tree({"s": (np.zeros(1, np.float32),)}),
            {"s": (np.zeros(1, np.float32), np.zeros(1, np.float32))})


def test_spec_describes_offsets_and_dtypes():
    tree = {"a": np.zeros((2, 2), np.float32),
            "b": np.zeros((3,), ml_dtypes.bfloat16)}
    spec = serde.tree_spec(tree)
    assert spec["t"] == "dict" and spec["keys"] == ["a", "b"]
    a, b = spec["children"]
    assert (a["dtype"], a["off"], a["n"]) == ("float32", 0, 16)
    assert (b["dtype"], b["off"], b["n"]) == ("bfloat16", 16, 6)


def test_errors_bad_magic_truncation_unknown_key_type():
    with pytest.raises(serde.SerdeError):
        serde.decode_tree(b"XXXX\x00\x00\x00\x00")
    with pytest.raises(serde.SerdeError):
        serde.decode_tree(b"\x01")
    with pytest.raises(serde.SerdeError):
        serde.encode_tree({1: np.zeros(2)})   # non-string dict key


def test_grad_codec_round_trip_bit_exact():
    """The gradient-exchange payload: leaves in flatten order plus the
    round/learner/version bookkeeping; views must be bit-exact."""
    rng = np.random.default_rng(0)
    leaves = [_rand(rng, (3, 4), np.float32),
              _rand(rng, (7,), ml_dtypes.bfloat16),
              _rand(rng, (), np.float32)]
    buf = serde.encode_grads(leaves, round_idx=12, learner_id=3)
    out, meta = serde.decode_grads(buf)
    assert meta["round"] == 12 and meta["learner"] == 3
    assert meta["version"] == -1                    # spokes send -1
    assert len(out) == len(leaves)
    for a, b in zip(leaves, out):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    # the hub's broadcast stamps the delegated version
    buf2 = serde.encode_grads(out, round_idx=12, learner_id=0,
                              version=13)
    _out2, meta2 = serde.decode_grads(buf2)
    assert meta2["version"] == 13
    # a non-list payload is a protocol error, not a silent mis-decode
    with pytest.raises(serde.SerdeError, match="list"):
        serde.decode_grads(serde.encode_tree({"w": leaves[0]}))


def test_module_imports_without_jax():
    """Actor children must be able to move buffers without paying a jax
    import; guard the dependency edge, not just the behaviour."""
    import os
    import subprocess
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; import repro.distributed.serde, "
         "repro.distributed.transport, "
         "repro.distributed.socket_transport, "
         "repro.distributed.netserve, "
         "repro.distributed.learner, "
         "repro.distributed.group, repro.obs; sys.exit(1 if 'jax' in "
         "sys.modules else 0)"],
        env=env, timeout=120)
    assert r.returncode == 0, \
        "serde/transport/socket/netserve/learner/group/obs import " \
        "pulled jax in"


# ---------------------------------------------------------------------------
# property tests (skip cleanly when hypothesis is absent)

if HAVE_HYPOTHESIS:
    leaf_dtypes = st.sampled_from(DTYPES)

    @st.composite
    def leaves(draw):
        dtype = draw(leaf_dtypes)
        shape = tuple(draw(st.lists(st.integers(0, 4), min_size=0,
                                    max_size=3)))
        rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
        return _rand(rng, shape, dtype)

    def trees(depth=2):
        base = st.one_of(leaves(), st.none())
        ext = lambda inner: st.one_of(  # noqa: E731
            st.lists(inner, max_size=3),
            st.lists(inner, max_size=3).map(tuple),
            st.dictionaries(st.text(min_size=1, max_size=6), inner,
                            max_size=3))
        return st.recursive(base, ext, max_leaves=8)
else:  # decorators below still need *something* to reference
    def trees():
        return None


@settings(max_examples=60, deadline=None)
@given(tree=trees())
def test_property_roundtrip_bitexact_any_tree(tree):
    out, _ = serde.decode_tree(serde.encode_tree(tree))
    _assert_same_tree(tree, out)
    _assert_leaves_bitexact(tree, out)


@settings(max_examples=30, deadline=None)
@given(tree=trees())
def test_property_double_roundtrip_stable(tree):
    buf1 = serde.encode_tree(tree)
    out1, _ = serde.decode_tree(buf1)
    buf2 = serde.encode_tree(out1)
    assert buf1 == buf2                     # encoding is a fixed point


# ---------------------------------------------------------------------------
# wire codecs: quantized payloads


def test_check_codec_rejects_unknown_loudly():
    assert serde.check_codec("bf16") == "bf16"
    with pytest.raises(serde.CodecMismatchError, match="fp4"):
        serde.check_codec("fp4")
    with pytest.raises(serde.CodecMismatchError):
        serde.encode_tree({"x": np.zeros(2, np.float32)}, codec="fp4")


def test_bf16_codec_restores_logical_dtype_and_rounds():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((16, 5)).astype(np.float32)
    out, _ = serde.decode_tree(serde.encode_tree({"x": x}, codec="bf16"))
    assert out["x"].dtype == np.float32        # logical dtype survives
    want = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    assert out["x"].tobytes() == want.tobytes()


def test_bf16_codec_is_a_fixed_point():
    """bf16-representable values survive the lossy codec bit-exactly:
    the second encode of a decoded payload is byte-identical, which is
    what makes publish -> subscribe -> republish stable."""
    rng = np.random.default_rng(4)
    x = rng.standard_normal((64,)).astype(ml_dtypes.bfloat16) \
           .astype(np.float32)
    buf1 = serde.encode_tree({"x": x}, codec="bf16")
    out1, _ = serde.decode_tree(buf1)
    assert out1["x"].tobytes() == x.tobytes()
    assert serde.encode_tree(out1, codec="bf16") == buf1


def test_lossy_codec_keeps_nonfloat_leaves_bitexact():
    rng = np.random.default_rng(5)
    tree = {"obs": rng.integers(0, 255, (20, 8)).astype(np.uint8),
            "n": rng.integers(0, 9, (7,)).astype(np.int64),
            "f16": rng.standard_normal(6).astype(np.float16)}
    for codec in ("bf16", "int8"):
        out, _ = serde.decode_tree(serde.encode_tree(tree, codec=codec))
        _assert_leaves_bitexact(tree, out)


def test_int8_nonfinite_leaf_falls_back_to_raw():
    x = np.array([np.inf, -1.0, 2.0], np.float32)
    out, _ = serde.decode_tree(serde.encode_tree({"x": x}, codec="int8"))
    assert out["x"].tobytes() == x.tobytes()   # kept verbatim, not NaN soup


def test_traj_item_codec_protects_credit_assignment_leaves():
    """encode_item quantizes observation-sized leaves only: rewards,
    discounts, and behaviour log-probs feed the importance weights and
    must cross the wire bit-exact under EVERY codec."""
    rng = np.random.default_rng(6)
    data = {"obs_image": rng.standard_normal((12, 4, 10, 10, 1))
            .astype(np.float32),
            "rewards": rng.standard_normal((12, 4)).astype(np.float32),
            "discounts": np.ones((12, 4), np.float32),
            "behaviour_logprob": -rng.random((12, 4)).astype(np.float32)}
    item = serde.TrajectoryItem(data, param_version=5, actor_id=1,
                                produced_at=1.0)
    for codec in ("bf16", "int8"):
        out = serde.decode_item(serde.encode_item(item, codec=codec))
        for k in ("rewards", "discounts", "behaviour_logprob"):
            assert out.data[k].tobytes() == data[k].tobytes(), (codec, k)
        assert out.data["obs_image"].dtype == np.float32
        assert not np.array_equal(out.data["obs_image"],
                                  data["obs_image"]) or codec == "bf16"


def test_param_store_bf16_publish_subscribe_roundtrip():
    """The param wire end to end: a store publishing under bf16 hands
    subscribers exactly the bf16-rounded tree, and republishing what a
    subscriber holds is byte-stable (no drift across generations)."""
    from repro.distributed.paramstore import ParameterStore
    rng = np.random.default_rng(7)
    params = {"w": rng.standard_normal((128, 64)).astype(np.float32),
              "b": rng.standard_normal((64,)).astype(np.float32)}
    store = ParameterStore(params, version=3, wire_codec="bf16")
    buf, version = store.pull_serialized()
    assert version == 3
    sub, _ = serde.decode_tree(buf, copy=True)
    want = {k: v.astype(ml_dtypes.bfloat16).astype(np.float32)
            for k, v in params.items()}
    _assert_leaves_bitexact(want, sub)
    store2 = ParameterStore(sub, version=3, wire_codec="bf16")
    buf2, _ = store2.pull_serialized()
    sub2, _ = serde.decode_tree(buf2)
    _assert_leaves_bitexact(sub, sub2)
    assert store.serialized_wire_bytes < store.serialized_raw_bytes / 1.5


def test_grads_codec_shrinks_and_bounds_error():
    rng = np.random.default_rng(8)
    leaves = [rng.standard_normal((64, 32)).astype(np.float32) * 0.01,
              rng.standard_normal((256,)).astype(np.float32)]
    raw = serde.encode_grads(leaves, round_idx=1, learner_id=1)
    q8 = serde.encode_grads(leaves, round_idx=1, learner_id=1,
                            codec="int8")
    assert len(q8) < len(raw) / 3
    out, meta = serde.decode_grads(q8)
    assert meta["round"] == 1
    for a, b in zip(leaves, out):
        bound = np.max(np.abs(a)) / 127.0
        assert np.max(np.abs(a - b)) <= bound + 1e-12


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 2**31 - 1) if HAVE_HYPOTHESIS else None)
def test_property_int8_error_bounded_by_absmax(seed):
    """The int8 contract: per-leaf max abs error <= absmax / 127 (the
    quantization step is absmax/127 and rounding adds at most half a
    step, so the bound is loose by 2x on purpose — it must hold for
    every float leaf, every scale)."""
    rng = np.random.default_rng(seed)
    scale = float(10.0 ** rng.integers(-6, 6))
    tree = {"a": (rng.standard_normal((11, 7)) * scale)
            .astype(np.float32),
            "b": (rng.standard_normal((130,)) * scale)
            .astype(np.float32),
            "z": np.zeros((4,), np.float32)}
    out, _ = serde.decode_tree(serde.encode_tree(tree, codec="int8"))
    for k, a in tree.items():
        absmax = float(np.max(np.abs(a))) if a.size else 0.0
        err = float(np.max(np.abs(a - out[k]))) if a.size else 0.0
        assert err <= absmax / 127.0 + 1e-30, (k, err, absmax)
