"""Sharding rules: divisibility fallback, axis reuse, profile overrides,
spec trees, and (in a subprocess) multi-device MoE/step equivalence."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.models import common
from repro.models.common import Spec
from repro.sharding.rules import Rules


@pytest.fixture(scope="module")
def mesh():
    # 1x1 mesh on the single CPU device: resolution logic is identical
    from repro.launch.mesh import auto_mesh
    return auto_mesh((1, 1), ("data", "model"))


def _spec_with_sizes(mesh_shape=(1, 1)):
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": mesh_shape[0], "model": mesh_shape[1]}
    return FakeMesh()


def test_divisibility_fallback():
    rules = Rules(_spec_with_sizes((16, 16)))
    # 10 heads on a 16-way model axis -> replicated
    s = rules.spec(("batch", None, "heads", None), (256, 4096, 10, 256))
    assert s == P(("data",), None, None, None) or s == P("data", None, None, None)
    # divisible -> sharded
    s2 = rules.spec(("batch", None, "heads", None), (256, 4096, 16, 256))
    assert s2[2] == "model"


def test_axis_used_once():
    rules = Rules(_spec_with_sizes((16, 16)))
    # experts and ff both map to model; only the first gets it
    s = rules.spec(("experts", "embed", "ff"), (32, 1024, 512))
    assert s[0] == "model" and s[2] is None


def test_missing_mesh_axis_dropped():
    rules = Rules(_spec_with_sizes((16, 16)))  # no 'pod' axis
    s = rules.spec(("batch", None), (256, 64))
    assert s[0] in ("data", ("data",))


def test_profile_overrides():
    from repro.sharding.profiles import get_profile
    from repro.configs.base import INPUT_SHAPES
    from repro.configs.registry import get_config
    arch = get_config("mamba2-1.3b")
    shape = INPUT_SHAPES["long_500k"]
    assert get_profile("baseline", arch, shape) is None
    prof = get_profile("seq_data", arch, shape)
    rules = Rules(_spec_with_sizes((16, 16)), prof)
    s = rules.spec(("batch", "seq", "embed"), (1, 524288, 2048))
    assert s[0] is None and s[1] is not None


def test_param_spec_trees(mesh):
    specs = {"w": Spec((8, 4), ("embed", "ff")),
             "nested": {"b": Spec((4,), ("ff",), init="zeros")}}
    params = common.init_params(specs, jax.random.key(0))
    assert params["w"].shape == (8, 4)
    assert float(jnp.abs(params["nested"]["b"]).sum()) == 0.0
    abstract = common.abstract_params(specs)
    assert abstract["w"].shape == (8, 4)
    shardings = common.param_shardings(specs, Rules(mesh))
    assert shardings["w"].spec is not None
    assert common.param_count(specs) == 36


SUBPROCESS_EQUIV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json, jax, jax.numpy as jnp, numpy as np
    from repro.configs.registry import get_smoke_config
    from repro.models import moe as moe_lib, common
    from repro.launch.mesh import auto_mesh
    from repro.sharding.rules import Rules, use_rules

    cfg = get_smoke_config("olmoe_1b_7b")
    mesh = auto_mesh((2, 4), ("data", "model"))
    rules = Rules(mesh)
    specs = moe_lib.moe_specs(cfg)
    params = common.init_params(specs, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model),
                          jnp.float32).astype(jnp.bfloat16)
    y_dense, _ = jax.jit(lambda p, x: moe_lib.apply_moe(p, x, cfg))(params, x)
    cfg2 = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                               dispatch_impl="shard_map_a2a"))
    def f(p, x):
        with use_rules(rules):
            return moe_lib.apply_moe(p, x, cfg2)
    with mesh:
        y_sm, _ = jax.jit(f)(params, x)
    err = float(jnp.abs(y_dense.astype(jnp.float32) -
                        y_sm.astype(jnp.float32)).max())
    print(json.dumps({"err": err}))
""")


def test_shard_map_moe_equivalence_subprocess():
    """Expert-parallel shard_map MoE == single-device dense MoE (8 devices)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SUBPROCESS_EQUIV],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    err = json.loads(r.stdout.strip().splitlines()[-1])["err"]
    assert err < 1e-3, err


def test_tp2d_profile_resolution():
    from repro.sharding.profiles import get_profile
    from repro.configs.base import INPUT_SHAPES
    from repro.configs.registry import get_config

    class Mesh2D:
        axis_names = ("data", "model_a", "model_b")
        shape = {"data": 16, "model_a": 4, "model_b": 4}

    prof = get_profile("tp2d", get_config("qwen1.5-4b"),
                       INPUT_SHAPES["train_4k"])
    rules = Rules(Mesh2D(), prof)
    # qwen's 20 heads shard on model_a (20 % 4 == 0)
    s = rules.spec(("embed", "heads", "head_dim"), (2560, 20, 128))
    assert s[1] == "model_a"
    # ff uses the full 16-way product
    s2 = rules.spec(("embed", "ff"), (2560, 6912))
    assert s2[1] == ("model_a", "model_b")


def test_fsdp_pure_profile_resolution():
    from repro.sharding.profiles import get_profile
    from repro.configs.base import INPUT_SHAPES
    from repro.configs.registry import get_config

    prof = get_profile("fsdp_pure", get_config("mistral-nemo-12b"),
                       INPUT_SHAPES["train_4k"])
    rules = Rules(_spec_with_sizes((16, 16)), prof)
    # batch shards over every axis; weights shard on embed dim
    s = rules.spec(("batch", None, None), (256, 4096, 5120))
    assert set(s[0]) == {"data", "model"}
    w = rules.spec(("embed", "heads", "head_dim"), (5120, 32, 128))
    assert w[0] == ("data", "model") and w[1] is None


SUBPROCESS_DATA_MESH = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, jax
    from jax.sharding import PartitionSpec as P
    from repro.configs.registry import get_smoke_config
    from repro.launch.mesh import make_data_mesh
    from repro.models import backbone as bb, common
    from repro.sharding.rules import Rules

    mesh = make_data_mesh(8)
    rules = Rules(mesh)
    arch = get_smoke_config("impala-shallow")
    specs = bb.backbone_specs(arch, 3)
    shardings = common.param_shardings(specs, rules)
    # the conv-LSTM tree is full of dims an 8-way data mesh cannot
    # split (3x3 conv kernels, odd channel counts): every one must
    # resolve through the divisibility fallback to a replicated spec
    # instead of crashing — and the placement must actually build
    leaves = jax.tree.leaves(shardings,
                             is_leaf=lambda x: hasattr(x, "spec"))
    assert leaves, "no shardings resolved"
    params = common.init_params(specs, jax.random.key(0))
    placed = jax.tree.map(jax.device_put, params, shardings)
    jax.block_until_ready(placed)
    replicated = sum(1 for s in leaves
                     if all(ax is None for ax in tuple(s.spec)))
    # batch rule: trajectory rows shard when divisible, replicate when
    # not (the SPMD learner's bucket fallback rides exactly this)
    b32 = rules.spec(("batch",), (32,))
    b20 = rules.spec(("batch",), (20,))
    assert b32[0] in ("data", ("data",)), b32
    assert b20 == P(None) or b20[0] is None, b20
    print(json.dumps({"params": len(leaves), "replicated": replicated}))
""")


def test_data_mesh_divisibility_fallback_subprocess():
    """IMPALA's conv-LSTM param tree on an 8-device ('data',) mesh:
    indivisible leading dims replicate (Rules fallback) rather than
    crash, and the batch rule shards 32 rows / replicates 20."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SUBPROCESS_DATA_MESH],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["params"] > 0
    # nothing in this net shards on a data-only mesh: full replication
    assert out["replicated"] == out["params"], out
