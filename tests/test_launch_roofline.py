"""Launch-layer pure functions: input specs, pair applicability, HLO
collective parsing, analytic flops/bytes model sanity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import ASSIGNED, get_config
from repro.launch import steps as steps_lib
from repro.roofline import analysis
from repro.roofline.flops_model import step_cost


def test_input_specs_shapes():
    arch = get_config("gemma-7b")
    tr = steps_lib.input_specs(arch, INPUT_SHAPES["train_4k"])
    assert tr["obs_token"].shape == (256, 4096)
    assert tr["actions"].shape == (256, 4095)
    de = steps_lib.input_specs(arch, INPUT_SHAPES["decode_32k"])
    assert de["token"].shape == (128, 1)
    leaves = jax.tree.leaves(de["cache"], is_leaf=lambda x: isinstance(
        x, jax.ShapeDtypeStruct))
    assert any(l.shape[-2:] == (16, 256) for l in leaves)  # kv heads x dh


def test_input_specs_stub_frontends():
    """audio/vlm stub carve-out: precomputed embeddings, right shapes."""
    wh = get_config("whisper-small")
    tr = steps_lib.input_specs(wh, INPUT_SHAPES["train_4k"])
    assert tr["enc_embed"].shape == (256, 1500, 768)
    vlm = get_config("llama-3.2-vision-11b")
    tr = steps_lib.input_specs(vlm, INPUT_SHAPES["prefill_32k"])
    assert tr["image_embed"].shape == (32, 1600, 4096)


def test_pair_supported_matrix():
    """long_500k runs only for sub-quadratic context archs."""
    expect_runnable = {"mamba2-1.3b", "recurrentgemma-2b"}
    for name in ASSIGNED:
        arch = get_config(name.replace("_", "-").replace(
            "mamba2-1-3b", "mamba2-1.3b"))
        ok, why = steps_lib.pair_supported(arch, INPUT_SHAPES["long_500k"])
        assert ok == (arch.name in expect_runnable), (arch.name, why)
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            ok, _ = steps_lib.pair_supported(arch, INPUT_SHAPES[s])
            assert ok
    from repro.configs.mistral_nemo_12b import swa_variant
    ok, _ = steps_lib.pair_supported(swa_variant(), INPUT_SHAPES["long_500k"])
    assert ok


def test_decode_cache_len_sliding_window():
    from repro.configs.mistral_nemo_12b import swa_variant
    assert steps_lib.decode_cache_len(swa_variant(), 524288) == 4096
    assert steps_lib.decode_cache_len(get_config("gemma-7b"), 32768) == 32768


# ---------------------------------------------------------------------------
# HLO collective parsing


HLO_SAMPLE = """
  %all-reduce.5 = f32[8,1,768]{2,1,0} all-reduce(%x), channel_id=1
  %ar.done = f32[8]{0} all-reduce-done(%p)
  %ag = bf16[16,1024]{1,0} all-gather(%y), dimensions={0}
  %tuple.ar = (f32[4]{0}, f32[2]{0}) all-reduce(%a, %b), channel_id=3
  %cp = f32[8,1]{1,0} collective-permute(%z), source_target_pairs={{0,1}}
  %rs = bf16[512]{0} reduce-scatter(%w), dimensions={0}
  %a2a = bf16[2,256]{1,0} all-to-all(%v), dimensions={0}
  %not.a.collective = f32[9]{0} add(%c, %d)
"""


def test_collective_bytes_parsing():
    got = analysis.collective_bytes(HLO_SAMPLE)
    assert got["all-reduce"] == 8 * 768 * 4 + 4 * 4 + 2 * 4
    assert got["all-gather"] == 16 * 1024 * 2
    assert got["collective-permute"] == 8 * 4
    assert got["reduce-scatter"] == 512 * 2
    assert got["all-to-all"] == 2 * 256 * 2


def test_analyse_bottleneck():
    r = analysis.analyse({"flops": 1e12, "bytes accessed": 1e9},
                         HLO_SAMPLE,
                         {"peak_flops_bf16": 197e12, "hbm_bw": 819e9,
                          "ici_bw": 50e9}, model_flops=5e11)
    assert r.bottleneck == "compute"
    assert 0 < r.useful_flops_ratio <= 1


# ---------------------------------------------------------------------------
# analytic model sanity


@pytest.mark.parametrize("name", ["gemma-7b", "mistral-nemo-12b"])
def test_flops_model_train_matches_6nd(name):
    """For big dense archs, train flops/device must be within ~2.5x of
    6*N*D/devices (attention + remat overhead on top of 6ND)."""
    from repro.models import backbone as bb
    from repro.models import common
    arch = get_config(name)
    n = common.param_count(bb.backbone_specs(arch, 18))
    sh = INPUT_SHAPES["train_4k"]
    f, _ = step_cost(arch, sh, 256)
    model = 6.0 * n * sh.global_batch * sh.seq_len / 256
    assert 0.8 * model < f < 3.0 * model, (f, model)


def test_flops_model_decode_much_smaller_than_train():
    arch = get_config("gemma-7b")
    ft, _ = step_cost(arch, INPUT_SHAPES["train_4k"], 256)
    fd, _ = step_cost(arch, INPUT_SHAPES["decode_32k"], 256)
    assert fd < ft / 1000


def test_flops_model_replication_penalty():
    """qwen's 20 heads don't divide the 16-way model axis: per-device
    attention flops must exceed gemma-like perfectly-sharded scaling."""
    arch = get_config("qwen1.5-4b")
    f16, _ = step_cost(arch, INPUT_SHAPES["train_4k"], 256, model_axis=16)
    f4, _ = step_cost(arch, INPUT_SHAPES["train_4k"], 256, model_axis=4)
    # with model_axis=4 heads (20) divide evenly -> better sharding can
    # beat 16-way despite fewer shards on mlp
    assert f4 < f16 * 2  # sanity: same order


def test_lower_pair_end_to_end_subprocess():
    """The dry-run machinery itself (input specs -> shardings -> jit lower
    -> compile -> memory/cost analysis) on an 8-device mesh with a smoke
    config — guards deliverable (e) against regressions in-process."""
    import json
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json, jax, jax.numpy as jnp
        from repro.configs.base import InputShape
        from repro.configs.registry import get_smoke_config
        from repro.launch import steps as steps_lib
        from repro.roofline import analysis
        from repro.launch.mesh import auto_mesh
        from repro.sharding.rules import Rules

        mesh = auto_mesh((2, 4), ("data", "model"))
        rules = Rules(mesh)
        out = {}
        for name in ["stablelm_1_6b", "olmoe_1b_7b", "mamba2_1_3b"]:
            arch = get_smoke_config(name).replace(scan_layers=False)
            for shape in [InputShape("t", 64, 8, "train"),
                          InputShape("d", 64, 8, "decode")]:
                lowered, meta = steps_lib.lower_pair(arch, shape, mesh,
                                                     rules)
                compiled = lowered.compile()
                cost = analysis.executable_cost(compiled)
                coll = analysis.collective_bytes(compiled.as_text())
                out[f"{name}/{shape.kind}"] = {
                    "flops": cost.get("flops", 0),
                    "coll": sum(coll.values()),
                    "params": meta["params"],
                }
        print(json.dumps(out))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert len(out) == 6
    for key, rec in out.items():
        assert rec["flops"] > 0, key
        if "train" in key:
            assert rec["coll"] > 0, key  # grad sync must appear
