"""Distribution correctness: the sharded train_step computes the same
function as the single-device one, across sharding profiles and the
mixed-precision variant. Runs in a subprocess with 8 fake devices."""
import json
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import ImpalaConfig
    from repro.configs.registry import get_smoke_config
    from repro.core import learner as learner_lib
    from repro.launch.mesh import auto_mesh
    from repro.models import backbone as bb, common
    from repro.sharding.rules import Rules, use_rules

    cfg = get_smoke_config("stablelm_1_6b").replace(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, d_ff=128,
        vocab_size=512)
    icfg = ImpalaConfig(num_actions=9, learning_rate=1e-3)
    specs = bb.backbone_specs(cfg, 9)
    params = common.init_params(specs, jax.random.key(0))
    key = jax.random.key(1)
    B, T = 8, 12
    batch = {
        "obs_token": jax.random.randint(key, (B, T + 1), 0, cfg.vocab_size),
        "actions": jax.random.randint(key, (B, T), 0, 9),
        "rewards": jax.random.normal(key, (B, T)),
        "discounts": jnp.full((B, T), 0.99),
        "behaviour_logprob": -jnp.ones((B, T)),
    }

    losses = {}
    # single device reference
    ts, opt = learner_lib.build_train_step(cfg, icfg, 9)
    p1, _, m = jax.jit(ts)(params, opt.init(params), jnp.int32(0), batch)
    losses["single"] = float(m["loss/total"])
    ref_leaf = np.asarray(jax.tree.leaves(p1)[0], np.float32)

    mesh = auto_mesh((2, 4), ("data", "model"))
    for profile in [None, {"embed": ("data", "model"), "heads": None,
                           "kv_heads": None, "ff": None, "vocab": None,
                           "batch": ("data", "model")}]:
        rules = Rules(mesh, profile)
        def step(p, o, s, b):
            with use_rules(rules):
                return ts(p, o, s, b)
        psh = common.param_shardings(specs, rules)
        osh = {"ms": psh}
        bsh = jax.tree.map(
            lambda x: NamedSharding(mesh, rules.spec(
                ("batch",) + (None,) * (x.ndim - 1), x.shape)), batch)
        with mesh:
            f = jax.jit(step, in_shardings=(psh, osh, NamedSharding(mesh, P()), bsh))
            p2, _, m2 = f(params, opt.init(params), jnp.int32(0), batch)
        tag = "baseline_tp" if profile is None else "fsdp"
        losses[tag] = float(m2["loss/total"])
        leaf = np.asarray(jax.tree.leaves(p2)[0], np.float32)
        losses[tag + "_param_err"] = float(np.abs(leaf - ref_leaf).max())
    print(json.dumps(losses))
""")


def test_sharded_train_step_matches_single_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    ref = out["single"]
    assert abs(out["baseline_tp"] - ref) < 1e-2 * max(abs(ref), 1), out
    assert abs(out["fsdp"] - ref) < 1e-2 * max(abs(ref), 1), out
    assert out["baseline_tp_param_err"] < 1e-3, out
    assert out["fsdp_param_err"] < 1e-3, out
