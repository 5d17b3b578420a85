"""Drive the IMPALA trainer end to end on a TPU and check what it makes.

    python chip_smoke.py               # one chip: phases (a)-(d)
    python chip_smoke.py --four-chips  # four chips: the SPMD learner only

Everything runs in this one process, through the training CLI's own
entry point (``repro.launch.train.main``), so the chip has one owner;
the process-actor phase spawns actor children that act on the host CPU.
Weights are random, made from ``--seed``.

One chip:

  (a) thread actors in unroll mode, the ``impala-deep`` agent at its
      published widths (15 conv layers, LSTM 256) on chase's frames,
      unroll 100, 32 envs per actor: every update lands, the loss is
      finite, the params live on the TPU, policy lag is measured, and
      the V-trace loss ran the fused Pallas kernel compiled by Mosaic;
  (b) on the last 4-trajectory batch (a)'s learner staged, the IMPALA
      loss and its gradients with the fused kernel against the
      ``lax.scan`` reference, on the chip (max relative difference
      1e-4);
  (c) inference-mode actors: the dynamic-batching service on the
      learner's chip;
  (d) process actors over the shm transport: actors on the host CPU,
      learner on the chip.

Four chips (``--four-chips``): a few ``--learner-mode spmd
--spmd-devices 4`` updates (batch shards on 4 distinct devices, the
collective gradient exchange), then one SPMD step against the same
update computed on one device from the same params and batch.

Earlier lines report per-phase host-clock seconds, compile seconds and
updates per second; none of them is a device metric. The last line is
the JSON result ``{"ok": true, "device": {...}}``; any failed phase
exits nonzero without it, and so does a run that finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

T = 100              # unroll (the ImpalaConfig default, paper Table D.3)
NUM_ENVS = 32
MAX_TRAJS = 4
REL_TOL = 1e-4


class Failed(Exception):
    pass


def check(cond, what):
    if not cond:
        raise Failed(what)
    print(f"  ok: {what}", flush=True)


def device_check(chips: int) -> dict:
    interp = os.environ.get("REPRO_PALLAS_INTERPRET", "")
    if interp not in ("", "0"):
        raise Failed(f"REPRO_PALLAS_INTERPRET={interp!r} would interpret "
                     f"the kernels this smoke exists to run compiled")
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        raise Failed(f"no TPU: JAX runs on {dev['platform']}")
    if dev["count"] < chips:
        raise Failed(f"needs {chips} chips, found {dev['count']}")
    return dev


class CompileClock:
    """Seconds JAX spent getting executables (compiling, or loading
    them from the persistent cache), and persistent-cache hits."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.seconds, self.cache_hits


class Run:
    """One ``train.main`` call, watched from inside this process: the
    published params and metrics of every update (``on_update``) and
    the batches the learner stages (a tap on the learner's stacker)."""

    def __init__(self, name, argv, clock):
        self.name, self.argv, self.clock = name, argv, clock
        self.updates = 0
        self.losses = []
        self.params = None
        self.batch = None

    def _on_update(self, step, params, metrics, snapshot_fn):
        self.updates = step
        self.losses.append(float(metrics["loss/total"]))
        self.params = params

    def go(self, tmp):
        import jax
        from repro.distributed import learner as learner_mod
        from repro.launch import train

        tel_path = os.path.join(tmp, f"{self.name}.json")
        real_stack = learner_mod._stack

        def staged(items, stager=None):
            out = real_stack(items, stager)
            rows = jax.tree.leaves(out)[0].shape[0]
            if self.batch is None or rows >= \
                    jax.tree.leaves(self.batch)[0].shape[0]:
                self.batch = out
            return out

        c0, h0 = self.clock.mark()
        t0 = time.monotonic()
        learner_mod._stack = staged
        try:
            rc = train.main(self.argv + ["--telemetry-json", tel_path],
                            on_update=self._on_update)
        finally:
            learner_mod._stack = real_stack
        wall = time.monotonic() - t0
        c1, h1 = self.clock.mark()
        with open(tel_path) as f:
            self.tel = json.load(f)
        print(f"[{self.name}] host-clock wall {wall:.1f} s, compile "
              f"{c1 - c0:.1f} s, persistent-cache hits {h1 - h0}, "
              f"updates/s (host clock, steady window) "
              f"{self.tel['updates_per_sec']:.3f}", flush=True)
        check(rc == 0, f"{self.name}: train.main returned 0")
        return self

    def check_common(self, steps):
        import jax
        import numpy as np

        check(self.tel["learner_updates"] == steps == self.updates,
              f"{self.name}: learner_updates == {steps}")
        check(len(self.losses) == steps and
              all(np.isfinite(x) for x in self.losses),
              f"{self.name}: loss finite on every update "
              f"(last {self.losses[-1]:.4g})")
        plats = {d.platform for leaf in jax.tree.leaves(self.params)
                 for d in leaf.devices()}
        check(plats == {"tpu"}, f"{self.name}: params live on {plats}")
        v = self.tel["vtrace"]
        print(f"  vtrace_impl={v['impl']}, interpret={v['interpret']}",
              flush=True)
        check(v == {"impl": "fused", "interpret": False},
              f"{self.name}: V-trace ran the fused kernel, compiled")


def agent(env):
    from repro.configs.base import ImpalaConfig
    from repro.configs.registry import get_config

    arch = get_config("impala-deep").replace(image_hw=env.image_hw)
    return arch, ImpalaConfig(num_actions=env.num_actions, unroll_length=T)


def max_rel_diff(got, want):
    """Largest over leaves of max|got - want| / max|want|."""
    import jax
    import numpy as np

    worst = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g = np.asarray(g, np.float64)
        w = np.asarray(w, np.float64)
        worst = max(worst, float(np.max(np.abs(g - w))) /
                    max(float(np.max(np.abs(w))), 1e-30))
    return worst


def base_argv(seed, steps):
    return ["--runtime", "async", "--arch", "impala-deep", "--env", "chase",
            "--unroll", str(T), "--num-envs", str(NUM_ENVS),
            "--max-batch-trajs", str(MAX_TRAJS), "--steps", str(steps),
            "--log-every", "5", "--seed", str(seed)]


def kernel_vs_reference(run):
    """(b): the fused loss/V-trace kernel against the scan reference,
    on one batch (a)'s learner staged and (a)'s final params."""
    import jax
    from repro.core import learner as learner_lib
    from repro.core import losses
    from repro.data.envs import make_env

    env = make_env("chase")
    arch, icfg = agent(env)
    batch = run.batch
    rows = jax.tree.leaves(batch)[0].shape[0]
    logits, values, _ = jax.jit(
        lambda p, b: learner_lib.forward_trajectory(
            p, b, arch, env.num_actions))(run.params, batch)
    loss_batch = {k: batch[k] for k in ("actions", "rewards", "discounts",
                                        "behaviour_logprob")}
    loss_batch["bootstrap_value"] = values[:, -1]

    def loss_and_grads(impl):
        def total(lg, v):
            return losses.impala_loss(icfg, lg, v, loss_batch,
                                      impl=impl)[0]
        return jax.jit(jax.value_and_grad(total, argnums=(0, 1)))(
            logits[:, :-1], values[:, :-1])

    fused = loss_and_grads("fused")
    scan = loss_and_grads("scan")
    plats = {d.platform for d in fused[0].devices()}
    check(plats == {"tpu"}, "(b): both losses computed on the TPU")
    rel = max_rel_diff(fused, scan)
    print(f"  (b) B={rows} T={T}: loss fused={float(fused[0]):.6g} "
          f"scan={float(scan[0]):.6g}; max relative difference of loss "
          f"and d/d(logits, values) = {rel:.3e}", flush=True)
    check(rel <= REL_TOL, f"(b): fused vs scan within {REL_TOL}")


def one_chip(args, clock, tmp):
    a = Run("a-unroll", base_argv(args.seed, 20) + [
        "--actor-threads", "4"], clock).go(tmp)
    a.check_common(20)
    check(a.tel["lag"]["measured"] > 0,
          f"(a): policy lag measured ({a.tel['lag']['measured']} "
          f"trajectories, mean {a.tel['lag']['mean']:.2f})")
    kernel_vs_reference(a)

    c = Run("c-inference", base_argv(args.seed, 6) + [
        "--actor-threads", "4", "--actor-mode", "inference"],
        clock).go(tmp)
    c.check_common(6)
    inf = c.tel["inference"]
    check(inf.get("flushes", 0) > 0,
          f"(c): inference service flushed {inf.get('flushes')} batches "
          f"(mean batch {inf.get('mean_batch', 0):.1f} requests)")

    d = Run("d-process", base_argv(args.seed, 4) + [
        "--actor-threads", "4", "--actor-backend", "process",
        "--transport", "shm"], clock).go(tmp)
    d.check_common(4)
    check(d.tel["actors"]["backend"] == "process",
          "(d): trajectories came from actor processes")


def four_chips(args, clock, tmp):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import learner as learner_lib
    from repro.data.envs import make_env
    from repro.launch.mesh import make_data_mesh

    s = Run("spmd", base_argv(args.seed, 6) + [
        "--actor-threads", "4", "--learner-mode", "spmd",
        "--spmd-devices", "4"], clock).go(tmp)
    s.check_common(6)
    group = s.tel["group"]
    check(group["exchange_backend"] == "collective" and
          group["spmd_devices"] == 4,
          f"spmd: collective exchange over {group['spmd_devices']} devices")
    leaf = jax.tree.leaves(s.batch)[0]
    shard_devs = {sh.device for sh in leaf.addressable_shards}
    check(len(shard_devs) == 4 and
          all(sh.data.shape[0] * 4 == leaf.shape[0]
              for sh in leaf.addressable_shards),
          f"spmd: a {leaf.shape[0]}-row batch staged as 4 shards on "
          f"{len(shard_devs)} distinct devices")
    # where actors act: thread actors unroll on the device the published
    # params live on, and the inference service would run there too
    pub = sorted({str(d) for leaf in jax.tree.leaves(s.params)
                  for d in leaf.devices()})
    print(f"  spmd: published params (what actors pull) live on {pub}; "
          f"mesh devices {[str(d) for d in leaf.sharding.device_set]}",
          flush=True)

    # one SPMD step against the same update on one device: the mean of
    # the four shards' gradients, then one optimizer step. Highest
    # matmul precision keeps bf16 passes from masking the comparison
    env = make_env("chase")
    arch, icfg = agent(env)
    mesh = make_data_mesh(4)
    dev0 = jax.devices()[0]
    host_batch = jax.tree.map(jax.device_get, s.batch)
    params = jax.device_get(s.params)
    n = jax.tree.leaves(host_batch)[0].shape[0]
    with jax.default_matmul_precision("highest"):
        spmd_step, opt = learner_lib.build_spmd_train_step(
            arch, icfg, env.num_actions, mesh, vtrace_impl="fused")
        repl = NamedSharding(mesh, P())
        p_spmd, _, _ = jax.jit(spmd_step)(
            jax.device_put(params, repl),
            jax.device_put(opt.init(params), repl),
            jax.device_put(jnp.int32(0), repl),
            jax.device_put(host_batch, NamedSharding(mesh, P("data"))))
        grad_step, apply_step, opt1 = learner_lib.build_grad_apply_steps(
            arch, icfg, env.num_actions, vtrace_impl="fused")
        p1 = jax.device_put(params, dev0)
        grads = [jax.jit(grad_step)(p1, jax.device_put(jax.tree.map(
            lambda x: x[k * n // 4:(k + 1) * n // 4], host_batch),
            dev0))[0] for k in range(4)]
        mean = jax.tree.map(lambda *g: sum(g) / 4.0, *grads)
        p_one, _, _ = jax.jit(apply_step)(p1, opt1.init(p1),
                                          jnp.int32(0), mean)
    delta_spmd = jax.tree.map(lambda a, b: a - b, p_spmd, params)
    delta_one = jax.tree.map(lambda a, b: a - b, p_one, params)
    rel = max_rel_diff(delta_spmd, delta_one)
    print(f"  spmd step vs one-device step on {n} rows: max relative "
          f"difference of the parameter update = {rel:.3e}", flush=True)
    check(rel <= REL_TOL, f"spmd: step matches one device within {REL_TOL}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the SPMD learner on 4 chips and its "
                        "one-device comparison")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    try:
        dev = device_check(4 if args.four_chips else 1)
        from repro.launch.train import enable_compile_cache

        print(f"compile cache: {enable_compile_cache()}", flush=True)
        clock = CompileClock()
        with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
            (four_chips if args.four_chips else one_chip)(args, clock, tmp)
    except Failed as e:
        print(f"FAILED: {e}", flush=True)
        return 1
    print(f"compile total {clock.seconds:.1f} s, persistent-cache hits "
          f"{clock.cache_hits}", flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # every child has been joined by now; skip interpreter teardown,
    # where live XLA runtime threads can abort and flip the exit code
    os._exit(rc)
