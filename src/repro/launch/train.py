"""Training driver: the IMPALA loop (actors -> queue -> V-trace learner)
with checkpointing, replay, policy lag, and optional multi-task suites.

Two runtimes:
  --runtime sync    one loop, acting and learning interleaved; policy lag
                    is *simulated* deterministically (LagController), the
                    right mode for controlled lag/correction experiments.
  --runtime async   real concurrency (repro.distributed): N actors feed a
                    backpressured transport, the learner drains it with
                    dynamic batching, and per-trajectory policy lag is
                    *measured* from parameter-store versions. Actors run
                    as threads (--actor-backend thread, zero-copy
                    in-process queue) or as spawned processes
                    (--actor-backend process --transport shm, serialized
                    trajectory buffers over a cross-process wire — acting
                    stops competing with the learner for the GIL).

CPU-scale entry points (real envs, real learning):
  PYTHONPATH=src python -m repro.launch.train --arch impala-shallow \
      --env catch --steps 500 --num-envs 32
  PYTHONPATH=src python -m repro.launch.train --runtime async \
      --actor-threads 4 --env catch --steps 200 --smoke
  PYTHONPATH=src python -m repro.launch.train --runtime async \
      --actor-backend process --transport shm --env catch \
      --steps 100 --smoke

On a TPU the learner (and thread actors, and the inference service)
run on the chip; spawned actor children run on the host CPU, since a
chip belongs to one process. ``chip_smoke.py`` at the repository root
drives this entry point on a chip.

The production mesh path for the assigned architectures is exercised by
``repro.launch.dryrun`` (compile-only).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

# the checkout's own persistent compile cache (listed in .gitignore); a
# fixed path, because the path is part of what a cache hit matches
CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache for a TPU run; returns
    its directory, or None where it stays off. A
    ``JAX_COMPILATION_CACHE_DIR`` from the environment is JAX's to use as
    it stands, and nothing else is set; otherwise a TPU run caches at
    ``CACHE_DIR``. Other backends compile this repo's programs in
    seconds, and XLA:CPU warns on every cached executable it loads.
    Call before the first compile (it initializes the backend), never
    at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


def main(argv=None, on_update=None) -> int:
    """The training CLI. ``argv`` defaults to ``sys.argv[1:]``;
    ``on_update(step, params, metrics, snapshot_fn)``, when given, also
    sees every async learner update (single learner), so a caller in
    this process can check what the run produced."""
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="impala-shallow")
    p.add_argument("--env", default="catch")
    p.add_argument("--steps", type=int, default=500)
    p.add_argument("--num-envs", type=int, default=32)
    p.add_argument("--unroll", type=int, default=20)
    p.add_argument("--lr", type=float, default=6e-4)
    p.add_argument("--entropy-cost", type=float, default=0.003)
    p.add_argument("--rmsprop-eps", type=float, default=0.01)
    p.add_argument("--policy-lag", type=int, default=1,
                   help="simulated lag (sync runtime only; async measures)")
    p.add_argument("--correction", default="vtrace",
                   choices=["vtrace", "onestep_is", "eps", "none"])
    p.add_argument("--replay-fraction", type=float, default=0.0,
                   help="share of each trained batch drawn from the "
                        "trajectory replay buffer (0 disables replay; "
                        "the paper's replay experiments use 0.5). The "
                        "async learner caps fresh collection at "
                        "(1-fraction) of the batch and tops it up with "
                        "replayed rows, so env-frame consumption per "
                        "update drops by the same share")
    p.add_argument("--replay-capacity", type=int, default=10_000,
                   help="replay buffer size in trajectories (FIFO ring)")
    p.add_argument("--replay-reuse", type=int, default=2,
                   help="K: max TOTAL consumptions per trajectory "
                        "(online pass included); 0 = unlimited. The "
                        "IMPACT-style reuse cap")
    p.add_argument("--replay-priority", default="pertd",
                   choices=["pertd", "uniform"],
                   help="replay sampling: 'pertd' draws proportional to "
                        "the last-seen V-trace advantage magnitude "
                        "(Ape-X prioritization), 'uniform' is the "
                        "paper's uniform mix")
    p.add_argument("--replay-target-period", type=int, default=16,
                   help="updates between target-network syncs: replayed "
                        "rows take the target's values as the V-trace "
                        "baseline (IMPACT), so K reuses chase a fixed "
                        "target")
    p.add_argument("--reward-clip", default="abs_one")
    p.add_argument("--smoke", action="store_true",
                   help="use the reduced smoke config of --arch")
    p.add_argument("--runtime", default="sync", choices=["sync", "async"])
    p.add_argument("--actor-threads", type=int, default=2,
                   help="actor worker count (async runtime; threads or "
                        "processes per --actor-backend). With "
                        "--learners N this is the TOTAL slot count, "
                        "sharded contiguously over the learners")
    p.add_argument("--learners", type=int, default=1,
                   help="learner worker count (async runtime). 1 (the "
                        "default) runs the single-learner loop in this "
                        "process. N>1 spawns N learner processes, each "
                        "owning a disjoint shard of the actor slots and "
                        "its own transport; gradients are mean-reduced "
                        "over a CRC-framed TCP channel every round and "
                        "learner 0 (the designated publisher) numbers "
                        "the param versions. With --listen HOST:PORT, "
                        "learner k binds PORT+k and external actors may "
                        "dial any of them (a full learner refuses with "
                        "the shard map; the actor spills)")
    p.add_argument("--learner-mode", default="process",
                   choices=["process", "spmd"],
                   help="how data-parallel learning scales (async "
                        "runtime): 'process' is the hub/spoke learner "
                        "group (--learners N spawns N processes "
                        "exchanging gradients over TCP); 'spmd' keeps "
                        "ONE learner process and runs the train step as "
                        "a shard_map over --spmd-devices local devices "
                        "— batch sharded on the trajectory axis, params "
                        "replicated, gradients mean-reduced by an "
                        "in-XLA psum (zero TCP frames). Same update "
                        "math as a --learners N group at equal global "
                        "batch")
    p.add_argument("--spmd-devices", type=int, default=0,
                   help="device count for --learner-mode spmd (0 = all "
                        "local devices). On CPU, grow the pool with "
                        "XLA_FLAGS=--xla_force_host_platform_device_"
                        "count=N before launch")
    p.add_argument("--coord-addr", default="",
                   help="multi-host SPMD stub: HOST:PORT of the "
                        "jax.distributed coordinator (process 0). "
                        "Calls jax.distributed.initialize before any "
                        "device use so the ('data',) mesh can span "
                        "hosts; single-host runs leave it empty")
    p.add_argument("--num-hosts", type=int, default=1,
                   help="total participating hosts for --coord-addr")
    p.add_argument("--host-id", type=int, default=0,
                   help="this host's process index for --coord-addr")
    p.add_argument("--grad-stale-s", type=float, default=180.0,
                   help="learner-group stale-grad deadline: the hub "
                        "reduces a round without a learner that missed "
                        "this window (the dropped gradient is counted; "
                        "the laggard still applies the broadcast mean, "
                        "so replicas stay identical)")
    p.add_argument("--actor-backend", default="thread",
                   choices=["thread", "process", "remote"],
                   help="where actors live: threads of this interpreter "
                        "(zero-copy), spawned processes (serialized "
                        "trajectories, no GIL contention), or remote "
                        "machines dialing a TCP listen address "
                        "(--transport socket; without --listen the "
                        "learner spawns loopback children itself)")
    p.add_argument("--actor-mode", default="unroll",
                   choices=["unroll", "inference"],
                   help="unroll: every actor runs its own jitted n-step "
                        "unroll with a private params copy. inference: "
                        "actors are host-side env steppers submitting to "
                        "one dynamic-batching InferenceService on the "
                        "learner's device (paper §3.1; conv-LSTM archs)")
    p.add_argument("--infer-flush-ms", type=float, default=20.0,
                   help="inference service flush deadline: a pending "
                        "request is never delayed past this waiting for "
                        "a fuller batch (actor_mode=inference)")
    p.add_argument("--no-donate", action="store_true",
                   help="disable donate_argnums on the async learner's "
                        "train step (donation updates params/opt_state "
                        "in place; published params become a device "
                        "copy)")
    p.add_argument("--transport", default="",
                   choices=["", "inproc", "shm", "socket"],
                   help="trajectory transport; default inproc for thread "
                        "actors, shm (serialized buffers over a "
                        "cross-process wire) for process actors, socket "
                        "(CRC-framed TCP) for remote actors")
    p.add_argument("--listen", default="",
                   help="HOST:PORT the learner binds for remote actors "
                        "(actor_backend=remote). Given: wait for "
                        "--actor-threads external actors to dial in. "
                        "Empty: loopback ephemeral port, learner spawns "
                        "its own loopback actor children")
    p.add_argument("--connect", default="",
                   help="run as REMOTE ACTOR(S) instead of a learner: "
                        "dial HOST:PORT, receive the whole run config "
                        "in the handshake (env/arch/seed/mode), act "
                        "until the learner says stop. --actor-threads "
                        "sets how many actor processes this machine "
                        "contributes")
    p.add_argument("--wire-codec", default="none",
                   choices=["none", "bf16", "int8"],
                   help="quantize serialized wire payloads: published "
                        "params, trajectory observations (shm/socket "
                        "transports), and grouped gradient frames. "
                        "bf16 halves float bytes losslessly-in-spirit "
                        "(params republish bit-exactly as bf16-rounded "
                        "values); int8 stores per-leaf absmax scales "
                        "(~4x smaller, max error absmax/127). Remote "
                        "actors pick the codec up in the connection "
                        "handshake; a peer that doesn't speak it is "
                        "refused loudly")
    p.add_argument("--vtrace-impl", default="auto",
                   choices=["auto", "fused", "pallas", "scan",
                            "reference"],
                   help="V-trace implementation for the async learner's "
                        "loss: auto = fused Pallas loss kernel on TPU, "
                        "scan elsewhere; fused forces the single-kernel "
                        "softmax+V-trace path (interpret mode off-TPU)")
    p.add_argument("--queue-capacity", type=int, default=8)
    p.add_argument("--queue-policy", default="block",
                   choices=["block", "drop_oldest", "drop_newest"])
    p.add_argument("--max-batch-trajs", type=int, default=4,
                   help="learner dynamic batching: max trajectories "
                        "stacked per update, rounded DOWN to a power of "
                        "two (batch sizes are bucketed so XLA compiles "
                        "at most log2 variants; async runtime)")
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--ckpt-every", type=int, default=200)
    p.add_argument("--resume", action="store_true",
                   help="let a --learners N group resume from the "
                        "latest fleet-v1 checkpoint in --ckpt-dir "
                        "(params + optimizer state + version, "
                        "continuing the monotonic version stream); "
                        "without it a group refuses to run over an "
                        "existing checkpoint. Single-learner runs "
                        "resume from --ckpt-dir automatically.")
    p.add_argument("--supervise", action="store_true",
                   help="self-healing fleet mode (async runtime): "
                        "heartbeat liveness + lease reaping for remote "
                        "actors, supervised respawn of dead actor "
                        "children / threads / spoke learners (restart "
                        "budget + backoff), hub failover (the lowest "
                        "live learner id is promoted; survivors degrade "
                        "to solo past the deadline), and periodic full "
                        "checkpoints (params + opt state) to --ckpt-dir")
    p.add_argument("--heartbeat-timeout-s", type=float, default=10.0,
                   help="remote-actor liveness deadline (--supervise): "
                        "a slot silent this long has its lease reaped; "
                        "clients heartbeat at a third of it")
    p.add_argument("--elastic", action="store_true",
                   help="with --supervise: let late-dialing remote "
                        "actors grow the slot range past "
                        "--actor-threads instead of being refused")
    p.add_argument("--failover-deadline-s", type=float, default=20.0,
                   help="learner-group hub failover budget: a survivor "
                        "that cannot rejoin a new hub within this many "
                        "seconds degrades to solo training (loud "
                        "degraded_solo telemetry flag)")
    p.add_argument("--log-every", type=int, default=25)
    p.add_argument("--seed", type=int, default=0)
    obs = p.add_argument_group("observability (async runtime)")
    obs.add_argument("--metrics-port", type=int, default=None,
                     help="serve /metrics (Prometheus), /healthz and "
                          "/telemetry (JSON) from a background HTTP "
                          "server on the learner (0 = ephemeral port; "
                          "with --learners N the parent aggregates the "
                          "whole group behind this one port)")
    obs.add_argument("--metrics-host", default="127.0.0.1",
                     help="bind address for --metrics-port")
    obs.add_argument("--telemetry-json", default="",
                     help="write the complete final telemetry snapshot "
                          "(merged across learners for --learners N) to "
                          "this path as JSON")
    obs.add_argument("--trace", default="", dest="trace_path",
                     help="record sampled per-trajectory lifecycle spans "
                          "(env unroll -> encode -> transport -> queue "
                          "wait -> collect -> step -> publish) and write "
                          "Chrome trace-event JSON here (load in "
                          "Perfetto). Single-learner async runs, "
                          "actor_mode=unroll")
    obs.add_argument("--trace-every", type=int, default=64,
                     help="sample every Nth trajectory per actor for "
                          "--trace")
    obs.add_argument("--profile-steps", default="",
                     help="A:B — wrap learner updates [A, B) in "
                          "jax.profiler.start_trace/stop_trace")
    obs.add_argument("--profile-dir", default="/tmp/repro-profile",
                     help="output directory for --profile-steps traces")
    obs.add_argument("--telemetry-sink", default="",
                     help="append periodic JSONL telemetry snapshots to "
                          "this path while training")
    obs.add_argument("--sink-interval-s", type=float, default=5.0,
                     help="seconds between --telemetry-sink lines")
    args = p.parse_args(argv)

    if args.connect:
        # remote actor mode: this process contributes actors to a
        # learner elsewhere — every run parameter arrives in the
        # connection handshake, so none of the learner flags apply here
        return _run_remote_actors(args)

    if args.coord_addr:
        # multi-host SPMD stub: initialize the jax.distributed runtime
        # BEFORE anything touches the backend, so jax.devices() spans
        # every host and the ('data',) mesh (and its psum) is global.
        # Single-host SPMD never comes through here.
        if args.num_hosts < 1 or not (0 <= args.host_id < args.num_hosts):
            raise SystemExit(f"--coord-addr needs --num-hosts >= 1 and "
                             f"0 <= --host-id < num_hosts, got "
                             f"{args.num_hosts}/{args.host_id}")
        jax.distributed.initialize(coordinator_address=args.coord_addr,
                                   num_processes=args.num_hosts,
                                   process_id=args.host_id)
        print(f"jax.distributed up: host {args.host_id}/{args.num_hosts} "
              f"coordinator={args.coord_addr} "
              f"devices={jax.device_count()} "
              f"(local {jax.local_device_count()})")
    enable_compile_cache()

    if args.learner_mode == "spmd":
        if args.runtime != "async":
            raise SystemExit("--learner-mode spmd requires "
                             "--runtime async")
        if args.learners > 1:
            raise SystemExit("--learner-mode spmd keeps ONE learner "
                             "process; drop --learners (device "
                             "parallelism comes from --spmd-devices)")

    from repro.configs.base import ImpalaConfig
    from repro.configs.registry import get_config, get_smoke_config
    from repro.data.envs import make_env

    env = make_env(args.env)
    arch = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if arch.family == "impala_cnn":
        arch = arch.replace(image_hw=env.image_hw)
    elif arch.vocab_size < env.vocab_size:
        arch = arch.replace(vocab_size=env.vocab_size)
    icfg = ImpalaConfig(
        num_actions=env.num_actions, unroll_length=args.unroll,
        learning_rate=args.lr, entropy_cost=args.entropy_cost,
        rmsprop_eps=args.rmsprop_eps, policy_lag=args.policy_lag,
        correction=args.correction, replay_fraction=args.replay_fraction,
        replay_capacity=args.replay_capacity,
        replay_reuse=args.replay_reuse,
        replay_priority=args.replay_priority,
        replay_target_period=args.replay_target_period,
        reward_clip=args.reward_clip, seed=args.seed)

    if args.runtime == "async":
        return _run_async(args, env, arch, icfg, on_update)
    return _run_sync(args, env, arch, icfg)


def _build_obs(args):
    """ObsConfig from the CLI flags, or None when no obs flag is set
    (the runtime then skips all instrumentation glue)."""
    wants = (args.metrics_port is not None or args.trace_path
             or args.profile_steps or args.telemetry_sink)
    if not wants:
        return None
    from repro.obs import ObsConfig
    return ObsConfig(
        metrics_port=args.metrics_port,
        metrics_host=args.metrics_host,
        trace_path=args.trace_path or None,
        trace_every=max(1, args.trace_every),
        profile_steps=args.profile_steps or None,
        profile_dir=args.profile_dir,
        sink_path=args.telemetry_sink or None,
        sink_interval_s=args.sink_interval_s)


def _dump_telemetry(path: str, tel) -> None:
    with open(path, "w") as f:
        json.dump(tel, f, default=float, indent=2)
        f.write("\n")
    print(f"telemetry snapshot written to {path}")


def _parse_hostport(spec: str, default_host: str = "127.0.0.1"):
    host, sep, port = spec.rpartition(":")
    if not sep or not port.isdigit():
        raise SystemExit(f"expected HOST:PORT, got {spec!r}")
    return (host or default_host, int(port))


def _run_remote_actors(args) -> int:
    import multiprocessing as mp

    addr = _parse_hostport(args.connect)
    n = max(1, args.actor_threads)
    print(f"remote actor mode: {n} actor process(es) -> "
          f"{addr[0]}:{addr[1]}")
    if n == 1:
        from repro.distributed.netserve import remote_actor_main
        err = remote_actor_main(addr)
        if err:
            print(err)
            return 1
        print("learner said stop; exiting cleanly")
        # hard exit: XLA runtime threads can abort C++ teardown on a
        # normal interpreter exit, flipping a clean run's exit code
        os._exit(0)
    ctx = mp.get_context("spawn")
    from repro.distributed.netserve import remote_actor_child
    from repro.distributed.supervise import KillSafeEvent
    stop = KillSafeEvent(ctx)
    procs = [ctx.Process(target=remote_actor_child, args=(addr, stop),
                         name=f"remote-actor-{i}") for i in range(n)]
    for proc in procs:
        proc.start()
    try:
        for proc in procs:
            proc.join()
    except KeyboardInterrupt:
        stop.set()
        for proc in procs:
            proc.join(timeout=10)
            if proc.is_alive():
                proc.terminate()
        return 0
    # a failed actor (dial timeout, refusal, crash) exits nonzero;
    # surface it like the single-actor path does
    return 1 if any(p.exitcode not in (0, None) for p in procs) else 0


def _run_sync(args, env, arch, icfg) -> int:
    from repro.core import actor as actor_lib
    from repro.core import learner as learner_lib
    from repro.core.metrics import EpisodeTracker
    from repro.core.queue import LagController
    from repro.core.replay import ReplayBuffer, mix_batches
    from repro.checkpoint import checkpoint as ckpt
    from repro.models import backbone as bb
    from repro.models import common

    specs = bb.backbone_specs(arch, env.num_actions)
    params = common.init_params(specs, jax.random.key(args.seed))
    print(f"arch={arch.name} params={common.param_count(specs):,} "
          f"env={env.name} actions={env.num_actions} runtime=sync")

    init_fn, unroll = actor_lib.build_actor(env, arch, icfg, args.num_envs)
    train_step, opt = learner_lib.build_train_step(arch, icfg,
                                                   env.num_actions)
    train_step = jax.jit(train_step)
    opt_state = opt.init(params)
    start_step = 0
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        params, start_step = ckpt.restore(args.ckpt_dir, params)
        print(f"restored checkpoint at step {start_step}")

    carry = init_fn(jax.random.key(args.seed + 1))
    lag = LagController(icfg.policy_lag, params)
    buf = ReplayBuffer(icfg.replay_capacity, seed=args.seed,
                       reuse_limit=icfg.replay_reuse,
                       priority=icfg.replay_priority)
    tracker = EpisodeTracker(args.num_envs)
    frames = 0
    # steady-state fps window opens after the first jitted update lands —
    # otherwise early prints are dominated by XLA compile time (matching
    # the async runtime's convention)
    t0 = None
    frames0 = 0
    for step in range(start_step, args.steps):
        # acting and learning interleave directly — no queue theatre: the
        # trajectory IS the batch (the real queue lives in the async path)
        carry, batch = unroll(lag.actor_params(), carry)
        tracker.update(np.asarray(batch["rewards"]),
                       np.asarray(batch["done"]))
        if icfg.replay_fraction > 0:
            buf.add_batch(batch)
            rep = buf.sample(args.num_envs)
            batch = mix_batches(batch, rep, icfg.replay_fraction,
                                buffer=buf)
        params, opt_state, metrics = train_step(params, opt_state,
                                                jnp.int32(step), batch)
        lag.on_update(params)
        frames += args.num_envs * args.unroll
        if t0 is None:
            jax.block_until_ready(params)
            t0 = time.time()
            frames0 = frames
        if (step + 1) % args.log_every == 0:
            dt = time.time() - t0
            fps = (frames - frames0) / dt if dt > 0 else 0.0
            print(f"step {step+1:6d} return(100)={tracker.mean_return():7.3f} "
                  f"loss={float(metrics['loss/total']):10.2f} "
                  f"entropy={-float(metrics['loss/entropy']):8.1f} "
                  f"fps={fps:7.0f} episodes={len(tracker.completed)}")
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, step + 1, params)
    if args.ckpt_dir:
        ckpt.save(args.ckpt_dir, args.steps, params)
    print(f"final return(100) = {tracker.mean_return():.3f}")
    return 0


def _run_async(args, env, arch, icfg, caller_on_update=None) -> int:
    from repro.checkpoint import checkpoint as ckpt
    from repro.distributed import run_async_training
    from repro.models import backbone as bb
    from repro.models import common

    transport = args.transport or {
        "process": "shm", "remote": "socket"}.get(args.actor_backend,
                                                  "inproc")
    if args.actor_backend == "process" and transport != "shm":
        raise SystemExit("--actor-backend process requires --transport shm")
    if args.actor_backend == "remote" and transport != "socket":
        raise SystemExit("--actor-backend remote requires "
                         "--transport socket")
    if args.learners > 1:
        return _run_group(args, env, arch, icfg, transport)
    listen_addr = (_parse_hostport(args.listen, default_host="0.0.0.0")
                   if args.listen else None)
    # an explicit --listen means real remote machines dial in; without
    # it the learner spawns loopback actor children itself
    spawn_remote = not args.listen
    spmd_devices = 0
    if args.learner_mode == "spmd":
        spmd_devices = args.spmd_devices or jax.device_count()
    specs = bb.backbone_specs(arch, env.num_actions)
    print(f"arch={arch.name} params={common.param_count(specs):,} "
          f"env={env.name} actions={env.num_actions} runtime=async "
          f"actors={args.actor_threads}({args.actor_backend}/"
          f"{args.actor_mode}) transport={transport} "
          f"queue={args.queue_capacity}/{args.queue_policy} "
          f"max_batch_trajs={args.max_batch_trajs} "
          f"donate={not args.no_donate}"
          + (f" learner_mode=spmd spmd_devices={spmd_devices}"
             if spmd_devices else ""))
    initial_params, initial_opt, start_step = None, None, 0
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        tree, ck_step, extra = ckpt.load_with_extra(args.ckpt_dir)
        if (extra or {}).get("format") == "fleet-v1":
            # full resume: params + optimizer state + version — the
            # run continues the exact monotonic version stream
            initial_params, initial_opt = tree["params"], tree["opt"]
            start_step = int(extra.get("version", ck_step))
            print(f"restored fleet checkpoint at version {start_step} "
                  f"(params + optimizer state)")
        else:
            like = common.init_params(specs, jax.random.key(args.seed))
            initial_params, start_step = ckpt.restore(args.ckpt_dir,
                                                      like)
            print(f"restored checkpoint at step {start_step}")

    last_params = [None]

    def on_update(step, params, metrics, snapshot_fn):
        last_params[0] = params
        if step % args.log_every == 0:
            tel = snapshot_fn()
            lag = tel["lag"]
            q = tel["queue"]
            extra = ""
            if "inference" in tel:
                inf = tel["inference"]
                extra = (f" infer(batch/wait_p95)="
                         f"{inf['mean_batch']:.1f}/"
                         f"{inf['queue_wait_ms_p95']:.1f}ms")
            print(f"update {step:6d} "
                  f"loss={float(metrics['loss/total']):10.2f} "
                  f"lag(mean/max)={lag['mean']:.2f}/{lag['max']} "
                  f"queue(occ/drop/stall)={q['mean_occupancy']:.1f}/"
                  f"{q['dropped']}/{q['put_stalls']} "
                  f"learner_fps={tel['frames_per_sec']:7.0f} "
                  f"actor_fps={tel['actors']['actor_fps']:7.0f}" + extra)
        if args.ckpt_dir and step % args.ckpt_every == 0 and \
                not args.supervise:
            # legacy params-only saves; --supervise switches to the
            # runtime's combined fleet-v1 checkpoints instead
            ckpt.save(args.ckpt_dir, step, params)
        if caller_on_update is not None:
            caller_on_update(step, params, metrics, snapshot_fn)

    env_arg = (args.env if args.actor_backend in ("process", "remote")
               else env)
    tracker, metrics, tel = run_async_training(
        env_arg, icfg, args.num_envs, args.steps,
        num_actors=args.actor_threads,
        actor_backend=args.actor_backend,
        actor_mode=args.actor_mode,
        transport=transport,
        listen_addr=listen_addr,
        spawn_remote=spawn_remote,
        queue_capacity=args.queue_capacity,
        queue_policy=args.queue_policy,
        max_batch_trajs=args.max_batch_trajs,
        donate=not args.no_donate,
        infer_flush_timeout_s=args.infer_flush_ms / 1e3,
        wire_codec=args.wire_codec, vtrace_impl=args.vtrace_impl,
        spmd_devices=spmd_devices,
        seed=args.seed, arch=arch, initial_params=initial_params,
        initial_opt_state=initial_opt,
        start_step=start_step, on_update=on_update,
        supervise=args.supervise,
        heartbeat_timeout_s=args.heartbeat_timeout_s,
        elastic=args.elastic,
        ckpt_dir=(args.ckpt_dir if args.supervise else None),
        ckpt_every=args.ckpt_every,
        obs=_build_obs(args))
    if args.ckpt_dir and last_params[0] is not None and \
            not args.supervise:
        ckpt.save(args.ckpt_dir, args.steps, last_params[0])
    print(f"final return(100) = {tracker.mean_return():.3f}")
    keys = ["learner_updates", "frames_consumed", "updates_per_sec",
            "frames_per_sec", "batch_size_hist", "lag", "queue",
            "actors", "param_version", "vtrace"]
    if "inference" in tel:
        keys.append("inference")
    if "replay" in tel:
        keys.append("replay")
    if "group" in tel:
        # spmd runs surface the group section (collective backend)
        keys += ["group", "exchange"]
    print("telemetry:", json.dumps({k: tel[k] for k in keys},
                                   default=float))
    if args.telemetry_json:
        _dump_telemetry(args.telemetry_json, tel)
    return 0


def _run_group(args, env, arch, icfg, transport) -> int:
    """N>1 learner processes: sharded actors, gradient exchange over
    the framed channel, one designated publisher. With --supervise the
    group writes fleet-v1 checkpoints (params + optimizer state +
    version) every ``--ckpt-every`` updates and resumes from the latest
    one, continuing the same monotonic version stream. ``transport``
    arrives resolved/validated from _run_async."""
    from repro.checkpoint import checkpoint as ckpt
    from repro.distributed import run_group_training
    from repro.models import backbone as bb
    from repro.models import common

    resume_from = None
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        step0 = ckpt.latest_step(args.ckpt_dir)
        man = ckpt.read_manifest(args.ckpt_dir)
        fleet = man.get("extra", {}).get("format") == "fleet-v1"
        if not args.resume:
            # refusing beats silently restarting from scratch AND
            # overwriting the existing checkpoint at the end
            hint = ("pass --resume to continue it"
                    if fleet else "move it aside or pick a fresh "
                                  "--ckpt-dir")
            raise SystemExit(
                f"{args.ckpt_dir!r} already holds a checkpoint "
                f"(step {step0}); {hint}.")
        if fleet:
            resume_from = args.ckpt_dir
            print(f"resuming learner group from fleet checkpoint "
                  f"(step {step0})")
        else:
            # a params-only checkpoint has no optimizer state to hand
            # the workers — refusing beats silently restarting from
            # scratch AND overwriting the existing checkpoint
            raise SystemExit(
                f"{args.ckpt_dir!r} holds a params-only checkpoint "
                f"(step {step0}); a learner group resumes only from "
                f"fleet-v1 checkpoints (params + optimizer state — "
                f"written by --supervise runs). Move it aside or pick "
                f"a fresh --ckpt-dir.")
    listen_addr = (_parse_hostport(args.listen, default_host="0.0.0.0")
                   if args.listen else None)
    spawn_remote = not args.listen
    specs = bb.backbone_specs(arch, env.num_actions)
    print(f"arch={arch.name} params={common.param_count(specs):,} "
          f"env={env.name} actions={env.num_actions} runtime=async "
          f"learners={args.learners} "
          f"actors={args.actor_threads}({args.actor_backend}/"
          f"{args.actor_mode}) transport={transport} "
          f"queue={args.queue_capacity}/{args.queue_policy} "
          f"max_batch_trajs={args.max_batch_trajs} "
          f"donate={not args.no_donate}")
    def on_progress(learner_id, snap):
        lag = snap["lag"]
        q = snap["queue"]
        ex = snap.get("exchange", {})
        print(f"learner {learner_id} update {snap['learner_updates']:6d} "
              f"lag(mean/max)={lag['mean']:.2f}/{lag['max']} "
              f"queue(occ/stall)={q.get('mean_occupancy', 0.0):.1f}/"
              f"{q.get('put_stalls', 0)} "
              f"fps={snap['frames_per_sec']:7.0f} "
              f"reduce_ms={ex.get('reduce_wait_ms_mean', 0.0):.1f} "
              f"stale={ex.get('stale_dropped', 0)}", flush=True)

    tracker, metrics, tel, params = run_group_training(
        args.env, icfg, args.num_envs, args.steps,
        num_learners=args.learners,
        num_actors=args.actor_threads,
        actor_backend=args.actor_backend,
        actor_mode=args.actor_mode,
        transport=transport,
        listen_addr=listen_addr,
        spawn_remote=spawn_remote,
        queue_capacity=args.queue_capacity,
        queue_policy=args.queue_policy,
        max_batch_trajs=args.max_batch_trajs,
        donate=not args.no_donate,
        stale_after_s=args.grad_stale_s,
        infer_flush_timeout_s=args.infer_flush_ms / 1e3,
        wire_codec=args.wire_codec, vtrace_impl=args.vtrace_impl,
        seed=args.seed, arch=arch,
        telemetry_every=args.log_every, on_progress=on_progress,
        ckpt_every=args.ckpt_every if args.ckpt_dir else 0,
        # supervised groups save fleet-v1 (params + opt state) through
        # ckpt_dir; legacy params-only saves would mix formats
        on_checkpoint=(lambda step, p: ckpt.save(args.ckpt_dir, step, p))
        if args.ckpt_dir and not args.supervise else None,
        supervise=args.supervise,
        failover_deadline_s=args.failover_deadline_s,
        resume_from=resume_from,
        ckpt_dir=args.ckpt_dir if args.supervise else None,
        return_final_params=True, obs=_build_obs(args))
    if args.ckpt_dir and not args.supervise:
        ckpt.save(args.ckpt_dir, args.steps, params)
    print(f"final return(100) = {tracker.mean_return():.3f}")
    keys = ["group", "learner_updates", "frames_consumed",
            "updates_per_sec", "frames_per_sec", "lag", "actors",
            "param_version"]
    if "replay" in tel:
        keys.append("replay")
    print("telemetry:", json.dumps({k: tel[k] for k in keys},
                                   default=float))
    per = tel["actors"]["per_learner_trajectories"]
    print("per-learner trajectories:", json.dumps(per))
    if args.telemetry_json:
        _dump_telemetry(args.telemetry_json, tel)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
