"""The on-chip benchmark of the asynchronous IMPALA trainer.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run, and it is the only one that touches the chips. It
checks the device (a TPU, at least the cell's chips, a known
``device_kind``), builds the cell from its files, drives the program's own
async runtime (``repro.distributed.runtime._setup`` and ``Learner.run``)
the way ``run_async_training`` does, and measures a window of
``--seconds`` once every actor has produced and a few updates have
landed. Both ends of the window wait for the device. The last line of
standard output is one JSON object; the comparison that decides
``correct`` closes standard error.

What the end-to-end metrics measure:

  frames_per_s        environment frames the learner trained on in the
                      window, over the window's seconds;
  publish_gap_ms_p95  the 95th percentile of the time between consecutive
                      parameter publishes in the window;
  setup_s             process start to window start: imports, weights,
                      compiles (or cache loads), warm-up.

With ``--trace 1`` the window (at most ``TRACE_WINDOW_S``) runs under
``jax.profiler`` and the line carries the per-layer metrics instead.

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name in ``BENCHMARK.json``:

  chipbench/configs/<config>.json   the agent: the program's registry name
                                    (``arch``), frame, widths, unroll, the
                                    learning constants, env, ``reduced``,
                                    ``assumed``
  chipbench/flops/<config>.py       ``forward_flops(cfg)``,
                                    ``train_flops(cfg)`` per observation
  chipbench/envs/<env>.py           ``make(**env_args)`` -> ``repro`` Env
  chipbench/traffic/<traffic>.json  actors, envs per actor, actor mode and
                                    backend, transport, learner batch,
                                    queue, SPMD devices
  chipbench/limits/<cell>.json      the limit of each number ``correct``
                                    compares (``chipbench/compare.py``)
  chipbench/metrics/<metric>.py     ``compute(ctx)`` -> a number, or None
                                    where the run has nothing to read

To add a cell, add files and entries; edit none that are there. For
example, the shallow agent with thread actors in unroll mode on one
chip, whose traffic ``unroll-4x32`` exists: write
``chipbench/limits/shallow-unroll.json`` (limits set from
``chipbench/calibrate.py`` readings as PERF.md describes), and add to
``BENCHMARK.json`` the workload ``{"name": "shallow-unroll", "config":
"impala-shallow-72x96", "traffic": "unroll-4x32", "chips": 1, "why":
...}`` plus ``"shallow-unroll"`` in the ``workloads`` of each metric it
reports (``actors.unroll_ms`` among them). A new traffic mix is a new
``chipbench/traffic/<name>.json``; a new metric is
``chipbench/metrics/<name>.py`` and its entry in ``per_layer``; a new
configuration is its ``configs`` and ``flops`` files (and an ``envs``
file if it needs one) and its entry in ``configs``.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import compare  # noqa: E402

# updates before the window: the first STEPS are the ones ``correct``
# checks, the rest let the pipeline fill
WARM_UPDATES = compare.STEPS + 2
TRACE_WINDOW_S = 8.0
SETUP_LIMIT_S = 900.0
# the program's own seed (action sampling, env resets): fixed, so every
# run compiles the same programs and finds them in the cache; the
# weights, and so the trajectories, come from ``--seed``
PROGRAM_SEED = 12
TRACE_DIR = os.path.join(ROOT, "chiprun_out", "chipbench-trace")


class Failed(Exception):
    pass


def load_json(*parts) -> Dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(*parts):
    path = os.path.join(HERE, *parts)
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + os.path.splitext(parts[-1])[0].replace("-", "_")
        .replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, bench: Optional[Dict] = None) -> Dict:
    """The cell's entry and every file it names, found by name."""
    if bench is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Failed(f"no workload {name!r} in BENCHMARK.json "
                     f"(have {sorted(cells)})")
    cell = cells[name]
    config = load_json("configs", cell["config"] + ".json")
    return {
        "cell": cell,
        "config": config,
        "traffic": load_json("traffic", cell["traffic"] + ".json"),
        "limits": load_json("limits", name + ".json"),
        "flops": load_module("flops", cell["config"] + ".py"),
        "env": load_module("envs", config["env"] + ".py"),
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def device_check(chips: int) -> Dict:
    """The device the cell runs on; fails without a TPU with enough
    chips, or with one the peaks table does not know."""
    interp = os.environ.get("REPRO_PALLAS_INTERPRET", "")
    if interp not in ("", "0"):
        raise Failed(f"REPRO_PALLAS_INTERPRET={interp!r} would interpret "
                     f"the kernels the benchmark times")
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Failed(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise Failed(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    peaks = load_json("peaks.json")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise Failed(f"device_kind {kind!r} is not in chipbench/peaks.json")
    return {"platform": devs[0].platform, "kind": kind, "count": chips,
            "peaks": peaks[kind]}


class CompileClock:
    """Seconds JAX spent compiling (or loading from the persistent
    cache), and how many programs."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def program_configs(files: Dict):
    """The program's agent and learning configuration for the cell."""
    from repro.configs.base import ImpalaConfig
    from repro.configs.registry import get_config

    cfg = files["config"]
    arch = get_config(cfg["arch"]).replace(
        image_hw=tuple(cfg["frame"]), lstm_width=cfg["lstm_width"])
    if arch.family != "impala_cnn" or arch.impala_net != cfg["torso"]:
        raise Failed(f"{cfg['arch']} is not the {cfg['torso']} conv agent")
    learn = cfg["learning"]
    icfg = ImpalaConfig(
        num_actions=cfg["num_actions"], unroll_length=cfg["unroll_length"],
        discount=learn["discount"], baseline_cost=learn["baseline_cost"],
        entropy_cost=learn["entropy_cost"], rho_bar=learn["rho_bar"],
        c_bar=learn["c_bar"], lambda_=learn["lambda"],
        reward_clip=learn["reward_clip"],
        learning_rate=learn["learning_rate"],
        rmsprop_decay=learn["rmsprop_decay"],
        rmsprop_eps=learn["rmsprop_eps"],
        rmsprop_momentum=learn["rmsprop_momentum"],
        grad_clip_norm=learn["grad_clip_norm"])
    return arch, icfg


def build_learner(files: Dict, seed: int):
    """The program's learner with its actors, service and queue, on the
    benchmark's weights made from ``seed``."""
    from chipbench import reference
    from repro.distributed.runtime import _setup

    cfg, traffic = files["config"], files["traffic"]
    env = files["env"].make(**cfg["env_args"])
    if list(env.image_hw) != list(cfg["frame"]) or \
            env.num_actions != cfg["num_actions"]:
        raise Failed(f"env {cfg['env']} renders {env.image_hw} with "
                     f"{env.num_actions} actions, the config says "
                     f"{cfg['frame']} and {cfg['num_actions']}")
    arch, icfg = program_configs(files)
    return _setup(
        env, icfg, traffic["num_envs"],
        num_actors=traffic["num_actors"],
        actor_backend=traffic["actor_backend"],
        actor_mode=traffic["actor_mode"], transport=traffic["transport"],
        queue_capacity=traffic["queue_capacity"],
        queue_policy=traffic["queue_policy"],
        max_batch_trajs=traffic["max_batch_trajs"],
        seed=PROGRAM_SEED, arch=arch,
        initial_params=reference.init_params(cfg, seed),
        infer_flush_timeout_s=traffic["infer_flush_timeout_s"],
        spmd_devices=traffic["spmd_devices"])


def capture_first_steps(learner, steps: int) -> Dict:
    """Keep host copies of what the learner's first ``steps`` updates
    trained on and produced; afterwards the learner runs its own method
    again, untouched."""
    import jax

    cap: Dict = {"batches": [], "losses": []}
    real = learner._update_once

    def update_once(batch, jnp, jax_, timings=None):
        host = jax.device_get(batch)
        out = real(batch, jnp, jax_, timings=timings)
        if out is None:
            return out
        published, metrics = out
        cap["batches"].append(host)
        cap["losses"].append(float(metrics["loss/total"]))
        if len(cap["batches"]) == 1:
            cap["ms1"] = jax.device_get(learner._opt_state)
        if len(cap["batches"]) == steps:
            cap["params_last"] = jax.device_get(published)
            del learner._update_once
        return out

    learner._update_once = update_once
    return cap


def _queue_area(q, now: float) -> Optional[float]:
    """Depth integrated over time since the queue was made."""
    lock = getattr(q, "_lock", None)
    if lock is None or not hasattr(q, "_occ_area"):
        return None
    with lock:
        return q._occ_area + len(q._q) * (now - q._occ_last)


def counters(learner, clock) -> Dict:
    now = time.monotonic()
    out = {"t": now, "frames": learner.frames_consumed,
           "updates": learner.updates,
           "batch_hist": dict(learner.batch_hist),
           "queue_area": _queue_area(learner.queue, now),
           "queue_dropped": learner.queue.snapshot().get("dropped", 0),
           "compile_s": clock.seconds, "compiles": clock.count}
    if learner.service is not None:
        snap = learner.service.snapshot()
        out["infer_requests"] = snap["requests"]
        out["infer_flushes"] = snap["flushes"]
    return out


class Window:
    """Opens the measured window once the pipeline is full and closes it
    after ``seconds``; both edges wait for the device."""

    def __init__(self, learner, clock, seconds: float, trace_dir=None):
        self.learner, self.clock = learner, clock
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.start = self.end = None
        self.publishes = []
        self._annotation = None

    def on_update(self, step, published, metrics, snapshot_fn):
        import jax

        if self.start is not None:
            self.publishes.append(time.monotonic())
            return
        if step < WARM_UPDATES or \
                not all(f > 0 for f in self.learner.pool.frames):
            return
        if self.trace_dir is not None:
            annotate_host_work(self.learner)
            jax.profiler.start_trace(
                self.trace_dir, profiler_options=_profile_options())
            self._annotation = jax.profiler.TraceAnnotation(
                "chipbench.window")
        jax.block_until_ready(published)
        if self._annotation is not None:
            self._annotation.__enter__()
        self.start = counters(self.learner, self.clock)
        self.publishes.append(self.start["t"])

    def should_stop(self) -> bool:
        import jax

        if self.start is None:
            if time.monotonic() - T_PROCESS > SETUP_LIMIT_S:
                raise Failed(f"no steady state after {SETUP_LIMIT_S} s: "
                             f"actor frames {self.learner.pool.frames}, "
                             f"updates {self.learner.updates}")
            return False
        if self.end is not None:
            return True
        if time.monotonic() < self.start["t"] + self.seconds:
            return False
        jax.block_until_ready(self.learner._params)
        self.end = counters(self.learner, self.clock)
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return True


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def annotate_host_work(learner) -> None:
    """Wrap the learner's and the inference service's host-side calls in
    profiler annotations, so the trace says what the host was doing
    while a chip sat idle (traced runs only)."""
    import jax

    def wrap(obj, attr, label):
        real = getattr(obj, attr)

        def annotated(*a, **kw):
            with jax.profiler.TraceAnnotation(label):
                return real(*a, **kw)
        setattr(obj, attr, annotated)

    wrap(learner.queue, "get", "learner: wait for a trajectory")
    wrap(learner, "_update_once", "learner: step and publish")
    wrap(learner._stager, "stack", "learner: stage the batch")
    if learner.service is not None:
        wrap(learner.service, "drive_flushes", "actors: inference flush")


class Context:
    """What a per-layer metric reader may read about the traced window."""

    def __init__(self, files, window: Window, trace, chips: int, peaks):
        self.config = files["config"]
        self.traffic = files["traffic"]
        self.flops = files["flops"]
        self.chips = chips
        self.peaks = peaks
        self.trace = trace
        self.start, self.end = window.start, window.end
        self.window_s = self.end["t"] - self.start["t"]
        h0, h1 = self.start["batch_hist"], self.end["batch_hist"]
        # trajectories per update -> updates in the window
        self.updates_by_trajs = {k: h1.get(k, 0) - h0.get(k, 0)
                                 for k in h1 if h1.get(k, 0) > h0.get(k, 0)}
        self.updates = self.end["updates"] - self.start["updates"]

    @property
    def rows_trained(self) -> int:
        """Trajectory rows (env streams) the updates in the window
        trained on."""
        return sum(k * n for k, n in self.updates_by_trajs.items()) * \
            self.traffic["num_envs"]


def read_metrics(per_layer, ctx) -> Dict:
    """Each per-layer metric the cell lists, from its own reader. A
    reader returns None where it finds nothing; the cell lists the
    metric, so its program ran in the window, and a pattern that matched
    nothing fails the run rather than drop the metric from the line."""
    out = {}
    for m in per_layer:
        v = load_module("metrics", m["name"] + ".py").compute(ctx)
        if v is None:
            raise Failed(f"metric {m['name']} found nothing to read in "
                         f"the trace of a cell that lists it")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def p95(values) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), 95))


def run_cell(files: Dict, seed: int, seconds: float, trace: bool,
             device: Dict, out=sys.stderr) -> Dict:
    """One run of a cell on the devices JAX has; returns the result line.
    The caller has checked the device."""
    import jax

    from repro.launch.train import enable_compile_cache

    enable_compile_cache()
    # every program, however quick to compile, comes from the cache on
    # the next run, so set-up does the same work every time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()
    t0 = time.monotonic()
    learner = build_learner(files, seed)
    t_built = time.monotonic()
    cap = capture_first_steps(learner, compare.STEPS)
    trace_dir = None
    if trace:
        trace_dir = os.path.join(TRACE_DIR, files["cell"]["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        seconds = min(seconds, TRACE_WINDOW_S)
    window = Window(learner, clock, seconds, trace_dir)
    learner.run(10 ** 9, warm_buckets=True, on_update=window.on_update,
                should_stop=window.should_stop)
    if window.end is None:
        raise Failed("the learner stopped before the window closed")
    chips = files["cell"]["chips"]
    devs = jax.devices()[:chips]
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
    start, end = window.start, window.end
    window_s = end["t"] - start["t"]
    lag = dict(sorted(learner.lag_hist.items()))
    print(f"set-up: imports {t0 - T_PROCESS:.1f} s, build {t_built - t0:.1f}"
          f" s, warm-up to the window {start['t'] - t_built:.1f} s, "
          f"compiles {start['compiles']} taking {start['compile_s']:.1f} s",
          file=out, flush=True)
    print(f"window {window_s!r} s, {end['updates'] - start['updates']} "
          f"updates, {end['frames'] - start['frames']} frames, "
          f"{len(window.publishes)} publishes; compiles in the window "
          f"{end['compiles'] - start['compiles']}; policy lag histogram "
          f"{lag}", file=out, flush=True)
    spmd = files["traffic"]["spmd_devices"]
    del learner
    gc.collect()

    t_ref = time.monotonic()
    leaves = {}
    values = compare.readings(files["config"], seed, cap, max(1, spmd),
                              leaves)
    print(f"reference {time.monotonic() - t_ref:.1f} s; readings {values}; "
          f"step loss gaps {leaves['loss_gaps']}, worst grad leaf "
          f"{leaves['grad']}, worst update leaf {leaves['update']}, left "
          f"out {leaves['dropped']}", file=out, flush=True)
    limits = files["limits"]
    correct = compare.judge(values, limits)

    result = {"correct": correct,
              "attempted": end["updates"] - start["updates"],
              "failed": end["queue_dropped"] - start["queue_dropped"],
              "metrics": {},
              "device": {"platform": device["platform"],
                         "kind": device["kind"], "count": chips,
                         "memory_peak_bytes": int(mem_peak)}}
    if trace:
        from chipbench import trace_reduce

        t_tr = time.monotonic()
        tr = trace_reduce.load(trace_dir)
        result["metrics"] = read_metrics(
            files["per_layer"], Context(files, window, tr, chips,
                                        device["peaks"]))
        if tr.devices:
            result["device"]["busy_s"] = tr.mean_busy_s()
            result["device"]["window_s"] = tr.window_s
        bd = trace_reduce.breakdown(tr)
        if bd is not None:
            result["breakdown"] = bd
        print(f"trace read in {time.monotonic() - t_tr:.1f} s", file=out,
              flush=True)
    else:
        gaps = [b - a for a, b in zip(window.publishes,
                                      window.publishes[1:])]
        result["metrics"] = {
            "frames_per_s": {"value": (end["frames"] - start["frames"])
                             / window_s, "unit": "frames/s"},
            "publish_gap_ms_p95": {"value": 1e3 * p95(gaps), "unit": "ms"},
            "setup_s": {"value": start["t"] - T_PROCESS, "unit": "s"},
        }
    result["checks"] = {k: {"value": values[k], "limit": lim}
                        for k, lim in limits.items()}
    for line in compare.report_lines(values, limits):
        print(line, file=out, flush=True)
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        files = load_cell(args.workload)
        device = device_check(files["cell"]["chips"])
        result = run_cell(files, args.seed, args.seconds, bool(args.trace),
                          device)
    except Failed as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        # a trace is tens of MB; the numbers read from it are in the line
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # every worker thread has been joined by now; skip interpreter
    # teardown, where live XLA runtime threads can abort and flip the
    # exit code
    os._exit(rc)
