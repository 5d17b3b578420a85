"""Time-series sink and profiling hooks.

``JsonlSink`` appends the telemetry snapshot to a JSONL file every
``interval_s`` from a daemon thread — the poor operator's Prometheus:
a run leaves behind a greppable time series (one JSON object per line,
wall-clock stamped) even when nobody was curling /metrics.

``ProfileHook`` wraps ``jax.profiler`` around a chosen train-step
window (``--profile-steps A:B``): the trace starts before step A's
update and stops after step B's, producing a TensorBoard-loadable
profile directory. It records the device planes and the host's
annotations — the program spans of ``repro.obs.trace``
(``HOST_SPAN_NAMES``) among them — and no Python tracer: jax's default
would add an event for every Python call. A profiler that fails to
start or stop (profiler unavailable, trace dir not writable) fails the
run: a traced run without its trace has not measured what it was
started for.
"""
from __future__ import annotations

import json
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple


class JsonlSink:
    """Periodic snapshot dumps: one JSON object per line."""

    def __init__(self, path: str,
                 snapshot_fn: Callable[[], Dict[str, Any]],
                 interval_s: float = 5.0):
        self.path = path
        self._snapshot_fn = snapshot_fn
        self._interval_s = max(0.05, interval_s)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.lines_written = 0

    def _write_one(self, f) -> None:
        try:
            snap = self._snapshot_fn()
        except Exception as e:
            snap = {"error": repr(e)}
        f.write(json.dumps({"t": time.time(), "telemetry": snap},
                           default=float))
        f.write("\n")
        f.flush()
        self.lines_written += 1

    def _run(self) -> None:
        with open(self.path, "a") as f:
            while not self._stop.wait(self._interval_s):
                self._write_one(f)
            self._write_one(f)      # final state on shutdown

    def start(self) -> "JsonlSink":
        self._thread = threading.Thread(target=self._run,
                                        name="telemetry-sink",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)


def parse_profile_steps(spec: str) -> Tuple[int, int]:
    """``"A:B"`` -> (A, B), inclusive update-index window, A <= B."""
    a, sep, b = spec.partition(":")
    if not sep:
        raise ValueError(f"--profile-steps wants A:B, got {spec!r}")
    lo, hi = int(a), int(b)
    if lo < 0 or hi < lo:
        raise ValueError(f"bad profile window {spec!r} (need 0<=A<=B)")
    return lo, hi


def profile_options():
    """The profiler's options: device planes and host annotations
    (``host_tracer_level`` 1), no Python tracer."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


class ProfileHook:
    """Start/stop ``jax.profiler`` around updates [A, B]."""

    def __init__(self, steps: str, out_dir: str):
        self.lo, self.hi = parse_profile_steps(steps)
        self.out_dir = out_dir
        self.active = False
        self.done = False

    def on_step(self, next_update: int) -> None:
        """Call once per loop iteration with the index of the update
        about to run (0-based ``learner.updates``)."""
        if self.done:
            return
        if not self.active and self.lo <= next_update <= self.hi:
            import jax
            jax.profiler.start_trace(self.out_dir,
                                     profiler_options=profile_options())
            self.active = True
            print(f"[obs] jax.profiler tracing updates "
                  f"[{self.lo}, {self.hi}] -> {self.out_dir}", flush=True)
        elif self.active and next_update > self.hi:
            self.stop()

    def stop(self) -> None:
        self.done = True
        if self.active:
            self.active = False     # never stop twice, even on error
            import jax
            jax.profiler.stop_trace()
