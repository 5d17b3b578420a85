"""Flight-recorder observability for the distributed runtime.

Four pieces, all stdlib-only at import time (the distributed modules
import this package before paying the jax import, like the transports):

  metrics   a thread-compatible registry of named counters / gauges /
            integer histograms plus pull-time *producers*. The hot-path
            modules (tqueue, socket transport, inference service,
            learner) write their existing counters through registry
            instruments, and ``Learner.telemetry_snapshot`` /
            ``group.merge_telemetry`` derive the pinned telemetry key
            sets from a registry ``collect()`` — live metrics and
            end-of-run telemetry are one data source, not two.
  trace     sampled per-trajectory lifecycle spans (env unroll -> serde
            encode -> transport -> queue wait -> batch collect -> train
            step -> publish), stamped across process/socket boundaries
            and normalized to the learner's clock, exported as Chrome
            trace-event JSON (loadable in Perfetto / chrome://tracing);
            and ``span``, the program's always-on host spans
            (``HOST_SPAN_NAMES``) on a ``jax.profiler`` trace.
  http      a background stdlib HTTP server next to the learner serving
            ``/metrics`` (Prometheus text format), ``/healthz``
            (ok / degraded / unhealthy), and ``/telemetry`` (live JSON).
  sink      periodic JSONL time-series dumps of the telemetry snapshot,
            plus the ``--profile-steps A:B`` hook wrapping
            ``jax.profiler`` around chosen train steps.

``ObsConfig`` is the single knob bag the CLI builds and the runtime
threads through ``run_async_training(obs=...)`` /
``run_group_training(obs=...)``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from repro.obs.metrics import Counter, Gauge, IntHistogram, Registry  # noqa: F401
from repro.obs.trace import (HOST_SPAN_NAMES, SPAN_NAMES,  # noqa: F401
                             TraceRecorder, span)


@dataclasses.dataclass
class ObsConfig:
    """What the operator asked to observe. All fields default to off;
    an all-defaults ObsConfig still enables phase timing (it only
    exists because someone passed ``obs=``)."""

    metrics_port: Optional[int] = None      # None = no HTTP server
    metrics_host: str = "127.0.0.1"
    trace_path: Optional[str] = None        # Chrome trace JSON out
    trace_every: int = 64                   # sample every Nth unroll/actor
    profile_steps: Optional[str] = None     # "A:B" train-step window
    profile_dir: str = "/tmp/repro-profile"
    sink_path: Optional[str] = None         # JSONL time series out
    sink_interval_s: float = 5.0
    telemetry_interval_s: float = 2.0       # child->parent pipe shipping
    # set by the runtime once the HTTP server binds (port 0 resolves
    # here), so tests and log lines can discover the real address
    bound_address: Optional[Tuple[str, int]] = None
