"""Sampled per-trajectory lifecycle tracing across process boundaries.

A sampled trajectory carries a ``trace`` dict of CLOCK_MONOTONIC stamps
in its ``TrajectoryItem`` (and through the serde meta when it crosses a
wire):

    u0 / u1   env unroll start / end (actor side, actor's clock)
    e0 / e1   serde encode start / end (actor side; ``serde.encode_item``
              stamps e1 itself, *after* the payload bytes are built, so
              the stamp can still ride in the header it closes)
    r         receipt into the learner-side policy queue (stamped by
              ``TrajectoryQueue._accept`` — uniform across the inproc,
              shm, and socket transports)

The learner adds its own loop stamps (dequeue, batch collect, train
step, publish) and the recorder folds each sampled item into the seven
lifecycle spans::

    env_unroll -> serde_encode -> transport -> queue_wait
               -> batch_collect -> train_step -> publish

Clock normalization reuses the socket transport's learner-clock
precedent: CLOCK_MONOTONIC is comparable across processes on one box,
so same-box stamps need no shift. When actor and learner clocks
visibly disagree (different machines — the send/receive gap exceeds
``CLOCK_SKEW_S``), the actor-side stamps are shifted so the send
coincides with the learner's receive stamp: every span lands on the
learner's clock, at the cost of folding the (unknowable one-way) wire
latency into the transport span's start.

Export is Chrome trace-event JSON (``{"traceEvents": [...]}``, complete
"X" events, microsecond timestamps) — loadable in Perfetto or
chrome://tracing. Each actor renders as its own process row; the
learner's spans render under the learner row.

Program spans
-------------

Separately from the sampled lifecycle above, the hot loops open
``span(name)`` — a ``jax.profiler.TraceAnnotation`` — around their host
work, always, with no flag. With no profiler running one costs about a
microsecond; under a profiler (``--profile-steps A:B``, or any
``jax.profiler`` trace) each lands on the profiler's host plane, on the
same clock as the device planes, so an idle stretch of the chip can be
put down to the host work that filled it. The names
(``HOST_SPAN_NAMES``):

    acting.step       one step of the thread-mode inference driver:
                      every logical actor's submit, the flush, every
                      env-step dispatch
    acting.env_step   nested in acting.step: the replies taken and the
                      env steps dispatched
    acting.assemble   one inference-mode trajectory packed (the thread
                      driver's is one device program's dispatch)
    acting.emit       one trajectory handed to the transport, with its
                      backpressure retries (driver and unroll actors)
    acting.unroll     one unroll actor's jitted unroll, to
                      ``block_until_ready``
    infer.flush       one inference flush, from picking the bucket to
                      the last reply handed out (params pull, dispatch,
                      and the wait for the device)
    learner.wait      the learner blocked in ``queue.get``
    learner.stage     first trajectory in hand to the batch staged:
                      collect, bookkeeping, replay, stack and its
                      ``device_put`` (a lone trajectory goes to the
                      device inside the step's call)
    learner.reshard   nested in learner.stage (SPMD learner): a batch
                      the host stager could not take (device arrays
                      from thread actors) concatenated where it lives
                      and moved onto the ``('data',)`` mesh (dispatch)
    learner.step      the update (on the fused path, its dispatch)
    learner.publish   the parameter publish

The learner's spans open and close where ``Learner._record_obs`` takes
its stamps, so ``phases``, this module's lifecycle and a profile cut the
loop in the same places. On the fused single-learner path the
``train_step`` span here and ``phases.step`` (like ``learner.step``)
time the step's *dispatch*, not its device work; a profile's device
plane has the device time. Spans opened in actor processes land in
those processes' own profiler sessions, not the learner's.
"""
from __future__ import annotations

import json
import threading
from typing import Any, Dict, List, Optional

SPAN_NAMES = ("env_unroll", "serde_encode", "transport", "queue_wait",
              "batch_collect", "train_step", "publish")

# gradient-exchange rounds render on their own process row (pid 2):
# hub_wait (round open -> last contribution in), reduce (mean + encode),
# broadcast (fan the mean back out to every live spoke)
EXCHANGE_SPAN_NAMES = ("hub_wait", "reduce", "broadcast")

# same-box monotonic clocks agree to microseconds; a send->receive gap
# beyond this means a different clock domain (another machine)
CLOCK_SKEW_S = 5.0

HOST_SPAN_NAMES = ("acting.step", "acting.env_step", "acting.assemble",
                   "acting.emit", "acting.unroll", "infer.flush",
                   "learner.wait", "learner.stage", "learner.reshard",
                   "learner.step", "learner.publish")
_HOST_SPANS = frozenset(HOST_SPAN_NAMES)


def span(name: str):
    """A ``jax.profiler.TraceAnnotation`` for one of ``HOST_SPAN_NAMES``
    (a context manager). jax is imported here, on first use, so this
    package stays importable without it."""
    if name not in _HOST_SPANS:
        raise ValueError(f"{name!r} is not in HOST_SPAN_NAMES")
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


class TraceRecorder:
    """Collects sampled trajectories' spans; bounded, thread-safe."""

    def __init__(self, max_trajectories: int = 2048):
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        self._pids_named: set = set()
        self._max = max_trajectories
        self.recorded = 0
        self.dropped = 0

    # ------------------------------------------------------------------

    def _name_pid(self, pid: int, name: str) -> None:
        if pid in self._pids_named:
            return
        self._pids_named.add(pid)
        self._events.append({"name": "process_name", "ph": "M",
                             "pid": pid, "tid": 0,
                             "args": {"name": name}})

    def record_item(self, item, *, dequeued: float, collected: float,
                    step0: float, step1: float, published: float,
                    lag: Optional[int] = None) -> None:
        """Fold one sampled item (its actor-side ``trace`` stamps plus
        the learner's loop stamps, all seconds CLOCK_MONOTONIC) into
        trace events. Safe to call with partial stamps — missing actor
        stamps degrade to zero-length spans, never to an exception."""
        tr = getattr(item, "trace", None)
        if tr is None:
            return
        with self._lock:
            if self.recorded >= self._max:
                self.dropped += 1
                return
            self.recorded += 1

            r = tr.get("r", dequeued)
            u1 = tr.get("u1", r)
            u0 = tr.get("u0", u1)
            e0 = tr.get("e0", u1)
            e1 = tr.get("e1", e0)
            # learner-clock normalization: shift actor stamps only when
            # the clocks visibly disagree (cross-machine)
            off = (r - e1) if abs(r - e1) > CLOCK_SKEW_S else 0.0
            u0, u1, e0, e1 = (t + off for t in (u0, u1, e0, e1))

            actor_pid = 1000 + int(item.actor_id)
            self._name_pid(actor_pid, f"actor-{item.actor_id}")
            self._name_pid(1, "learner")

            spans = (
                ("env_unroll", actor_pid, u0, u1),
                ("serde_encode", actor_pid, e0, e1),
                ("transport", actor_pid, e1, r),
                ("queue_wait", 1, r, dequeued),
                ("batch_collect", 1, dequeued, collected),
                ("train_step", 1, collected if step0 is None else step0,
                 step1),
                ("publish", 1, step1, published),
            )
            args = {"actor_id": int(item.actor_id),
                    "param_version": int(item.param_version)}
            if lag is not None:
                args["lag"] = int(lag)
            for name, pid, t0, t1 in spans:
                self._events.append({
                    "name": name, "ph": "X", "pid": pid, "tid": 0,
                    "ts": t0 * 1e6,
                    "dur": max(0.0, (t1 - t0) * 1e6),
                    "args": args,
                })

    def record_exchange_round(self, round_idx: int, *, enter: float,
                              gathered: float, reduced: float,
                              done: float) -> None:
        """Fold one gradient-exchange round (hub-side CLOCK_MONOTONIC
        stamps) into hub_wait -> reduce -> broadcast spans on the
        ``exchange`` row. A failover round shows up as an oversized
        hub_wait span followed by a gap in the round numbering."""
        with self._lock:
            if self.recorded >= self._max:
                self.dropped += 1
                return
            self.recorded += 1
            self._name_pid(2, "exchange")
            args = {"round": int(round_idx)}
            for name, t0, t1 in (("hub_wait", enter, gathered),
                                 ("reduce", gathered, reduced),
                                 ("broadcast", reduced, done)):
                self._events.append({
                    "name": name, "ph": "X", "pid": 2, "tid": 0,
                    "ts": t0 * 1e6,
                    "dur": max(0.0, (t1 - t0) * 1e6),
                    "args": args,
                })

    # ------------------------------------------------------------------

    def chrome_events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def export(self, path: str) -> int:
        """Write ``{"traceEvents": [...]}``; returns the number of
        sampled trajectories recorded."""
        with self._lock:
            doc = {"traceEvents": list(self._events),
                   "displayTimeUnit": "ms"}
            n = self.recorded
        with open(path, "w") as f:
            json.dump(doc, f)
            f.write("\n")
        return n
