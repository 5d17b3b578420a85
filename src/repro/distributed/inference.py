"""Dynamic-batching inference service (paper §3.1's batched actor
inference, service-shaped).

Instead of every actor paying a full policy forward for its own env
batch, actors in ``actor_mode='inference'`` become thin host-side env
steppers: each submits its per-step observation batch to one
``InferenceService`` that lives next to the learner, owns a single
jitted batched forward on the learner's device, and replies with
actions, behaviour log-probs, the next recurrent state, and the
parameter version it acted with. The service collects requests into
**power-of-two-bucketed** batches (at most log2 jit variants) and
flushes on whichever comes first:

  full      a max-size bucket of requests is pending;
  ready     every connected client has a request in (nobody else can
            submit — waiting longer is pure stall);
  timeout   the oldest pending request has waited ``flush_timeout_s``
            (stragglers don't gate the fleet).

Two client frontends share the service core:

  thread    ``service.connect()`` — requests are live array pytrees on a
            lock-protected deque, replies delivered through an Event. A
            request of device arrays gets its reply as device arrays,
            cut from the flush inside its program: the thread driver's
            acting steps never wait for a flush or copy its results.
  process   ``service.process_frontend(ctx)`` — requests travel as
            serde-encoded frames over a bounded multiprocessing wire,
            replies go back serde-encoded over a per-client pipe (the
            same byte boundary the trajectory pipeline already uses).

The service is deliberately limited to the paper's conv-LSTM agent
(``impala_cnn``): its per-step state is the explicit (h, c) pair the
client carries, so the service itself stays stateless and any flush can
mix any clients. Token backbones decode against a growing per-client
cache and keep their per-actor unrolls.

Telemetry: per-flush batch-size histogram, full/ready/timeout flush
counts, and request queue-wait quantiles — the knobs this service adds
(bucket size, flush timeout) are all observable from
``telemetry_snapshot()['inference']``.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import serde
from repro.distributed.paramstore import ParameterStore
from repro.models import backbone as bb
from repro.obs.trace import span

PyTree = Any

_STOP_FRAME = b""          # reply-pipe sentinel: service shut down


class InferenceReply(NamedTuple):
    """One client's slice of a flushed batch: device arrays for a
    request of device arrays, numpy otherwise."""
    action: Any                # (B,) int32
    logprob: Any               # (B,) f32 — behaviour log pi(a|x)
    lstm_state: Tuple[Any, Any]  # ((B, W), (B, W)) next recurrent state
    param_version: int


class _Pending(NamedTuple):
    data: PyTree               # request pytree (np or jax leaves)
    reply_fn: Callable[[Optional[InferenceReply]], None]
    submitted_at: float


class _Waiter:
    """Handle for an async in-process submission."""
    __slots__ = ("event", "slot")

    def __init__(self):
        self.event = threading.Event()
        self.slot: List[Optional[InferenceReply]] = [None]

    def deliver(self, r: Optional[InferenceReply]) -> None:
        self.slot[0] = r
        self.event.set()


def _wait_bucket(wait_s: float) -> int:
    """Power-of-two microsecond bucket for a queue wait: bucket ``k``
    covers ``[2^(k-1), 2^k)`` µs (k=0 is the sub-µs bucket). Integer
    keys so the registry's ``IntHistogram`` holds it and ``/metrics``
    renders one sample per bucket."""
    return max(0, int(wait_s * 1e6)).bit_length()


def _hist_quantile_ms(counts: Dict[int, int], q: float) -> float:
    """The q-quantile's bucket *upper bound* in ms, from a
    ``_wait_bucket`` histogram. Resolution is a factor of two — honest
    about what a bucketed histogram knows, and mergeable across
    learners, which the late point-sample deque this replaced was
    not."""
    total = sum(counts.values())
    if not total:
        return 0.0
    rank = q * total
    acc = 0
    for k in sorted(counts):
        acc += counts[k]
        if acc >= rank:
            return (1 << k) / 1e3
    return (1 << max(counts)) / 1e3


def _pow2_floor(n: int) -> int:
    b = 1
    while b * 2 <= n:
        b *= 2
    return b


def _pow2_ceil(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


class InferenceService:
    """One jitted batched per-step policy forward, shared by all actors.

    Request pytree (leaves batched over the client's envs)::

        {"obs_image": (B,H,W,C) u8, "last_action": (B,) i32,
         "last_reward": (B,) f32, "done": (B,) bool,
         "lstm_h": (B,W) f32, "lstm_c": (B,W) f32}

    Params come from the ``ParameterStore`` (pulled once per flush), so
    the behaviour policy advances with the learner and every reply is
    stamped with the version that produced it — the client stamps its
    trajectory with the version of the unroll's *first* step, keeping
    measured policy lag conservative.
    """

    def __init__(self, env, arch_cfg, icfg, store: ParameterStore, *,
                 num_clients: int, flush_timeout_s: float = 0.02,
                 max_batch_requests: Optional[int] = None, seed: int = 0,
                 rng_key=None, registry=None):
        """``rng_key`` (a jax PRNG key) overrides the seed-derived
        sampling stream — a learner group passes each member's
        ``fold_in(key(seed), learner_id)`` key so no two learners'
        services ever share an action-sampling stream; single-learner
        runs keep the plain ``seed`` path byte-for-byte."""
        if arch_cfg.family != "impala_cnn":
            raise ValueError(
                "InferenceService batches the per-step conv-LSTM policy; "
                f"family {arch_cfg.family!r} decodes against a per-client "
                "cache — use actor_mode='unroll'")
        if num_clients < 1:
            raise ValueError("num_clients must be >= 1")
        self._arch = arch_cfg
        self._num_actions = env.num_actions
        self._store = store
        self.flush_timeout_s = flush_timeout_s
        self.max_batch_requests = _pow2_floor(
            max_batch_requests or num_clients)
        base_key = jax.random.key(seed) if rng_key is None else rng_key
        self._key = jax.random.fold_in(base_key, 0x1f5)
        self._flush_seq = 0
        self._flush_fns: Dict[int, Callable] = {}   # bucket -> jitted fn
        self._warmed = False
        self._warm_lock = threading.Lock()

        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._pending: collections.deque = collections.deque()
        self._clients = 0           # connected clients (both frontends)
        self._paused = 0            # clients blocked outside the service
                                    # (e.g. on trajectory backpressure)
        self._stop = threading.Event()
        self._frontends: List[ProcessFrontend] = []
        self.errors: List[BaseException] = []

        # telemetry (service-thread writes under self._lock, snapshot()
        # reads). The hot-path request/frame totals live in a metrics
        # registry when one is passed so a live /metrics pull and the
        # end-of-run snapshot read the same storage.
        if registry is None:
            from repro.obs.metrics import Registry
            registry = Registry()
        self.registry = registry
        self.batch_hist = registry.int_histogram(
            "inference.batch_hist").counts
        # queue waits live in a registry histogram (power-of-two µs
        # buckets), not a bounded deque of samples: the percentiles in
        # snapshot() derive from ALL waits since start, and /metrics
        # exposes the full distribution as bucket-labelled samples
        self.wait_hist = registry.int_histogram(
            "inference.queue_wait_hist").counts
        self._c_requests = registry.counter("inference.requests")
        self._c_frames = registry.counter("inference.frames")
        self.flush_full = 0
        self.flush_ready = 0
        self.flush_timeouts = 0
        self.padded_requests = 0
        self.device_replies = 0     # requests answered with device rows
        self.host_replies = 0       # ... with numpy rows
        self._last_version = -1

        self._thread = threading.Thread(target=self._loop,
                                        name="inference-service",
                                        daemon=True)
        self._started = False
        self._loop_needed = False   # only process frontends need the
        # background flusher: thread clients leader-execute full buckets
        # and their wait() deadline covers straggler flushes, so in a
        # thread-only run the loop would just burn ~hundreds of spurious
        # GIL wake-ups per second on every submit notify

    # counter views (the registry instruments are the storage)

    @property
    def requests(self) -> int:
        return self._c_requests.value

    @property
    def frames(self) -> int:
        return self._c_frames.value

    # ------------------------------------------------------------------
    # the jitted flush: concat K requests -> one forward -> sample

    def _build_flush(self, k: int) -> Callable:
        """The batched forward of a ``k``-request bucket: concatenate the
        requests, one forward, sample. Returns the concatenated
        (action, logp, h, c); traced inside the bucket's program
        (``_bucket_program``)."""
        arch, num_actions = self._arch, self._num_actions
        base_key = self._key

        def forward(params, seq, reqs):
            # per-flush RNG stream derived *inside* the jit: a host-side
            # split/fold would cost one more device dispatch per flush
            key = jax.random.fold_in(base_key, seq)
            batch = (reqs[0] if k == 1 else
                     jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                                  *reqs))
            n = batch["last_action"].shape[0]
            model_batch = {
                "image": batch["obs_image"][:, None],
                "last_action": batch["last_action"][:, None],
                "last_reward": batch["last_reward"][:, None],
                "done": batch["done"][:, None],
                "lstm_state": (batch["lstm_h"], batch["lstm_c"]),
            }
            out = bb.apply_train(params, model_batch, arch, num_actions)
            logits = out.policy_logits[:, 0]
            action = jax.random.categorical(key, logits,
                                            axis=-1).astype(jnp.int32)
            logp = jax.nn.log_softmax(logits)[jnp.arange(n), action]
            h, c = out.cache
            return action, logp, h, c

        return forward

    def _bucket_program(self, k: int) -> Callable:
        """The jitted flush of a ``k``-request bucket. It returns the
        concatenated (action, logp, h, c), which a numpy client's reply
        slices on the host, and each request's own four rows, cut inside
        the program, which a device client's reply is handed as is."""
        forward = self._build_flush(k)

        def flush(params, seq, reqs):
            out = forward(params, seq, reqs)
            cuts = np.cumsum([r["last_action"].shape[0]
                              for r in reqs])[:-1].tolist()
            return out, tuple(zip(*(jnp.split(x, cuts) for x in out)))

        return jax.jit(flush)

    def _warm_buckets(self, sample: PyTree) -> None:
        """Compile every pow2 bucket variant up front (first request
        only): a straggler-sized bucket first appearing mid-run would
        otherwise drop a ~100ms+ XLA compile into the acting critical
        path — startup is the place to pay for all of them."""
        if self._warmed:
            return
        with self._warm_lock:
            if self._warmed:
                return
            params, _ = self._store.pull()
            b = 1
            while b <= self.max_batch_requests:
                with self._lock:
                    fn = self._flush_fns.get(b)
                    if fn is None:
                        fn = self._flush_fns[b] = self._bucket_program(b)
                jax.block_until_ready(fn(params, np.int64(0),
                                         (sample,) * b))
                b *= 2
            self._warmed = True

    # ------------------------------------------------------------------
    # service loop

    def _loop(self) -> None:
        try:
            while not self._stop.is_set():
                with self._cond:
                    batch, reason = self._take_locked()
                    if batch is None:
                        remaining = 0.05
                        if self._pending:
                            oldest = self._pending[0].submitted_at
                            remaining = max(0.0, self.flush_timeout_s -
                                            (time.monotonic() - oldest))
                        self._cond.wait(min(0.05, remaining)
                                        if self._pending else 0.05)
                        continue
                self._run_flush(batch, reason)
        except BaseException as e:     # surface in the learner thread
            self.errors.append(e)
            self.stop()

    def _take_locked(self) -> Tuple[Optional[List[_Pending]], str]:
        """Decide (under the lock) whether to flush now; pops the batch."""
        n = len(self._pending)
        if n == 0:
            return None, ""
        active = self._clients - self._paused
        if n >= self.max_batch_requests:
            k, reason = self.max_batch_requests, "full"
        elif self._clients and n >= max(1, active):
            # every client that *can* submit has a request in (paused
            # ones are blocked elsewhere, e.g. on trajectory
            # backpressure): waiting out the timeout cannot grow the
            # batch. Take everything up to the bucket — the flush pads
            # partial batches, it never splits a phase-coherent batch
            # into pow2 shards.
            k, reason = min(n, self.max_batch_requests), "ready"
        elif (time.monotonic() - self._pending[0].submitted_at
                >= self.flush_timeout_s):
            k, reason = min(n, self.max_batch_requests), "timeout"
        else:
            return None, ""
        return [self._pending.popleft() for _ in range(k)], reason

    def _run_flush(self, batch: List[_Pending], reason: str) -> None:
        # may run concurrently: on the service thread (timeout/frontend
        # flushes) and on leader client threads (full-bucket flushes) —
        # only the RNG advance and the jit cache need the lock, the
        # flush execution itself is free-threaded
        with span("infer.flush"):
            k = len(batch)
            # partial batches pad up to the power-of-two bucket by repeating
            # the last request (its duplicate replies are discarded): jit
            # variants stay log2-bounded and a phase-coherent partial batch
            # (e.g. 3 of 4 actors, the 4th mid-assembly) flushes whole
            # instead of splitting into pow2 shards
            kb = min(_pow2_ceil(k), self.max_batch_requests)
            self._warm_buckets(batch[0].data)
            with self._lock:
                fn = self._flush_fns[kb]
                self._flush_seq += 1
                seq = self._flush_seq
            params, version = self._store.pull()
            now = time.monotonic()
            reqs = [p.data for p in batch] + [batch[-1].data] * (kb - k)
            out, parts = fn(params, np.int64(seq), tuple(reqs))
            # a request of device arrays (the thread driver's) is answered
            # with its own rows as the program cut them: they stay on the
            # device, and nothing waits for the flush. A numpy request
            # (process and remote clients) needs host arrays: materialize
            # the concatenated outputs ONCE, since numpy row slices are
            # free views, where a lazy device slice per client would cost
            # each its own forced execution (~1ms each, measured)
            on_device = [all(isinstance(x, jax.Array)
                             for x in jax.tree.leaves(p.data))
                         for p in batch]
            host = (None if all(on_device) else
                    [np.asarray(x) for x in out])

            with self._lock:        # snapshot() reads these concurrently
                self.batch_hist[k] += 1
                if reason == "full":
                    self.flush_full += 1
                elif reason == "ready":
                    self.flush_ready += 1
                else:
                    self.flush_timeouts += 1
                self._c_requests.inc(k)
                self.device_replies += sum(on_device)
                self.host_replies += k - sum(on_device)
                self.padded_requests += kb - k
                self._last_version = version
                for p in batch:
                    self._c_frames.inc(p.data["last_action"].shape[0])
                    self.wait_hist[_wait_bucket(now - p.submitted_at)] += 1
            off = 0
            for p, dev, part in zip(batch, on_device, parts):
                b = p.data["last_action"].shape[0]
                action, logp, h, c = (part if dev else
                                      (x[off:off + b] for x in host))
                off += b
                try:
                    p.reply_fn(InferenceReply(action, logp, (h, c), version))
                except Exception as e:      # a dead pipe must not kill a flush
                    self.errors.append(e)

    # ------------------------------------------------------------------
    # submission + thread frontend

    def submit(self, data: PyTree,
               reply_fn: Callable[[Optional[InferenceReply]], None],
               submitted_at: Optional[float] = None) -> bool:
        """Queue one request for the background flusher; False iff the
        service is shut down (the caller gets no reply and should
        exit). This is the process frontend's path — in-process clients
        use ``submit_and_wait``/``submit_async``, whose callers also
        flush."""
        if self._stop.is_set():
            return False
        with self._cond:
            if self._stop.is_set():
                return False
            self._pending.append(_Pending(
                data, reply_fn, submitted_at or time.monotonic()))
            self._cond.notify()
        return True

    def submit_async(self, data: PyTree) -> Optional[_Waiter]:
        """Async submit for in-process clients: queue the request and
        return a waiter (None if shut down). The notify wakes the
        service thread, which flushes as soon as a bucket completes —
        the submitter is free to go do other work (the dual-stream
        actors step their other env half-batch here, hiding the flush
        latency entirely)."""
        w = _Waiter()
        with self._cond:
            if self._stop.is_set():
                return None
            self._pending.append(_Pending(data, w.deliver,
                                          time.monotonic()))
            self._cond.notify()
        return w

    def wait(self, w: _Waiter) -> Optional[InferenceReply]:
        """Block until the waiter's flush lands. A waiter whose wait
        crosses the flush deadline turns **leader** and runs the partial
        flush itself, so stragglers cannot stall behind a busy service
        thread. Returns None on shutdown."""
        while True:
            if w.event.wait(timeout=self.flush_timeout_s):
                return w.slot[0]
            if self._stop.is_set():
                return None
            with self._cond:
                batch, reason = self._take_locked()
            if batch is not None:
                self._run_flush(batch, reason)

    def submit_and_wait(self, data: PyTree) -> Optional[InferenceReply]:
        """Blocking submit, with **leader-executed flushes**: if this
        request completes a bucket (or makes every connected client
        pending), the submitting thread runs the flush itself instead of
        handing off to the service thread — on a busy host the two extra
        thread wake-ups per flush (wake the service, then wake the
        clients) are pure latency on the acting critical path. Returns
        None on shutdown."""
        with self._cond:
            if self._stop.is_set():
                return None
            w = _Waiter()
            self._pending.append(_Pending(data, w.deliver,
                                          time.monotonic()))
            self._cond.notify()
            batch, reason = self._take_locked()
        while batch is not None:
            self._run_flush(batch, reason)
            # the popped batch is the *oldest* pending; with more
            # requesters than the bucket holds, ours may not be in it
            if w.event.is_set():
                return w.slot[0]
            with self._cond:
                batch, reason = self._take_locked()
        return self.wait(w)

    def drive_flushes(self) -> None:
        """Flush everything pending, now, on the calling thread — the
        hot path of the single-threaded inference *driver* (thread-mode
        acting): the driver submits every logical actor's request and
        immediately executes the flush(es) itself, so a full acting
        cycle involves zero cross-thread wake-ups. Bypasses the
        full/ready/timeout rules (the driver knows nobody else is about
        to submit); frontend requests that happen to be pending ride
        along in the same flushes."""
        while True:
            with self._cond:
                n = len(self._pending)
                if n == 0:
                    return
                k = min(n, self.max_batch_requests)
                batch = [self._pending.popleft() for _ in range(k)]
            self._run_flush(
                batch, "full" if k >= self.max_batch_requests else "ready")

    def connect(self) -> "InferenceClient":
        with self._lock:
            self._clients += 1
        return InferenceClient(self)

    def _disconnect(self) -> None:
        with self._cond:
            self._clients = max(0, self._clients - 1)
            self._cond.notify()     # remaining pending may now be "ready"

    def _pause(self) -> None:
        """A client signalling it is blocked outside the service (its
        transport put is backpressured): stop counting it towards the
        ready rule so the others' batches flush without waiting for it —
        otherwise one learner-throttled actor stalls the whole fleet on
        flush timeouts and breaks the bucket phase."""
        with self._cond:
            self._paused += 1
            self._cond.notify()

    def _resume(self) -> None:
        with self._cond:
            self._paused = max(0, self._paused - 1)

    def attach_frontend(self, fe, num_clients: int = 0) -> None:
        """Register a frontend (process pipes, sockets, ...) with the
        service: count its clients towards the ready rule and make sure
        the background flusher runs — frontend submits have no waiting
        thread in this process. The one place the frontend lifecycle
        dance lives, whatever wire the frontend speaks."""
        with self._lock:
            self._clients += num_clients
        self._frontends.append(fe)
        self._loop_needed = True
        if self._started and not self._thread.is_alive():
            self._thread.start()

    def process_frontend(self, ctx, num_clients: int,
                         wire_capacity: Optional[int] = None
                         ) -> "ProcessFrontend":
        fe = ProcessFrontend(self, ctx, num_clients, wire_capacity)
        # clients counted per register() call, not up front
        self.attach_frontend(fe, num_clients=0)
        return fe

    # ------------------------------------------------------------------
    # lifecycle

    def start(self) -> None:
        if not self._started:
            self._started = True
            if self._loop_needed:
                self._thread.start()

    def stop(self) -> None:
        """Shut down: wake every blocked client with a None reply. Safe
        to call from any thread, idempotent. Process frontends are closed
        by the pool that created them (after its children joined)."""
        with self._cond:
            if self._stop.is_set():
                return
            self._stop.set()
            drained = list(self._pending)
            self._pending.clear()
            self._cond.notify_all()
        for p in drained:
            try:
                p.reply_fn(None)
            except Exception:
                pass
        if self._thread.is_alive() and \
                self._thread is not threading.current_thread():
            self._thread.join(timeout=5.0)

    close = stop

    @property
    def closed(self) -> bool:
        return self._stop.is_set()

    def raise_errors(self) -> None:
        if self.errors:
            raise RuntimeError("inference service failed") from \
                self.errors[0]

    # ------------------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            waits = dict(self.wait_hist)
            flushes = (self.flush_full + self.flush_ready +
                       self.flush_timeouts)
            return {
                "flushes": flushes,
                "flush_full": self.flush_full,
                "flush_ready": self.flush_ready,
                "flush_timeout": self.flush_timeouts,
                "batch_size_hist": dict(sorted(self.batch_hist.items())),
                "requests": self.requests,
                "padded_requests": self.padded_requests,
                "device_replies": self.device_replies,
                "host_replies": self.host_replies,
                "frames": self.frames,
                "mean_batch": (self.requests / flushes if flushes else 0.0),
                # bucket k covers [2^(k-1), 2^k) µs; /metrics renders
                # one repro_inference_queue_wait_hist{bucket="k"} per key
                "queue_wait_hist": dict(sorted(waits.items())),
                # quantiles derived from the full-run histogram (bucket
                # upper bounds): same keys the log line always printed
                "queue_wait_ms_p50": _hist_quantile_ms(waits, 0.50),
                "queue_wait_ms_p95": _hist_quantile_ms(waits, 0.95),
                "flush_timeout_s": self.flush_timeout_s,
                "max_batch_requests": self.max_batch_requests,
                "param_version": self._last_version,
            }


class InferenceClient:
    """Thread-mode client: blocking ``infer`` against the in-process
    service (leader-executed flushes — see ``submit_and_wait``). One
    outstanding request per client by construction."""

    def __init__(self, service: InferenceService):
        self._svc = service
        self._paused = False

    def infer(self, data: PyTree) -> Optional[InferenceReply]:
        """None means the service shut down: stop producing."""
        return self._svc.submit_and_wait(data)

    def submit_async(self, data: PyTree) -> Optional[_Waiter]:
        """Pipeline half of ``infer``; pair with ``wait``."""
        return self._svc.submit_async(data)

    def wait(self, w: Optional[_Waiter]) -> Optional[InferenceReply]:
        return None if w is None else self._svc.wait(w)

    def pause(self) -> None:
        """This client has left the request loop (assembly, transport
        backpressure): don't let batches wait for it. Idempotent."""
        if not self._paused:
            self._paused = True
            self._svc._pause()

    def resume(self) -> None:
        if self._paused:
            self._paused = False
            self._svc._resume()

    def close(self) -> None:
        self.resume()       # a paused client must not leak the count
        self._svc._disconnect()


class ProcessFrontend:
    """Parent-side bridge for actor *processes*: serde request frames in
    over one bounded wire, encoded replies out over per-client pipes.

    Mirrors ``ShmTransport``'s shutdown discipline: ``begin_shutdown``
    flips the drain loop to discard so children winding down can always
    flush their queue feeders; ``close`` (after the children are joined)
    tears the wire down.
    """

    def __init__(self, service: InferenceService, ctx, num_clients: int,
                 wire_capacity: Optional[int] = None):
        self._svc = service
        self._ctx = ctx
        self._wire = ctx.Queue(maxsize=wire_capacity or
                               max(2, num_clients * 2))
        self._reply_conns: Dict[int, Any] = {}
        self._paused_cids: set = set()
        self._discard = False
        self._stop_evt = threading.Event()
        self._closed = False
        self._thread = threading.Thread(target=self._loop,
                                        name="inference-frontend",
                                        daemon=True)

    def register(self, client_id: int) -> "PipeInferenceClient":
        """Create the picklable child-side handle for one actor process.
        Call before spawning; the parent keeps the reply send-end."""
        recv_conn, send_conn = self._ctx.Pipe(duplex=False)
        self._reply_conns[client_id] = send_conn
        with self._svc._lock:
            self._svc._clients += 1
        return PipeInferenceClient(client_id, self._wire, recv_conn)

    def start(self) -> None:
        self._thread.start()

    def _reply_fn_for(self, client_id: int
                      ) -> Callable[[Optional[InferenceReply]], None]:
        conn = self._reply_conns[client_id]

        def reply(r: Optional[InferenceReply]) -> None:
            if r is None:
                buf = _STOP_FRAME
            else:
                buf = serde.encode_tree(
                    {"action": np.asarray(r.action),
                     "logprob": np.asarray(r.logprob),
                     "lstm_h": np.asarray(r.lstm_state[0]),
                     "lstm_c": np.asarray(r.lstm_state[1])},
                    meta={"version": int(r.param_version)})
            try:
                conn.send_bytes(buf)
            except (OSError, BrokenPipeError, ValueError):
                pass                    # client exited first: fine

        return reply

    def _loop(self) -> None:
        import queue as stdlib_queue
        while not self._stop_evt.is_set():
            try:
                buf = self._wire.get(timeout=0.1)
            except stdlib_queue.Empty:
                continue
            except (EOFError, OSError):
                break
            try:
                data, meta = serde.decode_tree(buf)   # zero-copy views
            except serde.SerdeError as e:
                self._svc.errors.append(e)
                continue
            cid = int(meta["client"])
            ctl = meta.get("ctl")
            if ctl is not None:
                # pause/resume control frames, tracked per client id so
                # duplicated or reordered hints can never over- or
                # under-count the service's paused total
                if ctl == "pause" and cid not in self._paused_cids:
                    self._paused_cids.add(cid)
                    self._svc._pause()
                elif ctl == "resume" and cid in self._paused_cids:
                    self._paused_cids.discard(cid)
                    self._svc._resume()
                continue
            if self._discard or self._svc.closed:
                # shutdown: keep the wire flowing so child feeders can
                # always flush, and unblock the sender promptly
                self._reply_fn_for(cid)(None)
                continue
            if not self._svc.submit(data, self._reply_fn_for(cid),
                                    float(meta.get("t0",
                                                   time.monotonic()))):
                self._reply_fn_for(cid)(None)

    def begin_shutdown(self) -> None:
        """Flip to discard: the wire keeps draining (a child feeder
        blocked mid-write into a full pipe would hang that child's exit)
        but nothing reaches the service anymore."""
        self._discard = True

    def close(self) -> None:
        """Call after the client processes are joined."""
        if self._closed:
            return
        self._closed = True
        self._discard = True
        self._stop_evt.set()
        self._thread.join(timeout=5.0)
        try:
            while True:
                self._wire.get_nowait()
        except Exception:
            pass
        self._wire.close()
        self._wire.cancel_join_thread()
        for conn in self._reply_conns.values():
            try:
                conn.close()
            except OSError:
                pass


class PipeInferenceClient:
    """Picklable child-side handle: encodes the request pytree, ships it
    over the shared wire, blocks (stop-aware) on its private reply pipe.
    Moves only serde buffers — importable without jax."""

    def __init__(self, client_id: int, wire: Any, conn: Any):
        self._id = client_id
        self._wire = wire
        self._conn = conn
        self._stop: Optional[Any] = None    # bound by the child at start
        self._paused = False

    def bind_stop(self, stop_event: Any) -> None:
        self._stop = stop_event

    def _send_ctl(self, ctl: str, tries: int = 1) -> None:
        import queue as stdlib_queue
        buf = serde.encode_tree(None, meta={"client": self._id,
                                            "ctl": ctl})
        for _ in range(tries):
            if self._stop is not None and self._stop.is_set():
                return
            try:
                self._wire.put(buf, timeout=0.05)
                return
            except stdlib_queue.Full:
                continue
            except Exception:
                return                  # closed wire: shutting down

    def pause(self) -> None:
        """Tell the parent-side service this client left the request
        loop (assembly, trajectory backpressure). Idempotent; a tiny
        meta-only control frame rides the same FIFO wire, so it lands
        in order behind this client's requests. Best-effort — a lost
        pause only costs the others one flush-timeout wait."""
        if not self._paused:
            self._paused = True
            self._send_ctl("pause")

    def resume(self) -> None:
        """Unlike a lost pause, a lost *resume* would leave the service
        under-counting active clients for the rest of the run (chronic
        undersized batches), so it retries hard before giving up."""
        if self._paused:
            self._paused = False
            self._send_ctl("resume", tries=40)

    def submit_async(self, data: PyTree) -> Optional[bool]:
        """Ship the request frame; the reply is read by ``wait``. One
        outstanding request per client (each pipeline stream holds its
        own client, so FIFO on the private reply pipe is enough)."""
        import queue as stdlib_queue
        buf = serde.encode_tree(
            data, meta={"client": self._id, "t0": time.monotonic()})
        while True:
            if self._stop is not None and self._stop.is_set():
                return None
            try:
                self._wire.put(buf, timeout=0.1)
                return True
            except stdlib_queue.Full:
                continue
            except (ValueError, OSError):
                return None

    def wait(self, token: Optional[bool]) -> Optional[InferenceReply]:
        if token is None:
            return None
        while not self._conn.poll(0.1):
            if self._stop is not None and self._stop.is_set():
                return None
        try:
            rbuf = self._conn.recv_bytes()
        except (EOFError, OSError):
            return None
        if rbuf == _STOP_FRAME:
            return None
        tree, meta = serde.decode_tree(rbuf, copy=True)
        return InferenceReply(tree["action"], tree["logprob"],
                              (tree["lstm_h"], tree["lstm_c"]),
                              int(meta["version"]))

    def infer(self, data: PyTree) -> Optional[InferenceReply]:
        return self.wait(self.submit_async(data))

    def close(self) -> None:
        self.resume()
        try:
            self._conn.close()
        except OSError:
            pass
