"""The learner worker, extracted from the ``run_async_training``
monolith so one implementation serves both the single-learner runtime
and the multi-learner ``LearnerGroup`` (paper §3's *several learners,
each owning a shard of actors*).

A ``Learner`` owns exactly the four things the old loop hard-coded:

  batch collection   drain ONE ``Transport`` with dynamic batching
                     (power-of-two buckets, oldest-first requeue of
                     overflow, optional linger deadline) into per-bucket
                     ping-ponged host staging buffers;
  train step         the donated fused ``train_step`` when it trains
                     alone; a split ``grad_step`` / ``apply_step``
                     pair when a ``GradientExchange`` sits between the
                     backward pass and the optimizer (data-parallel
                     learners apply the *exchanged mean*, so replicas
                     stay bit-identical); or the donated ``shard_map``
                     SPMD step when the exchange is in-XLA
                     (``CollectiveExchange``): batch sharded over a
                     ``('data',)`` mesh, params/opt replicated, the
                     gradient mean a fused ``lax.pmean`` — the
                     N-learner-group update without N processes or a
                     single TCP frame;
  publish            every update lands in the learner's own
                     ``ParameterStore`` — self-versioned when alone,
                     at the exchange-delegated version when grouped
                     (one designated publisher numbers the rounds, so
                     every actor in the group sees a single monotonic
                     version stream);
  telemetry          the same snapshot keys the runtime always
                     reported (updates, fps, batch/lag histograms,
                     queue, actors, inference), plus ``learner_id`` /
                     ``exchange`` sections only when grouped.

Per-learner randomness is ``fold_in(key(seed), learner_id)`` —
``self.key``, which seeds the grouped inference service's sampling
stream — while parameter *initialization* stays at the raw
``key(seed)`` on every learner: data-parallel replicas must start
identical, and ``--learners 1`` must bit-match the single-learner run.

Deliberately no jax import at module level: ``LearnerGroup`` worker
processes import this module (like the transports) before paying the
jax import, and the import-guard test pins that edge.
"""
from __future__ import annotations

import collections
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.core.metrics import EpisodeTracker
from repro.core import replay as replay_lib
from repro.distributed.paramstore import ParameterStore
from repro.distributed.serde import TrajectoryItem
from repro.obs.metrics import Registry
from repro.obs.trace import span

PyTree = Any


class MultiTracker:
    """Episode-return accounting across actor-local env batches.

    ``slot_base`` maps *global* actor slot ids (what a sharded pool
    stamps into trajectories) onto this learner's local tracker list —
    learner k of a group owns slots [base, base+n) and sees only those.
    Completion times are recorded (CLOCK_MONOTONIC, comparable across
    processes on one box) so a group can merge the per-learner streams
    back into one chronological return history."""

    def __init__(self, num_actors: int, num_envs: int,
                 slot_base: int = 0):
        self.trackers = [EpisodeTracker(num_envs) for _ in range(num_actors)]
        self.slot_base = slot_base
        self._merged: List[float] = []
        self._merged_at: List[float] = []

    def update(self, actor_id: int, rewards, dones) -> None:
        t = self.trackers[actor_id - self.slot_base]
        before = len(t.completed)
        t.update(np.asarray(rewards), np.asarray(dones))
        # merge in consumption order so mean_return's last-n window is
        # chronological, not actor-grouped
        fresh = t.completed[before:]
        if fresh:
            now = time.monotonic()
            self._merged.extend(fresh)
            self._merged_at.extend([now] * len(fresh))

    @property
    def completed(self) -> List[float]:
        return list(self._merged)

    @property
    def completed_timed(self) -> List[Tuple[float, float]]:
        """(monotonic completion time, return) pairs, consumption
        order — what a group merge sorts on."""
        return list(zip(self._merged_at, self._merged))

    def mean_return(self, last_n: int = 100) -> float:
        if not self._merged:
            return float("nan")
        return float(np.mean(self._merged[-last_n:]))


def _buckets(max_batch_trajs: int) -> List[int]:
    """Power-of-two stack sizes <= max, descending (compile-count bound)."""
    out, b = [], 1
    while b <= max_batch_trajs:
        out.append(b)
        b *= 2
    return out[::-1]


def _collect_batch(queue, buckets: List[int], first: TrajectoryItem,
                   linger_s: float = 0.0,
                   max_items: Optional[int] = None) -> List[TrajectoryItem]:
    """Starting from ``first`` (already popped), drain the queue up to
    the largest bucket, then trim to the largest power-of-two that
    fits — requeueing the overflow *at the front, newest first*, so the
    queue keeps oldest-first order and the next batch starts with the
    trajectories this one could not stack.

    ``linger_s`` is the learner-side flush deadline (the mirror of the
    inference service's): rather than greedily training on whatever is
    queued, wait up to this long for the bucket to fill. A starved
    learner taking singleton batches pays the update's fixed cost per
    trajectory — and on a shared host, those extra updates steal the
    very cores the actors need to refill the queue. The deadline bounds
    the staleness this adds; a full bucket never waits.

    ``max_items`` (replay path) caps fresh collection below the top
    bucket — the learner tops the batch up with replayed trajectories,
    so it deliberately drains fewer online ones per update."""
    items = [first]
    cap = buckets[0] if max_items is None else min(max_items, buckets[0])
    deadline = (time.monotonic() + linger_s) if linger_s > 0 else None
    while len(items) < cap:
        nxt = queue.get_nowait()
        if nxt is None:
            if deadline is None:
                break
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            nxt = queue.get(timeout=remaining)
            if nxt is None:
                break
        items.append(nxt)
    k = next(b for b in buckets if b <= len(items))
    for extra in reversed(items[k:]):
        queue.requeue_front(extra)
    return items[:k]


def _device_put_copies() -> bool:
    """Probe whether ``jax.device_put`` of a host buffer COPIES on this
    backend. The CPU backend zero-copy *aliases* 64-byte-aligned numpy
    buffers (measured on jax 0.4.37, ~half of all allocations): the
    returned "device" array IS the host memory, so a staging buffer
    that produced one can never be rewritten while any consumer might
    still read the batch. Probed on a deterministically 64-aligned
    view so the answer doesn't depend on allocator luck."""
    import jax

    raw = np.zeros(1024 + 16, np.float32)
    off = (-raw.ctypes.data) % 64 // raw.itemsize
    aligned = raw[off:off + 1024]
    dev = jax.device_put(aligned)
    jax.block_until_ready(dev)
    aligned[0] = 1.0
    return float(np.asarray(dev)[0]) == 0.0


class _HostStager:
    """Per-(bucket, structure) host staging buffers for the learner's
    consume path.

    Serialized transports deliver numpy (often read-only view) leaves;
    stacking ``k`` trajectories with ``np.concatenate`` allocates one
    intermediate per leaf per update. Instead each leaf is written in
    place into a staging buffer and the whole tree moves with one
    ``device_put``. Buffer lifetime depends on what ``device_put``
    does, probed once:

      copies (accelerators)   two preallocated sets per bucket,
          **ping-ponged**, and before a set is *re*-written the batch
          it produced two updates ago is ``block_until_ready``-ed — the
          ping-pong alone only pipelines the async H2D transfer, it is
          not a completion guarantee (by reuse time the transfer has
          long finished, so the block is effectively free).
      aliases (CPU backend)   the "transfer" is free but the batch IS
          the staging memory, with no event to wait on for its
          consumers — so buffers are freshly allocated per stack and
          never reused (same copy count as the concatenate path, still
          a single device_put for the whole tree).

    ``mesh`` (SPMD learner mode) switches to *sharded* staging: one
    host buffer set per mesh device, each leaf's rows written straight
    into its shard's buffer, one ``device_put`` per shard, and the
    pieces assembled into global arrays under an explicit
    ``NamedSharding`` — the batch lands pre-sharded on the ``('data',)``
    axis with no dispatch-time re-slicing. A row count the mesh cannot
    split falls back to a single buffer replicated explicitly
    (mirroring ``sharding/rules.py``'s divisibility fallback).
    """

    def __init__(self, mesh=None):
        self._slots: Dict[Any, list] = {}
        self._reuse = _device_put_copies()
        self.last_device_put_s = 0.0    # phase-timing probe, per stack
        self._mesh = mesh
        self._n = int(mesh.devices.size) if mesh is not None else 1

    def stack(self, items: List[TrajectoryItem]) -> Optional[PyTree]:
        """Staged stack of >=2 same-shaped numpy trajectories; None if
        the items are not uniform host trees (caller falls back)."""
        import jax

        datas = [it.data for it in items]
        leaves0, treedef = jax.tree.flatten(datas[0])
        if not all(isinstance(x, np.ndarray) for x in leaves0):
            return None
        shapes = tuple((x.shape, x.dtype.name) for x in leaves0)
        for d in datas[1:]:
            ls, td = jax.tree.flatten(d)
            if td != treedef or \
                    tuple((x.shape, x.dtype.name) for x in ls) != shapes:
                return None                 # ragged: not the hot path
        k = len(items)

        if self._mesh is not None:
            b = leaves0[0].shape[0]
            if (k * b) % self._n == 0 and \
                    all(x.shape[0] == b for x in leaves0):
                return self._stack_sharded(datas, leaves0, treedef, k)

        def alloc():
            return [np.empty((x.shape[0] * k,) + x.shape[1:], x.dtype)
                    for x in leaves0]

        if self._reuse:
            key = (k, treedef, shapes)
            slot = self._slots.get(key)
            if slot is None:
                # [two buffer sets, next index, last batch per set]
                slot = self._slots[key] = [(alloc(), alloc()), 0,
                                           [None, None]]
            idx = slot[1]
            bufs = slot[0][idx]
            slot[1] ^= 1
            if slot[2][idx] is not None:
                jax.block_until_ready(slot[2][idx])
        else:
            bufs = alloc()
        for i, d in enumerate(datas):
            for buf, leaf in zip(bufs, jax.tree.leaves(d)):
                b = leaf.shape[0]
                buf[i * b:(i + 1) * b] = leaf
        t0 = time.monotonic()
        tree = jax.tree.unflatten(treedef, bufs)
        if self._mesh is not None:
            # Rules divisibility fallback, staging edition: rows the
            # mesh can't split land replicated, the sharding the step
            # compiles for them (``core.learner.batch_splits``).
            from jax.sharding import NamedSharding, PartitionSpec
            out = jax.device_put(
                tree, NamedSharding(self._mesh, PartitionSpec()))
        else:
            out = jax.device_put(tree)
        self.last_device_put_s = time.monotonic() - t0
        if self._reuse:
            slot[2][idx] = out
        return out

    def _stack_sharded(self, datas, leaves0, treedef, k) -> PyTree:
        """SPMD staging: write each item's rows into the per-device
        shard buffer(s) they land on, ship one ``device_put`` per mesh
        device, and assemble global arrays with an explicit
        ``NamedSharding(mesh, P('data'))`` via
        ``make_array_from_single_device_arrays`` — the jitted shard_map
        step sees exactly the sharding it was compiled for.

        Buffers are freshly allocated per stack: sharded staging is
        only reachable in SPMD mode, which forces multi-device CPU (or
        real accelerators where per-shard transfers copy anyway), and
        the alias-vs-copy ping-pong discipline of the single-device
        path would need one event per shard for no measured win."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh, n = self._mesh, self._n
        b = leaves0[0].shape[0]
        rows = k * b
        r = rows // n
        shard_bufs = [[np.empty((r,) + x.shape[1:], x.dtype)
                       for x in leaves0] for _ in range(n)]
        for i, d in enumerate(datas):
            for j, leaf in enumerate(jax.tree.leaves(d)):
                lo = i * b                      # item rows [lo, lo+b)
                for s in range(lo // r, (lo + b - 1) // r + 1):
                    a = max(lo, s * r)          # overlap with shard s
                    z = min(lo + b, (s + 1) * r)
                    shard_bufs[s][j][a - s * r:z - s * r] = \
                        leaf[a - lo:z - lo]
        devices = mesh.devices.flatten()
        t0 = time.monotonic()
        per_dev = [jax.device_put(shard_bufs[s], devices[s])
                   for s in range(n)]
        global_leaves = []
        for j, x in enumerate(leaves0):
            sharding = NamedSharding(mesh, P("data"))
            global_leaves.append(jax.make_array_from_single_device_arrays(
                (rows,) + x.shape[1:], sharding,
                [per_dev[s][j] for s in range(n)]))
        self.last_device_put_s = time.monotonic() - t0
        return jax.tree.unflatten(treedef, global_leaves)

    def reshard(self, tree: PyTree) -> PyTree:
        """SPMD fallback for batches that bypassed sharded host staging
        (device-array leaves from inproc thread actors, ragged trees):
        one resharding ``device_put`` onto the mesh, sharded on the
        leading axis when the rows divide, replicated otherwise — a
        batch left committed to one device would collide with the
        mesh-wide params at dispatch."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.core.learner import batch_splits

        spec = P("data") if batch_splits(self._mesh, tree) else P()
        return jax.device_put(tree, NamedSharding(self._mesh, spec))


def _stack(items: List[TrajectoryItem],
           stager: Optional[_HostStager] = None,
           routes: Optional[Dict[str, Any]] = None) -> PyTree:
    """The items' trajectories as one batch. ``routes`` (SPMD learner)
    counts how it reached the mesh: ``sharded``, staged from host
    buffers by the stager; ``resharded``, device leaves moved by
    ``_HostStager.reshard``."""
    import jax
    import jax.numpy as jnp

    if len(items) == 1 and (stager is None or stager._mesh is None):
        # SPMD staging must see even single items so the batch lands
        # pre-sharded (or explicitly replicated) on the mesh.
        return items[0].data

    if stager is not None:
        staged = stager.stack(items)
        if staged is not None:
            if routes is not None:
                routes["sharded"].inc()
            return staged

    def cat(*xs):
        # fallback: host concatenate for numpy leaves (one copy, feeding
        # the jit's host->device transfer), device concatenate otherwise
        if isinstance(xs[0], np.ndarray):
            return np.concatenate(xs, axis=0)
        return jnp.concatenate(xs, axis=0)

    if stager is None or stager._mesh is None:
        return jax.tree.map(cat, *[it.data for it in items])
    # SPMD, leaves the stager could not take (device arrays from thread
    # actors, ragged trees): concatenated where they live, then moved
    # onto the mesh
    with span("learner.reshard"):
        out = stager.reshard(jax.tree.map(cat, *[it.data for it in items]))
    if routes is not None:
        routes["resharded"].inc()
    return out


class Learner:
    """One learner worker: drains a ``Transport`` with dynamic
    batching, trains, publishes versioned params, reports telemetry.

    Construction builds the params/optimizer/train-step state and the
    learner's own ``ParameterStore`` (available as ``self.store`` for
    wiring the actor pool / inference service); ``attach`` binds the
    pool (and optional service) once they exist; ``run`` executes the
    training loop end to end, owning the start/stop/join/close
    lifecycle exactly as ``run_async_training`` always did.

    ``exchange`` (a ``group.GradientExchange``) switches the update
    from the fused donated ``train_step`` to the data-parallel split:
    jitted backward pass -> host gradient leaves -> synchronous
    exchange (mean over the group, stale contributions dropped by the
    hub's rule) -> donated ``apply_step`` of the *mean* -> publish at
    the exchange-delegated version. Every learner applies the same
    broadcast mean with the same optimizer state, so the replicas stay
    bit-identical without ever shipping parameters between learners.

    An *in-XLA* exchange (``group.CollectiveExchange``) selects SPMD
    mode instead: one process, one donated ``shard_map`` train step
    over a ``('data',)`` device mesh. The batch is staged pre-sharded
    on the leading trajectory axis, params/opt state stay replicated,
    and the gradient mean is a fused ``lax.pmean`` — the same
    mathematical update as an N-learner group at equal global batch,
    with zero host round-trips (and zero TCP frames) in the gradient
    path. The exchange object only delegates version numbers and
    records per-round latency; stale-drop never fires because nothing
    can be stale.
    """

    def __init__(self, *, arch, icfg, num_actions: int, num_envs: int,
                 num_actors: int, transport, seed: int = 0,
                 learner_id: int = 0, num_learners: int = 1,
                 slot_base: int = 0, actor_mode: str = "unroll",
                 max_batch_trajs: int = 4, batch_linger_s: float = 0.0,
                 donate: bool = True, start_step: int = 0,
                 initial_params: Optional[PyTree] = None,
                 initial_opt_state: Optional[PyTree] = None,
                 exchange=None, registry: Optional[Registry] = None,
                 wire_codec: str = "none", vtrace_impl: str = "auto",
                 trace=None, phase_timing: bool = False, profile=None):
        import jax
        import jax.numpy as jnp

        from repro.core import learner as learner_lib
        from repro.models import backbone as bb
        from repro.models import common as pcommon

        if max_batch_trajs < 1:
            raise ValueError(f"max_batch_trajs must be >= 1, got "
                             f"{max_batch_trajs}")
        self.arch = arch
        self.icfg = icfg
        self.learner_id = learner_id
        self.num_learners = num_learners
        self.slot_base = slot_base
        self.actor_mode = actor_mode
        self.donate = donate
        self.batch_linger_s = batch_linger_s
        self.queue = transport
        self._exchange = exchange
        self.wire_codec = wire_codec
        self.vtrace_impl = vtrace_impl
        # learner-local randomness (NOT param init): fold the learner id
        # into the run seed so two learners of one group never share a
        # stream. Today this feeds the grouped inference service's
        # action-sampling key (see runtime._setup); any future
        # learner-local stochastic op must draw from it too.
        self.key = jax.random.fold_in(jax.random.key(seed), learner_id)

        specs = bb.backbone_specs(arch, num_actions)
        if initial_params is not None:
            params = initial_params
        else:
            # param init stays at the RAW seed on every learner:
            # data-parallel replicas must start identical, and
            # --learners 1 must bit-match the single-learner run
            params = pcommon.init_params(specs, jax.random.key(seed))
        # what the loss will run, resolved now so telemetry shows it:
        # the implementation, and whether its Pallas kernel is
        # interpreted (never on a TPU unless forced)
        from repro.core.losses import resolve_loss_impl
        from repro.kernels.vtrace import resolve_interpret
        impl = resolve_loss_impl(icfg, vtrace_impl,
                                 replay=icfg.replay_fraction > 0.0)
        self.vtrace_resolved = {
            "impl": impl,
            "interpret": (impl in ("fused", "pallas")
                          and resolve_interpret())}
        spmd_on = exchange is not None and getattr(exchange, "in_xla",
                                                   False)
        from repro.launch.mesh import make_data_mesh
        self._spmd_mesh = (make_data_mesh(exchange.num_devices) if spmd_on
                           else None)
        # params and opt state are donated in every mode; the replay
        # target, the step's trailing argument, is a long-lived snapshot
        donated = (0, 1) if donate else ()
        if exchange is None or spmd_on:
            # one program per update, on one device or over the mesh
            train_step, opt = learner_lib.build_train_step(
                arch, icfg, num_actions, vtrace_impl=vtrace_impl,
                mesh=self._spmd_mesh)
            self._train_step = jax.jit(train_step, donate_argnums=donated)
            self._grad_step = self._apply_step = None
        else:
            grad_step, apply_step, opt = learner_lib.build_grad_apply_steps(
                arch, icfg, num_actions, vtrace_impl=vtrace_impl)
            self._train_step = None
            self._grad_step = jax.jit(grad_step)
            self._apply_step = jax.jit(apply_step, donate_argnums=donated)
        # one jitted whole-tree device copy: the decoupling between the
        # learner's donated working tree and every reference that
        # escapes (store, service, on_update). XLA never aliases
        # non-donated outputs to inputs, so the copy's buffers are
        # independent by construction.
        self._snapshot = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))
        if initial_opt_state is not None:
            # checkpoint resume: restore the optimizer moments instead
            # of re-initializing — device_put so donation never aliases
            # the caller's (possibly mmapped) host buffers
            opt_state = jax.device_put(initial_opt_state)
        else:
            opt_state = opt.init(params)
        if spmd_on:
            from jax.sharding import NamedSharding, PartitionSpec as P

            # params and opt state live replicated over the mesh from
            # the start: a donated shard_map step whose arguments
            # already carry the compiled sharding never reshards on
            # entry
            params, opt_state = jax.device_put(
                (params, opt_state), NamedSharding(self._spmd_mesh, P()))
            # the published snapshot is re-homed on one device so the
            # inference service's forward doesn't run replicated over
            # the whole mesh
            self._spmd_publish_dev = jax.devices()[0]
        self._params, self._opt_state = params, opt_state
        self.store = ParameterStore(self._publish(params),
                                    version=start_step,
                                    wire_codec=wire_codec)
        self.start_step = start_step
        self.tracker = MultiTracker(num_actors, num_envs,
                                    slot_base=slot_base)
        self._buckets = _buckets(max_batch_trajs)
        self._stager = _HostStager(mesh=self._spmd_mesh)
        self._frames_per_traj = num_envs * icfg.unroll_length
        self._num_envs = num_envs
        self._init_replay(seed)
        self._target_syncs = 0
        self.frames_trained = 0
        self.pool = None
        self.service = None

        # telemetry state (same pinned snapshot keys the runtime always
        # reported, but the storage now lives in a metrics registry: the
        # lag/batch histograms ARE registry instruments — the hot-path
        # `hist[k] += 1` writes the registry — and everything else is a
        # pull-time producer, so the live /metrics endpoint and the
        # end-of-run snapshot can never disagree.
        self.obs_registry = registry if registry is not None else Registry()
        self.lag_hist = self.obs_registry.int_histogram(
            "learner.lag_hist").counts
        self.batch_hist = self.obs_registry.int_histogram(
            "learner.batch_hist").counts
        self.updates = start_step
        self.frames_consumed = 0
        self._steady_t0: Optional[float] = None
        self._steady_updates0 = 0
        self._steady_frames0 = 0
        self._steady_trained0 = 0
        self._first_t0: Optional[float] = None
        self._first_updates0 = 0
        self._first_frames0 = 0
        self._first_trained0 = 0
        self.metrics: Dict = {}
        # flight recorder hooks (all optional, see repro.obs)
        self.trace = trace                  # TraceRecorder or None
        self._phase_timing = bool(phase_timing)
        self._profile = profile             # ProfileHook or None
        self._phase_acc = {"collect": 0.0, "host_stage": 0.0,
                           "device_put": 0.0, "step": 0.0, "publish": 0.0}
        self._phase_n = 0
        reg = self.obs_registry
        # SPMD only: how each update's batch reached the mesh
        # (``sharded`` + ``resharded`` = batches, counted by ``_stack``),
        # and apart from those, how often the step ran on a batch the
        # mesh could not split (every device computing the whole batch)
        self._spmd_batches = (
            {k: reg.counter(f"learner.spmd_batches.{k}")
             for k in ("sharded", "resharded", "replicated")}
            if spmd_on else None)
        reg.register_producer("learner", self._core_telemetry)
        reg.register_producer(
            "queue", lambda: (self.queue.snapshot()
                              if self.queue is not None else None))
        reg.register_producer(
            "actors", lambda: (self.pool.stats()
                               if self.pool is not None else {}))
        reg.register_producer(
            "inference", lambda: (self.service.snapshot()
                                  if self.service is not None else None))
        reg.register_producer(
            "exchange", lambda: (self._exchange.snapshot()
                                 if self._exchange is not None else None))
        reg.register_producer("replay", self._replay_telemetry)

    # ------------------------------------------------------------------

    def attach(self, pool, service=None) -> None:
        """Bind the actor pool (and optional inference service) this
        learner drives; both were built against ``self.store`` and
        ``self.queue``."""
        self.pool = pool
        self.service = service

    # ------------------------------------------------------------------

    def _core_telemetry(self) -> Dict:
        """The ``learner`` registry producer: counts, rates, version."""
        now = time.monotonic()
        if self._steady_t0 is not None:
            dt, u0, f0 = (now - self._steady_t0, self._steady_updates0,
                          self._steady_frames0)
        elif self._first_t0 is not None:
            dt, u0, f0 = (now - self._first_t0, self._first_updates0,
                          self._first_frames0)
        else:
            dt, u0, f0 = 0.0, 0, 0
        return {
            "updates": self.updates,
            "frames_consumed": self.frames_consumed,
            "updates_per_sec": ((self.updates - u0) / dt
                                if dt > 0 else 0.0),
            "frames_per_sec": ((self.frames_consumed - f0) / dt
                               if dt > 0 else 0.0),
            "param_version": self.store.version,
            "wire_codec": self.wire_codec,
            "param_wire_bytes": self.store.serialized_wire_bytes,
            "param_raw_bytes": self.store.serialized_raw_bytes,
        }

    def _replay_telemetry(self) -> Optional[Dict]:
        """The ``replay`` registry producer — None (and therefore
        omitted from /metrics and the snapshot) when replay is off, so
        the pinned single-learner key set is untouched."""
        if self._replay is None:
            return None
        now = time.monotonic()
        if self._steady_t0 is not None:
            dt, t0 = now - self._steady_t0, self._steady_trained0
        elif self._first_t0 is not None:
            dt, t0 = now - self._first_t0, self._first_trained0
        else:
            dt, t0 = 0.0, 0
        snap = self._replay.snapshot()
        snap["fraction"] = self.icfg.replay_fraction
        snap["fresh_max"] = self._fresh_max
        snap["frames_trained"] = self.frames_trained
        # reuse ratio: frames the optimizer saw per env frame consumed
        # (1.0 = one-pass IMPALA; ~1/(1-fraction) in steady state)
        snap["reuse_ratio"] = (self.frames_trained / self.frames_consumed
                               if self.frames_consumed else 0.0)
        snap["trained_frames_per_sec"] = ((self.frames_trained - t0) / dt
                                          if dt > 0 else 0.0)
        snap["target_syncs"] = self._target_syncs
        snap["target_period"] = self.icfg.replay_target_period
        return snap

    def telemetry_snapshot(self) -> Dict:
        """The pinned snapshot key set, assembled from one registry
        pull — the same storage the live /metrics endpoint reads."""
        col = self.obs_registry.collect()
        core = col.get("learner", {})
        lag_hist = col.get("learner.lag_hist", {})
        n_lags = sum(lag_hist.values())
        snap = {
            "learner_updates": core.get("updates", self.updates),
            "frames_consumed": core.get("frames_consumed",
                                        self.frames_consumed),
            "updates_per_sec": core.get("updates_per_sec", 0.0),
            "frames_per_sec": core.get("frames_per_sec", 0.0),
            "batch_size_hist": dict(col.get("learner.batch_hist", {})),
            "lag": {
                "hist": dict(sorted(lag_hist.items())),
                "mean": (sum(k * v for k, v in lag_hist.items())
                         / n_lags if n_lags else 0.0),
                "max": max(lag_hist) if lag_hist else 0,
                "measured": n_lags,
            },
            "queue": col.get("queue", {}),
            "actors": col.get("actors", {}),
            "param_version": core.get("param_version",
                                      self.store.version),
            "actor_mode": self.actor_mode,
            "donate": self.donate,
            "vtrace": dict(self.vtrace_resolved),
        }
        if "inference" in col:
            snap["inference"] = col["inference"]
        if "replay" in col:
            # replay runs only: reuse ratio, priority/staleness hists,
            # occupancy — the producer returns None (omitted) otherwise
            snap["replay"] = col["replay"]
        if self._exchange is not None:
            # grouped only: the single-learner snapshot keys must stay
            # exactly what run_async_training always reported
            snap["learner_id"] = self.learner_id
            snap["slot_base"] = self.slot_base
            snap["exchange"] = col.get("exchange",
                                       self._exchange.snapshot())
            if self._spmd_mesh is not None:
                # SPMD runs surface the same ``group`` section the
                # multi-process topologies emit, so dashboards key on
                # one shape; backend label tells them apart
                ex = snap["exchange"] or {}
                snap["group"] = {
                    "num_learners": 1,
                    "publisher": self.learner_id,
                    "exchange_backend": ex.get("exchange_backend",
                                               "collective"),
                    "spmd_devices": ex.get(
                        "devices", int(self._spmd_mesh.devices.size)),
                    "rounds": ex.get("rounds", 0),
                    "batches": {k: c.value for k, c in
                                self._spmd_batches.items()},
                }
        if "supervisor" in col:
            # supervised only: restart/failover/lease-reap counts ride
            # the snapshot so a final telemetry dump (and the group
            # parent's merge) shows exactly what the run survived;
            # unsupervised runs keep the pinned key set untouched
            snap["supervisor"] = col["supervisor"]
        if self._phase_timing:
            # gated on the flight recorder being enabled: the pinned
            # key-set equivalence (group-of-one vs single run) holds for
            # runs without obs, which never see this key
            n = self._phase_n
            snap["phases"] = {
                "updates_timed": n,
                "total_s": dict(self._phase_acc),
                "mean_ms": {k: (1e3 * v / n if n else 0.0)
                            for k, v in self._phase_acc.items()},
            }
        return snap

    # ------------------------------------------------------------------

    def _raise_worker_errors(self) -> None:
        self.pool.raise_errors()
        if self.service is not None:
            self.service.raise_errors()

    # ------------------------------------------------------------------
    # the update

    def _init_replay(self, seed: int) -> None:
        """Replay state, all None without replay: the buffer, the cap
        on fresh trajectories per batch, and the IMPACT target."""
        icfg = self.icfg
        self._replay = self._fresh_max = self._target_params = None
        if icfg.replay_fraction <= 0.0:
            return
        # replay RNG identity is (seed, learner_id) — the
        # fold_replay_seed discipline keeps group replicas on
        # deterministic per-replica streams (and since every replica
        # trains the same exchanged mean, giving them the SAME stream
        # isn't needed for digest-identity; what matters is that each
        # is deterministic across a restart)
        self._replay = replay_lib.ReplayBuffer(
            icfg.replay_capacity, seed=seed, learner_id=self.learner_id,
            reuse_limit=icfg.replay_reuse, priority=icfg.replay_priority)
        self._fresh_max = max(1, int(round(
            self._buckets[0] * (1.0 - icfg.replay_fraction))))
        # IMPACT target: a periodic copy of the learner params supplies
        # the V-trace baseline for replayed rows; synced every
        # icfg.replay_target_period updates (a pure function of the
        # update count, so group replicas sync in lockstep)
        self._target_params = self._snapshot(self._params)

    @property
    def _target_args(self) -> tuple:
        """The replay target as the step's trailing argument; empty
        without replay."""
        return () if self._target_params is None else (self._target_params,)

    def _with_replay_mask(self, batch, n_replayed: int, n_items: int):
        """The batch with its replay mask (replayed rows sit first) riding
        as data, so every bucket keeps a single compiled program; the
        batch itself without replay."""
        if self._replay is None:
            return batch
        mask = np.zeros(n_items * self._num_envs, np.float32)
        mask[:n_replayed * self._num_envs] = 1.0
        return {**batch, "replay_mask": mask}

    def _publish(self, params):
        """What escapes the learner after an update: a decoupled snapshot
        when the working tree is donated. SPMD re-homes it on one device,
        so the store (and through it the inference service's jit and
        every actor pull) sees a plain single-device tree, not an array
        replicated over the mesh — a replicated forward would run on
        every mesh device."""
        import jax
        if self.donate:
            params = self._snapshot(params)
        if self._spmd_mesh is not None:
            params = jax.device_put(params, self._spmd_publish_dev)
        return params

    def _warm(self, params, opt_state) -> None:
        """Pre-compile the train step for every batch bucket on
        throwaway copies (donation would otherwise consume the real
        trees), so benchmarks measure steady state, not XLA."""
        import jax
        import jax.numpy as jnp

        first = None
        while first is None:
            self._raise_worker_errors()
            first = self.queue.get(timeout=0.5)
        for b in self._buckets:
            # staged as steady state stages it, so each bucket's compile
            # sees its input sharding; an all-zero replay mask compiles
            # the one program each bucket ever needs
            warm = self._with_replay_mask(_stack([first] * b, self._stager),
                                          0, b)
            if self._grad_step is None:
                out = self._train_step(self._snapshot(params),
                                       self._snapshot(opt_state),
                                       jnp.int32(0), warm,
                                       *self._target_args)
            else:
                grads, _ = self._grad_step(params, warm, *self._target_args)
                out = self._apply_step(self._snapshot(params),
                                       self._snapshot(opt_state),
                                       jnp.int32(0), grads)
            jax.block_until_ready(out[0])   # compile only; discard
        self.queue.requeue_front(first)

    def _update_once(self, batch, jnp, jax, timings=None):
        """One training update on ``batch`` and its publish. Returns
        (published params, metrics) or None when the exchange shut
        down.

        ``timings`` (a dict, flight-recorder runs only) receives
        step0/step1/published stamps; the ``learner.step`` and
        ``learner.publish`` profiler spans open and close at the same
        points. On the fused path these bracket the async *dispatch* —
        blocking for the device would tax the pipeline the recorder
        exists to observe; the split path's ``np.asarray`` already
        forces the backward pass, so its stamps are real."""
        if timings is not None:
            timings["step0"] = time.monotonic()
        with span("learner.step"):
            stepped = self._step(batch, jnp, jax)
        if stepped is None:
            return None
        published, metrics, version = stepped
        if timings is not None:
            timings["step1"] = time.monotonic()
        with span("learner.publish"):
            if version is None:
                self.store.publish(published)
            else:
                # versioned publish delegation: the exchange's
                # designated publisher numbers the rounds; every
                # learner's store publishes at exactly that version, so
                # the group's actors observe one monotonic version
                # stream no matter which learner they pull from
                self.store.publish_at(published, version)
        if timings is not None:
            timings["published"] = time.monotonic()
        return published, metrics

    def _step(self, batch, jnp, jax):
        """The update itself: one program when alone or SPMD, split
        backward/exchange/apply under a host exchange. Returns (params
        to publish, metrics, the version to publish them at — None for
        the store's next) or None when the exchange shut down."""
        if self._grad_step is None:
            t0 = time.monotonic()
            self._params, self._opt_state, metrics = self._train_step(
                self._params, self._opt_state, jnp.int32(self.updates),
                batch, *self._target_args)
            if self._spmd_mesh is None:
                return self._publish(self._params), metrics, None
            # SPMD: the whole group update was that one donated dispatch
            # — backward, in-XLA pmean, optimizer. Nothing crosses the
            # host, so the exchange only delegates the version number
            # and books the round.
            from repro.core.learner import batch_splits
            if not batch_splits(self._spmd_mesh, batch):
                self._spmd_batches["replicated"].inc()
            reduced = self._exchange.allreduce((), round_idx=self.updates)
            if reduced is None:
                return None                 # exchange shutting down
            published = self._publish(self._params)
            # grad_norm is computed from the pmean'd mean: waiting on it
            # waits on the collective completing on every shard, so the
            # observed round latency is the real all-reduce+apply time
            jax.block_until_ready(metrics["opt/grad_norm"])
            self._exchange.observe_round_s(time.monotonic() - t0,
                                           round_idx=self.updates)
            return published, metrics, reduced[1]
        grads, metrics = self._grad_step(self._params, batch,
                                         *self._target_args)
        leaves, treedef = jax.tree.flatten(grads)
        # np.asarray forces the backward pass and lands the gradient
        # leaves host-side (views on the CPU backend, copies elsewhere)
        flat = [np.asarray(x) for x in leaves]
        reduced = self._exchange.allreduce(flat, round_idx=self.updates)
        if reduced is None:
            return None                     # group shutting down
        mean_leaves, version = reduced
        mean = jax.tree.unflatten(treedef, list(mean_leaves))
        self._params, self._opt_state, ametrics = self._apply_step(
            self._params, self._opt_state, jnp.int32(self.updates), mean)
        return self._publish(self._params), {**metrics, **ametrics}, version

    def _sample_replay(self, num_fresh: int, version_now: int):
        """Plan and draw the replayed top-up for a batch of
        ``num_fresh`` online trajectories; None = train pure online
        this round (fraction 0, buffer still filling, or starved)."""
        if self._replay is None:
            return None
        n_rep = replay_lib.plan_mix(
            num_fresh, self._buckets[0], self.icfg.replay_fraction,
            self._replay.num_sampleable())
        if n_rep < 1:
            return None
        return self._replay.sample_items(n_rep, version_now=version_now)

    def _replay_bookkeeping(self, metrics, samples, fresh_items):
        """Post-step replay accounting: pop the per-trajectory
        advantage-magnitude metric (it is (B,)-shaped and must not
        reach scalar metric consumers), re-score the replayed slots
        with it, and insert the freshly trained trajectories with their
        measured priority and their online pass pre-counted
        (``uses=1``), so ``--replay-reuse K`` caps *total*
        consumptions."""
        metrics = dict(metrics)
        mags = metrics.pop("vtrace/traj_adv_mag", None)
        n_rep = len(samples) if samples else 0
        per = None
        if mags is not None:
            # row r of the stacked batch belongs to trajectory r //
            # num_envs (the stager lays item i at rows [i*b, (i+1)*b))
            per = np.asarray(mags, np.float64).reshape(
                n_rep + len(fresh_items), self._num_envs).mean(axis=1)
        if n_rep and per is not None:
            self._replay.update_priorities(
                [s.uid for s in samples], per[:n_rep])
        for j, it in enumerate(fresh_items):
            self._replay.add_item(
                it,
                priority=(float(per[n_rep + j]) if per is not None
                          else None),
                uses=1)
        return metrics

    def _record_obs(self, items, version_now: int, t_deq: float,
                    t_col: float, t_stk: float,
                    timings: Dict[str, float]) -> None:
        """Fold one update's stamps into the phase accumulators and the
        trace recorder (sampled items only)."""
        step0 = timings.get("step0", t_stk)
        step1 = timings.get("step1", step0)
        pub = timings.get("published", step1)
        if self._phase_timing:
            acc = self._phase_acc
            acc["collect"] += t_col - t_deq
            acc["host_stage"] += t_stk - t_col
            acc["device_put"] += self._stager.last_device_put_s
            acc["step"] += step1 - step0
            acc["publish"] += pub - step1
            self._phase_n += 1
        if self.trace is not None:
            for it in items:
                if getattr(it, "trace", None) is not None:
                    self.trace.record_item(
                        it, dequeued=t_deq, collected=t_col,
                        step0=step0, step1=step1, published=pub,
                        lag=version_now - it.param_version)

    def run(self, steps: int, *, warm_buckets: bool = False,
            on_update: Optional[Callable] = None,
            should_stop: Optional[Callable[[], bool]] = None,
            on_checkpoint: Optional[Callable] = None,
            ckpt_every: int = 0) -> Tuple[Dict, Dict]:
        """Train until ``steps`` total updates (or ``should_stop``).
        Owns the full worker lifecycle: starts the service/pool, runs
        the loop, then stops/joins/closes in the only order that never
        tears a frame. Returns (last metrics, final telemetry).

        ``on_checkpoint(step, params, opt_state, version)`` fires every
        ``ckpt_every`` updates (host numpy trees, decoupled from the
        donated working state) — the periodic-checkpoint hook; the
        4-arg ``on_update`` signature stays exactly as it always was."""
        import jax
        import jax.numpy as jnp

        if self.pool is None:
            raise RuntimeError("attach(pool) before run()")
        if self.service is not None:
            self.service.start()
        self.pool.start()
        try:
            if warm_buckets:
                self._warm(self._params, self._opt_state)

            # flight-recorder stamps only when something consumes them:
            # the plain hot path stays free of per-update clock reads
            want_t = self._phase_timing or self.trace is not None
            while self.updates < steps:
                if should_stop is not None and should_stop():
                    break
                self._raise_worker_errors()
                with span("learner.wait"):
                    item = self.queue.get(timeout=0.5)
                if item is None:
                    continue
                t_deq = time.monotonic() if want_t else 0.0
                with span("learner.stage"):
                    # replay caps fresh collection below the top bucket —
                    # the batch is topped back up with replayed rows,
                    # which is exactly where the env-frame saving comes
                    # from
                    items = _collect_batch(self.queue, self._buckets, item,
                                           self.batch_linger_s,
                                           max_items=self._fresh_max)
                    k = len(items)
                    t_col = time.monotonic() if want_t else 0.0

                    version_now = self.store.version
                    for it in items:
                        self.lag_hist[version_now - it.param_version] += 1
                        self.tracker.update(it.actor_id,
                                            it.data["rewards"],
                                            it.data["done"])
                    samples = self._sample_replay(k, version_now)
                    train_items = ([s.item for s in samples] + items
                                   if samples else items)
                    if want_t:
                        self._stager.last_device_put_s = 0.0
                    batch = _stack(train_items, self._stager,
                                   self._spmd_batches)
                    batch = self._with_replay_mask(
                        batch, len(samples) if samples else 0,
                        len(train_items))
                t_stk = time.monotonic() if want_t else 0.0
                if self._profile is not None:
                    self._profile.on_step(self.updates)
                timings = {} if want_t else None
                stepped = self._update_once(batch, jnp, jax,
                                            timings=timings)
                if stepped is None:
                    break                   # exchange shut down under us
                published, metrics = stepped
                if self._replay is not None:
                    metrics = self._replay_bookkeeping(metrics, samples,
                                                       items)
                self.metrics = metrics
                self.updates += 1
                if self._replay is not None and \
                        self.updates % self.icfg.replay_target_period == 0:
                    # IMPACT target sync: a pure function of the update
                    # count, so group replicas flip targets in lockstep.
                    # `published` is already a decoupled snapshot (or
                    # the functionally-replaced live tree), never a
                    # donated buffer. SPMD re-replicates it over the
                    # mesh: the shard_map step was compiled for a
                    # P()-sharded target, and feeding it the
                    # single-device publish copy would recompile.
                    if self._spmd_mesh is not None:
                        from jax.sharding import (NamedSharding,
                                                  PartitionSpec)
                        self._target_params = jax.device_put(
                            published, NamedSharding(self._spmd_mesh,
                                                     PartitionSpec()))
                    else:
                        self._target_params = published
                    self._target_syncs += 1
                self.frames_consumed += k * self._frames_per_traj
                self.frames_trained += (len(train_items) *
                                        self._frames_per_traj)
                self.batch_hist[len(train_items)] += 1
                if want_t:
                    self._record_obs(items, version_now, t_deq, t_col,
                                     t_stk, timings)
                if self._steady_t0 is None:
                    jax.block_until_ready(self._params)
                    if self._first_t0 is None:
                        # first update includes the learner's jit compile
                        self._first_t0 = time.monotonic()
                        self._first_updates0 = self.updates
                        self._first_frames0 = self.frames_consumed
                        self._first_trained0 = self.frames_trained
                    if all(f > 0 for f in self.pool.frames):
                        # every worker is past import/compile and
                        # producing
                        self._steady_t0 = time.monotonic()
                        self._steady_updates0 = self.updates
                        self._steady_frames0 = self.frames_consumed
                        self._steady_trained0 = self.frames_trained
                if on_update is not None:
                    on_update(self.updates, published, self.metrics,
                              self.telemetry_snapshot)
                if on_checkpoint is not None and ckpt_every > 0 and \
                        self.updates % ckpt_every == 0:
                    on_checkpoint(self.updates,
                                  jax.tree.map(np.asarray, published),
                                  self.opt_state_host(),
                                  self.store.version)
            # snapshot before teardown: pool.join waits out in-flight
            # unrolls and put timeouts, which would silently pad the
            # steady-state dt
            jax.block_until_ready(self._params)
            final_telemetry = self.telemetry_snapshot()
        finally:
            # order matters: signal stop (a serializing transport flips
            # to discard mode so producer processes can always flush and
            # exit; the inference service wakes every blocked client
            # with a None reply), join the workers, and only then tear
            # the transport down — a wire closed under a live producer
            # can tear frames
            if self._profile is not None:
                self._profile.stop()
            self.pool.stop()
            if self.service is not None:
                self.service.stop()
            if self._exchange is not None:
                self._exchange.close()
            self.pool.join()
            self.queue.close()
        self._raise_worker_errors()
        return self.metrics, final_telemetry

    # ------------------------------------------------------------------

    def published_host(self) -> PyTree:
        """The latest published params as host numpy leaves — what a
        group worker ships to the parent for checkpointing."""
        params, _version = self.store.pull()
        import jax

        return jax.tree.map(np.asarray, params)

    def opt_state_host(self) -> PyTree:
        """The live optimizer state as host numpy leaves (copies, so a
        checkpoint writer never races the donated working tree)."""
        import jax

        return jax.tree.map(lambda x: np.array(x), self._opt_state)
