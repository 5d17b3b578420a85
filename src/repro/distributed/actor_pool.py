"""Actor thread pool: N workers, each owning its own env batch, RNG
stream, and jitted unroll (paper §3's distributed actors, in-process).

Concurrency model: each worker's loop is (pull params) -> (jitted unroll)
-> (transport put). The unroll dispatch drops the GIL while XLA executes,
so workers genuinely overlap with each other and with the learner's
train_step on a multicore host — this is real decoupling, not simulated
lag. Each worker builds its own ``build_actor`` closure, so its jit
cache, env batch, and RNG stream are private; the loop body itself lives
in ``runner.run_actor_loop``, shared verbatim with the process backend.

The pool is written against the ``Transport`` interface. With the
in-process transport, items are live pytrees and put() outcomes carry
the accounting; with a serializing transport (``ShmTransport``), policy
decisions happen at the drain side, so acceptance/rejection is counted
through the transport's attribution hooks instead. Either way,
``stats()["rejected"]`` charges every lost trajectory — drop_newest
rejections *and* drop_oldest evictions — back to the actor that made it.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as np

from repro.core import actor as actor_lib
from repro.distributed.paramstore import ParameterStore
from repro.distributed.runner import (run_actor_loop,
                                      run_inference_driver_loop)
from repro.distributed.serde import TrajectoryItem  # noqa: F401 (re-export)
from repro.distributed.supervise import Supervisor, fold_restart_seed
from repro.distributed.transport import Transport


class PoolAccounting:
    """The per-actor ledger both worker pools share: frames / accepted
    trajectories / losses, the steady-state fps clock, and the stats
    dict the runtime's telemetry embeds. Loss attribution can arrive
    from several threads at once (a producer counting its own rejection,
    the queue's eviction callback, a transport drain thread), so the
    ``rejected`` ledger is written under a lock.

    ``slot_base`` is the pool's first *global* actor slot id: a learner
    group shards the run's slots over its learners, and each pool owns
    the contiguous range [slot_base, slot_base + num_actors). Items
    carry global ids (that is what keeps an actor's RNG/env-seed stream
    independent of the sharding); the ledgers here are indexed locally,
    so attribution subtracts the base."""

    backend = "?"

    def _init_accounting(self, num_actors: int, frames_per_traj: int,
                         slot_base: int = 0) -> None:
        self.num_actors = num_actors
        self.slot_base = slot_base
        self.frames = [0] * num_actors          # env frames produced
        self.trajectories = [0] * num_actors    # accepted into the queue
        self.rejected = [0] * num_actors        # lost (rejected/evicted)
        # inference trajectories by where assemble_inference_traj
        # stacked their frames
        self.assembled = {"device": 0, "host": 0}
        self._acct_lock = threading.Lock()
        self._steady_t0: Optional[float] = None
        self._steady_frames0 = 0
        self._frames_per_traj = frames_per_traj

    def _note_accept(self, item: TrajectoryItem) -> None:
        self.trajectories[item.actor_id - self.slot_base] += 1

    def _note_loss(self, item: TrajectoryItem) -> None:
        with self._acct_lock:
            self.rejected[item.actor_id - self.slot_base] += 1

    def _note_assembly(self, item: TrajectoryItem) -> None:
        """Count one inference-mode trajectory by where its frames were
        stacked: a device array only when the acting loop kept the env
        outputs on the device. Serialized children convert every step's
        outputs to numpy, so what they send was stacked on the host."""
        where = ("host" if isinstance(item.data["obs_image"], np.ndarray)
                 else "device")
        with self._acct_lock:
            self.assembled[where] += 1

    def _note_frames(self, idx: int) -> None:
        self.frames[idx] += self._frames_per_traj
        if self._steady_t0 is None:
            # fps clock starts at the first finished trajectory
            # (post-compile), mirroring the learner's steady-state
            # window; benign race — near-identical timestamps
            self._steady_t0 = time.monotonic()
            self._steady_frames0 = sum(self.frames)

    def stats(self) -> Dict[str, float]:
        total_frames = sum(self.frames)
        fps = 0.0
        if self._steady_t0 is not None:
            dt = time.monotonic() - self._steady_t0
            if dt > 0:
                fps = (total_frames - self._steady_frames0) / dt
        return {
            "num_actors": self.num_actors,
            "slot_base": self.slot_base,
            "backend": self.backend,
            "frames": total_frames,
            "trajectories": sum(self.trajectories),
            "rejected": sum(self.rejected),
            "rejected_per_actor": list(self.rejected),
            "actor_fps": fps,
            "frames_per_actor": list(self.frames),
            "assembled_on_device": self.assembled["device"],
            "assembled_on_host": self.assembled["host"],
        }


class ActorPool(PoolAccounting):
    backend = "thread"

    def __init__(self, env, arch_cfg, icfg, num_envs: int, num_actors: int,
                 store: ParameterStore, queue: Transport, seed: int = 0,
                 service=None, slot_base: int = 0):
        """``service`` (an ``InferenceService``) switches the pool to
        inference mode: no per-actor policy or params — one *driver*
        thread multiplexes all logical actors' host-side env stepping
        against the shared batched forward (paper §3.1's dynamic
        batching); see ``_run_driver``.

        ``slot_base`` shifts this pool's actors onto the global slot
        range [slot_base, slot_base + num_actors) — workers derive
        their RNG stream from the *global* id, so a sharded learner
        group acts out exactly the per-actor randomness one learner
        owning all the slots would."""
        if num_actors < 1:
            raise ValueError("num_actors must be >= 1")
        self.env = env
        self.num_envs = num_envs
        self.store = store
        self.queue = queue
        self.seed = seed
        self.service = service
        self._arch_cfg = arch_cfg
        self._icfg = icfg
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._builders = []
        if service is None:
            for i in range(num_actors):
                # per-actor closure => per-actor jit cache and env batch
                self._builders.append(
                    actor_lib.build_actor(env, arch_cfg, icfg, num_envs))
        self.errors: List[BaseException] = []
        # supervised respawn (attach_supervisor): a dead worker thread
        # waits here for a restart grant instead of failing the run
        self._supervisor: Optional[Supervisor] = None
        self._dead: List[tuple] = []            # (idx, exc)
        self._respawns: Dict[str, tuple] = {}   # key -> (idx, decision)
        self._init_accounting(num_actors, num_envs * icfg.unroll_length,
                              slot_base)
        # attribution hooks: evictions always come back through the
        # transport; accept/reject only when the policy runs drain-side
        self._counts_at_drain = not queue.rejects_at_put
        if hasattr(queue, "on_drop"):
            queue.on_drop = self._note_loss
        if self._counts_at_drain:
            queue.on_item = self._note_accept
            queue.on_reject = self._note_loss

    # ------------------------------------------------------------------

    def _emit(self, idx: int, item: TrajectoryItem) -> bool:
        """Transport put with the policy-aware retry loop. True = keep
        producing; False = shut down."""
        attempt = 0
        while not self._stop.is_set():
            if self.queue.put(item, timeout=0.1, count_stall=attempt == 0):
                if not self._counts_at_drain:
                    self.trajectories[idx] += 1
                return True
            if self.queue.closed:
                return False                    # shutting down
            if self.queue.rejects_at_put and \
                    self.queue.policy == "drop_newest":
                with self._acct_lock:
                    self.rejected[idx] += 1
                return True                     # genuine drop, move on
            # block policy timed out (or wire momentarily full):
            # re-check stop flag and retry
            attempt += 1
        return False

    def _emit_assembled(self, actor_id: int, item: TrajectoryItem) -> bool:
        self._note_assembly(item)
        return self._emit(actor_id - self.slot_base, item)

    def _run(self, idx: int, epoch: int = 0) -> None:
        try:
            run_actor_loop(
                actor_id=self.slot_base + idx,
                builder=self._builders[idx],
                seed=fold_restart_seed(self.seed, epoch),
                pull_params=self.store.pull,
                emit=lambda item: self._emit(idx, item),
                should_stop=self._stop.is_set,
                on_unroll=lambda: self._note_frames(idx))
        except BaseException as e:  # surface in the learner thread
            self._note_death(idx, e)

    def _note_death(self, idx: int, exc: BaseException) -> None:
        """Unsupervised, a worker death fails the run (close the queue
        so the learner wakes and ``raise_errors`` fires). Supervised,
        it is parked for ``raise_errors`` to respawn — the queue stays
        open, the remaining workers keep producing."""
        if self._supervisor is not None and not self._stop.is_set():
            with self._acct_lock:
                self._dead.append((idx, exc))
        else:
            self.errors.append(exc)
            self.queue.close()

    def _run_driver(self, epoch: int = 0) -> None:
        """Inference mode: ONE thread multiplexes every logical actor —
        per-actor threads would only add GIL-serialized Event wake-ups
        to a loop whose heavy lifting (the batched policy forward)
        already happens in the shared service. Each logical actor keeps
        its thread-layout identity: own env batch, own
        fold_in(seed, actor_id) RNG stream, own trajectory stream."""
        try:
            run_inference_driver_loop(
                actor_ids=list(range(self.slot_base,
                                     self.slot_base + self.num_actors)),
                env=self.env, arch_cfg=self._arch_cfg, icfg=self._icfg,
                num_envs=self.num_envs,
                seed=fold_restart_seed(self.seed, epoch),
                service=self.service,
                emit=self._emit_assembled,
                should_stop=self._stop.is_set,
                on_unroll=lambda aid: self._note_frames(
                    aid - self.slot_base))
        except BaseException as e:  # surface in the learner thread
            self._note_death(-1, e)

    # ------------------------------------------------------------------

    def attach_supervisor(self, supervisor: Supervisor) -> None:
        """Opt into supervised respawn: a worker thread that dies is
        respawned (same global slot, restart-epoch folded into its
        seed) on the next ``raise_errors`` call instead of failing the
        run — until the restart policy is exhausted, at which point
        ``raise_errors`` raises exactly as the unsupervised pool does."""
        self._supervisor = supervisor

    def _spawn(self, idx: int, epoch: int = 0) -> None:
        if idx < 0:
            t = threading.Thread(target=self._run_driver, args=(epoch,),
                                 name="inference-driver", daemon=True)
        else:
            t = threading.Thread(target=self._run, args=(idx, epoch),
                                 name=f"actor-{idx}", daemon=True)
        self._threads.append(t)
        t.start()

    def start(self) -> None:
        if self.service is not None:
            self._spawn(-1)
            return
        for i in range(self.num_actors):
            self._spawn(i)

    def stop(self) -> None:
        self._stop.set()
        if hasattr(self.queue, "begin_shutdown"):
            self.queue.begin_shutdown()     # serializing transport: keep
            # the wire draining (discard) while workers wind down

    def join(self, timeout: float = 30.0) -> None:
        deadline = time.monotonic() + timeout
        for t in self._threads:
            t.join(max(0.0, deadline - time.monotonic()))

    def raise_errors(self) -> None:
        if self._supervisor is not None:
            self._heal()
        if self.errors:
            raise RuntimeError("actor thread died") from self.errors[0]

    def _heal(self) -> None:
        """Ask the supervisor for restart grants for parked deaths and
        launch every respawn whose backoff has elapsed. Non-blocking:
        called from the learner loop every iteration, so backoff waits
        ride the loop instead of stalling training."""
        sup = self._supervisor
        with self._acct_lock:
            dead, self._dead = self._dead, []
        for idx, exc in dead:
            key = (f"actor-{self.slot_base + idx}" if idx >= 0
                   else f"driver-{self.slot_base}")
            decision = sup.record_death(key)
            if decision is None:    # budget exhausted: fail loudly
                self.errors.append(exc)
                self.queue.close()
                continue
            self._respawns[key] = (idx, decision)
        now = time.monotonic()
        due = [k for k, (_i, d) in self._respawns.items()
               if d.not_before <= now]
        for key in due:
            idx, decision = self._respawns.pop(key)
            self._spawn(idx, decision.epoch)
            sup.note_restarted(key)
