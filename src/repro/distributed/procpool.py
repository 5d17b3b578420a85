"""Actor *process* pools: spawn-based workers behind the same interface
as ``ActorPool`` (paper §3's actors on separate interpreters — acting no
longer competes with the learner for the GIL).

Two pools live here. ``ProcessActorPool`` wires its children over
multiprocessing primitives (shm transport + param/control pipes).
``SocketActorPool`` wires them over TCP (``SocketTransport``): children
— or entirely separate machines — dial the learner's listen address,
receive the whole run config in the handshake, and run the *same* loop
bodies; with ``spawn_local=False`` the pool spawns nothing and simply
waits for remote actors to connect.

Each worker process builds its own env batch, RNG stream, and jit cache
from picklable ingredients (env *name*, config dataclasses, seed) — no
live jax object crosses the boundary. Two channels connect it to the
parent:

  params     a duplex pipe to the parent's *param server* thread. The
             child asks "anything newer than version v?"; the server
             answers from ``ParameterStore.pull_serialized`` (encoded
             once per version, shared by all children).
  data       the ``ShmTransport`` wire. The child ships serde-encoded
             trajectory buffers; the parent's drain thread decodes and
             applies the backpressure policy.

Accounting happens entirely parent-side through the transport's
attribution hooks (accepted / rejected / evicted per actor id), so
``stats()`` has the same meaning as the thread pool's — with the caveat
that ``frames`` counts trajectories that *arrived* (in-flight unrolls in
a child are invisible until they land).

Shutdown: set the shared stop event; children exit their loop (their
wire puts and param pulls are timeout/poll-based); join with a deadline;
``terminate()`` stragglers so no orphan can outlive the run.
"""
from __future__ import annotations

import multiprocessing as mp
import threading
import time
from multiprocessing import connection as mp_connection
from typing import List

from repro.distributed.actor_pool import PoolAccounting
from repro.distributed.paramstore import ParameterStore
from repro.distributed.runner import (inference_actor_main,
                                      process_actor_main)
from repro.distributed.serde import TrajectoryItem
from repro.distributed.supervise import (KillSafeEvent, Supervisor,
                                         fold_restart_seed)
from repro.distributed.transport import ShmTransport


class ProcessActorPool(PoolAccounting):
    backend = "process"

    def __init__(self, env_name: str, arch_cfg, icfg, num_envs: int,
                 num_actors: int, store: ParameterStore,
                 transport: ShmTransport, seed: int = 0, service=None,
                 infer_streams: int = 1, slot_base: int = 0):
        """``service`` (an ``InferenceService``) switches the children to
        inference mode: they hold no params and run no policy network —
        observation requests go up the service's process frontend wire,
        action replies come back over per-stream pipes
        (``infer_streams`` pipelined env half-batches per child), and
        the param pipe carries only error reports.

        ``slot_base`` shifts the children onto the global actor slot
        range [slot_base, slot_base + num_actors): each child derives
        its RNG stream (and its core-affinity pin) from the global id,
        so sharding the slots over a learner group changes neither the
        per-actor randomness nor which cores the children land on."""
        if num_actors < 1:
            raise ValueError("num_actors must be >= 1")
        if not isinstance(transport, ShmTransport):
            raise ValueError("ProcessActorPool requires a serializing "
                             "transport (--transport shm)")
        if not isinstance(env_name, str):
            raise ValueError("process actors rebuild the env by name; "
                             "pass an env name, not an Env object")
        self.env_name = env_name
        self.num_envs = num_envs
        self.store = store
        self.queue = transport
        self.seed = seed
        self._ctx = mp.get_context("spawn")
        # kill-safe: SIGKILLed children are this pool's normal case,
        # and a corpse holding mp.Event's lock would deadlock stop()
        self._stop = KillSafeEvent(self._ctx)
        self._procs: List[mp.process.BaseProcess] = []
        self._conns = []                        # parent ends of param pipes
        self._conn_lock = threading.Lock()      # respawns append live
        self.errors: List[str] = []             # child tracebacks
        # supervised respawn (attach_supervisor): a child that dies
        # WITHOUT reporting an error (SIGKILL, OOM) is respawned; a
        # reported traceback is a code bug and still raises
        self._supervisor: "Supervisor | None" = None
        self._live: dict = {}                   # local idx -> live process
        self._respawns: dict = {}               # key -> (idx, decision)
        # ``frames`` counts trajectories that *landed* parent-side: the
        # steady clock starts at the first arrival (post child startup +
        # compile), mirroring the thread pool's convention
        self._init_accounting(num_actors, num_envs * icfg.unroll_length,
                              slot_base)
        self._arch_cfg = arch_cfg
        self._icfg = icfg
        self.service = service
        self.infer_streams = infer_streams
        self._frontend = (service.process_frontend(
            self._ctx, num_actors * infer_streams)
            if service is not None else None)
        transport.on_item = self._note_arrival
        transport.on_reject = self._note_loss
        transport.on_drop = self._note_loss
        self._server = threading.Thread(target=self._serve_params,
                                        name="param-server", daemon=True)

    # ------------------------------------------------------------------
    # accounting (runs on the transport drain / param server threads)

    def _note_arrival(self, item: TrajectoryItem) -> None:
        self._note_accept(item)
        self._note_frames(item.actor_id - self.slot_base)
        if self.service is not None:
            self._note_assembly(item)

    # ------------------------------------------------------------------
    # param server: version-gated pub/sub over pipes

    def _serve_params(self) -> None:
        dead: set = set()
        while True:
            # re-read the conn list each pass: a supervised respawn
            # appends a fresh pipe mid-run and it must be served
            with self._conn_lock:
                conns = [c for c in self._conns if c not in dead]
            if not conns:
                if self._supervisor is None or self._stop.is_set():
                    break       # unsupervised: all children gone = done
                time.sleep(0.05)
                continue        # supervised: a respawn may repopulate
            ready = mp_connection.wait(conns, timeout=0.2)
            for conn in ready:
                try:
                    msg = conn.recv()
                except (EOFError, OSError):
                    dead.add(conn)
                    continue
                if msg[0] == "pull":
                    _, _actor_id, have_version = msg
                    if self._stop.is_set():
                        reply = ("stop",)
                    else:
                        fresh = self.store.pull_serialized(have_version)
                        reply = (("params", fresh[1], fresh[0])
                                 if fresh is not None else ("keep",))
                    try:
                        conn.send(reply)
                    except (OSError, BrokenPipeError):
                        dead.add(conn)
                elif msg[0] == "error":
                    self.errors.append(msg[2])
                    self.queue.close()
            if self._stop.is_set() and not any(
                    p.is_alive() for p in self._procs):
                break

    # ------------------------------------------------------------------

    def attach_supervisor(self, supervisor: Supervisor) -> None:
        """Opt into supervised respawn of silently-dead children (same
        global slot, restart-epoch folded into the seed). Children that
        *report* a traceback still raise — that is a code bug, not a
        fault. Inference-mode children are not respawned (their reply
        pipes are registered with the frontend once, at start)."""
        self._supervisor = supervisor

    def _spawn_child(self, i: int, epoch: int = 0):
        parent_conn, child_conn = self._ctx.Pipe()
        with self._conn_lock:
            self._conns.append(parent_conn)
        seed = fold_restart_seed(self.seed, epoch)
        clients = None
        if self._frontend is not None:
            # frontend client ids stay pool-local (the service is
            # per-learner); the child's actor id is global
            clients = [self._frontend.register(
                i * self.infer_streams + s)
                for s in range(self.infer_streams)]
            target, args = inference_actor_main, (
                self.slot_base + i, self.env_name, self._arch_cfg,
                self._icfg, self.num_envs, seed,
                self.queue.producer(), clients, child_conn,
                self._stop, self.queue.wire_codec)
        else:
            target, args = process_actor_main, (
                self.slot_base + i, self.env_name, self._arch_cfg,
                self._icfg, self.num_envs, seed,
                self.queue.producer(), child_conn, self._stop,
                self.queue.wire_codec)
        p = self._ctx.Process(target=target, args=args,
                              name=f"actor-proc-{i}", daemon=True)
        self._procs.append(p)
        self._live[i] = p
        p.start()
        child_conn.close()              # parent keeps only its end
        if clients is not None:
            for c in clients:
                c.close()               # ditto for reply recv-ends
        return p

    def start(self) -> None:
        for i in range(self.num_actors):
            self._spawn_child(i)
        if self._frontend is not None:
            self._frontend.start()
        self._server.start()

    def stop(self) -> None:
        self._stop.set()
        # keep the wires flowing (discarding) while children wind down,
        # so their queue feeders can always flush and no child ever
        # hangs at exit mid-write into a full pipe
        self.queue.begin_shutdown()
        if self._frontend is not None:
            self._frontend.begin_shutdown()

    def join(self, timeout: float = 30.0) -> None:
        deadline = time.monotonic() + timeout
        for p in self._procs:
            p.join(max(0.1, deadline - time.monotonic()))
        for p in self._procs:
            if p.is_alive():                # no orphans, ever
                p.terminate()
                p.join(timeout=5.0)
        if self._frontend is not None:
            self._frontend.close()          # children are gone: safe
        if self._server.is_alive():
            self._server.join(timeout=5.0)
        for conn in self._conns:
            try:
                conn.close()
            except OSError:
                pass

    def raise_errors(self) -> None:
        if self.errors:
            raise RuntimeError("actor process died:\n" + self.errors[0])
        if self._stop.is_set():
            return
        if self._supervisor is not None and self._frontend is None:
            self._heal()
            return
        # a child that crashed before it could report (import error,
        # OOM kill, ...) must not leave the learner polling forever
        for p in self._procs:
            if p.exitcode is not None and p.exitcode != 0:
                raise RuntimeError(
                    f"actor process {p.name} exited with code "
                    f"{p.exitcode} before reporting an error")

    def _heal(self) -> None:
        """Respawn silently-dead children under the restart policy.
        Non-blocking: called from the learner loop, so backoff waits
        ride the loop. A dead child reported no error (the errors
        branch above raised otherwise) — SIGKILL / preemption / OOM,
        the faults a fleet must absorb."""
        sup = self._supervisor
        for i, p in list(self._live.items()):
            if p.exitcode is None or p.exitcode == 0:
                continue
            del self._live[i]
            key = f"proc-{self.slot_base + i}"
            decision = sup.record_death(key)
            if decision is None:
                raise RuntimeError(
                    f"actor process {p.name} exited with code "
                    f"{p.exitcode}; restart budget exhausted")
            self._respawns[key] = (i, decision)
        now = time.monotonic()
        due = [k for k, (_i, d) in self._respawns.items()
               if d.not_before <= now]
        for key in due:
            i, decision = self._respawns.pop(key)
            self._spawn_child(i, decision.epoch)
            sup.note_restarted(key)


class SocketActorPool(PoolAccounting):
    """Remote actors over TCP behind the pool interface.

    The pool owns no channels of its own — it *configures* the
    ``SocketTransport`` it is given: the CONFIG-handshake payload (env
    name, arch/impala config, seed, mode) so a connecting machine needs
    nothing but the address, the param source
    (``ParameterStore.pull_serialized``, encoded once per version for
    all subscribers), the inference frontend when the run is in
    inference mode, and the per-actor attribution hooks.

    ``spawn_local=True`` (the default, and the benchmark / single-box
    path) spawns ``num_actors`` loopback children running
    ``netserve.remote_actor_child``; ``spawn_local=False`` is the real
    deployment shape — the learner listens, and ``num_actors`` remote
    machines run ``launch.train --connect host:port`` (or
    ``examples/train_remote.py actor``) whenever they come up.
    """

    backend = "remote"

    def __init__(self, env_name: str, arch_cfg, icfg, num_envs: int,
                 num_actors: int, store: ParameterStore,
                 transport, seed: int = 0, service=None,
                 infer_streams: int = 1, spawn_local: bool = True,
                 slot_base: int = 0):
        from repro.distributed import netserve
        from repro.distributed.socket_transport import SocketTransport

        if num_actors < 1:
            raise ValueError("num_actors must be >= 1")
        if not isinstance(transport, SocketTransport):
            raise ValueError("SocketActorPool requires a SocketTransport "
                             "(--transport socket)")
        if not isinstance(env_name, str):
            raise ValueError("remote actors rebuild the env by name; "
                             "pass an env name, not an Env object")
        self.env_name = env_name
        self.num_envs = num_envs
        self.store = store
        self.queue = transport
        self.seed = seed
        self.spawn_local = spawn_local
        self._ctx = mp.get_context("spawn")
        self._stop = KillSafeEvent(self._ctx)   # see ProcessActorPool
        self._procs: List[mp.process.BaseProcess] = []
        self.errors: List[str] = []             # remote tracebacks
        self._supervisor: "Supervisor | None" = None
        self._live: dict = {}                   # local idx -> live process
        self._respawns: dict = {}               # key -> (idx, decision)
        self._init_accounting(num_actors, num_envs * icfg.unroll_length,
                              slot_base)
        self.service = service
        self.infer_streams = infer_streams
        mode = "inference" if service is not None else "unroll"
        cfg = netserve.build_actor_config(
            env_name=env_name, arch_cfg=arch_cfg, icfg=icfg,
            num_envs=num_envs, seed=seed, mode=mode,
            infer_streams=infer_streams)
        transport.max_actors = num_actors
        transport.config_extra = lambda actor_id: cfg
        transport.param_source = store.pull_serialized
        transport.on_item = self._note_arrival
        transport.on_reject = self._note_loss
        transport.on_drop = self._note_loss
        transport.on_error = self.errors.append
        self._frontend = (netserve.SocketInferenceFrontend(
            service, transport, streams=infer_streams)
            if service is not None else None)

    # accounting runs on the transport's connection threads
    def _note_arrival(self, item: TrajectoryItem) -> None:
        self._note_accept(item)
        self._note_frames(item.actor_id - self.slot_base)
        if self.service is not None:
            self._note_assembly(item)

    # ------------------------------------------------------------------

    def attach_supervisor(self, supervisor: Supervisor) -> None:
        """Opt into supervised respawn of locally-spawned children that
        die without reporting an error. The respawned child redials the
        learner; ``_bind``'s reclaim hands it the dead slot (ownership
        transfer bumps the slot's restart epoch, which the CONFIG
        handshake folds into the seed). Truly remote actors are an
        operator's to relaunch — the reaper only frees their lease."""
        self._supervisor = supervisor

    def _spawn_child(self, i: int):
        from repro.distributed.netserve import remote_actor_child
        p = self._ctx.Process(
            target=remote_actor_child,
            args=(tuple(self.queue.address), self._stop),
            name=f"actor-remote-{i}", daemon=True)
        self._procs.append(p)
        self._live[i] = p
        p.start()
        return p

    def start(self) -> None:
        if not self.spawn_local:
            return                      # remote machines dial in
        for i in range(self.num_actors):
            self._spawn_child(i)

    def stop(self) -> None:
        self._stop.set()
        if self._frontend is not None:
            self._frontend.begin_shutdown()
        # flips the transport to discard (data conns keep draining so a
        # child mid-send can always finish its frame) and broadcasts the
        # stop control frame to every connected actor
        self.queue.begin_shutdown()

    def join(self, timeout: float = 30.0) -> None:
        deadline = time.monotonic() + timeout
        for p in self._procs:
            p.join(max(0.1, deadline - time.monotonic()))
        for p in self._procs:
            if p.is_alive():                # no orphans, ever
                p.terminate()
                p.join(timeout=5.0)

    def raise_errors(self) -> None:
        if self.errors:
            raise RuntimeError("remote actor died:\n" + self.errors[0])
        if self._stop.is_set():
            return
        if self._supervisor is not None and self.spawn_local:
            self._heal()
            return
        for p in self._procs:
            if p.exitcode is not None and p.exitcode != 0:
                raise RuntimeError(
                    f"actor process {p.name} exited with code "
                    f"{p.exitcode} before reporting an error")

    def _heal(self) -> None:
        """Mirror of ``ProcessActorPool._heal`` for loopback socket
        children: respawn a silently-dead child under the restart
        policy; the redial reclaims its slot via the nonce lease."""
        sup = self._supervisor
        for i, p in list(self._live.items()):
            if p.exitcode is None or p.exitcode == 0:
                continue
            del self._live[i]
            key = f"remote-{self.slot_base + i}"
            decision = sup.record_death(key)
            if decision is None:
                raise RuntimeError(
                    f"actor process {p.name} exited with code "
                    f"{p.exitcode}; restart budget exhausted")
            self._respawns[key] = (i, decision)
        now = time.monotonic()
        due = [k for k, (_i, d) in self._respawns.items()
               if d.not_before <= now]
        for key in due:
            i, decision = self._respawns.pop(key)
            self._spawn_child(i)
            sup.note_restarted(key)
