"""The actor loop bodies, extracted so one implementation drives both
thread workers (``ActorPool``) and process workers (``ProcessActorPool``).

Two actor modes share this module:

``run_actor_loop`` is the paper's self-contained actor (§3): pull
current params, run one jitted n-step unroll against a private env
batch, stamp the trajectory with the parameter version it was acted
with, hand it to the transport. What varies between backends is only
*how* params arrive and *where* the trajectory goes:

  threads     pull = ParameterStore.pull (shared memory, zero-copy);
              emit = Transport.put of the live pytree.
  processes   pull = request/reply over a pipe against the parent's
              param server (serde-encoded, cached per version);
              emit = serde-encode + wire put of the byte buffer.

``run_inference_actor_loop`` is the dynamic-batching variant (§3.1):
the actor holds **no parameters at all** — it steps its env batch on
the host, submits each per-step observation batch to the shared
``InferenceService`` (which batches across actors on the learner's
device), and assembles the returned actions/log-probs/recurrent states
into the same trajectory layout the unroll produces. Thread clients
talk to the service in-process; process clients ship serde frames over
a wire.

Each worker derives its RNG stream from ``fold_in(seed, actor_id)`` —
identical across backends, so a thread-backend run and a process-backend
run with the same seed act out the same per-actor randomness. The
``actor_id`` here is always the *global* slot id: a learner group
shards the run's slots over its learners (pool ``slot_base``), and
because the loop bodies fold in the global id, actor g's randomness —
and therefore its env-seed stream — is byte-identical however the
slots are sharded.
"""
from __future__ import annotations

import functools
import time
import traceback
from typing import Any, Callable, List, Optional, Tuple

from repro.obs.trace import span

PyTree = Any


def run_actor_loop(
    *,
    actor_id: int,
    builder: Tuple[Callable, Callable],
    seed: int,
    pull_params: Callable[[], Optional[Tuple[PyTree, int]]],
    emit: Callable[[Any], bool],
    should_stop: Callable[[], bool],
    on_unroll: Optional[Callable[[], None]] = None,
    trace_every: Optional[int] = None,
) -> None:
    """Drive one actor until ``should_stop`` or a channel closes.

    ``pull_params`` returns (params, version) or None on shutdown.
    ``emit`` owns backpressure/retry/accounting and returns False only
    when the worker should exit. ``on_unroll`` fires after each finished
    (host-materialized) unroll — the hook for frame counters.

    ``trace_every`` > 0 samples every Nth unroll for the flight
    recorder: the item carries a stamp dict (``u0``/``u1`` here; the
    serde/transport layers add theirs downstream). Defaults to the
    ``REPRO_TRACE_EVERY`` env var so spawned actor children inherit the
    sampling rate without any pipe-protocol change; 0 disables.
    """
    import os

    import jax  # deferred: keeps this module importable without jax

    from repro.distributed.serde import TrajectoryItem

    if trace_every is None:
        try:
            trace_every = int(os.environ.get("REPRO_TRACE_EVERY", "0"))
        except ValueError:
            trace_every = 0

    init_fn, unroll = builder
    base = jax.random.fold_in(jax.random.key(seed), actor_id)
    carry = init_fn(jax.random.fold_in(base, 1))
    idx = 0
    while not should_stop():
        pulled = pull_params()
        if pulled is None:
            break
        params, version = pulled
        idx += 1
        sampled = bool(trace_every) and idx % trace_every == 0
        u0 = time.monotonic() if sampled else 0.0
        with span("acting.unroll"):
            carry, traj = unroll(params, carry)
            # materialise before enqueue: backpressure must reflect
            # finished work, not a ballooning async dispatch queue
            traj = jax.block_until_ready(traj)
        if on_unroll is not None:
            on_unroll()
        now = time.monotonic()
        tr = {"u0": u0, "u1": now} if sampled else None
        item = TrajectoryItem(traj, version, actor_id, now, tr)
        with span("acting.emit"):
            emitted = emit(item)
        if not emitted:
            break


_TRAJ_KEYS = ("actions", "rewards", "discounts", "behaviour_logprob",
              "done", "obs_image", "last_action", "last_reward", "done_in",
              "lstm_state")


def assemble_inference_traj(steps: List[dict], boot: dict,
                            init_lstm: Tuple[Any, Any], icfg) -> dict:
    """Package one unroll's per-step records into the learner's
    trajectory layout — the exact shape ``core.actor``'s ``_finalize``
    produces (batch-major arrays, bootstrap step appended to the
    observation-side keys, the unroll's *initial* LSTM state attached).
    Shared by the thread-mode driver and the process actor loop so the
    layout cannot drift between backends.

    Where the env-step outputs (frames, rewards, dones) are device
    arrays, as in the thread driver, one jitted program stacks every
    column there (``_device_stacker``), the reply-side ones (actions,
    log-probs, last actions, the initial LSTM state) too, so the
    trajectory stays on the learner's device from env step to train
    step: on a TPU, forcing them to the host costs one transfer per
    leaf and step (five a step, about 500 per trajectory at unroll
    100), and the learner would copy them straight back. Where they are numpy
    (serialized actors convert each step's outputs, since their
    trajectories cross a wire), every leaf is stacked on the host. The
    two paths give the same keys, dtypes and values.

    ``steps[t]`` keys: obs_image/last_action/last_reward/done_in (the
    step's *inputs*), action/reward/done/behaviour_logprob (its
    outputs). ``boot``: the post-final-step obs_image/last_action/
    last_reward/done. The acting loops carry each step's action, reward
    and done into the next step's last_action/last_reward/done_in, and
    the device path relies on that: it takes those three columns from
    the action, reward and done stacks, shifted by one step behind the
    unroll's carried-in input."""
    import jax
    import numpy as np

    def col(k):
        return np.stack([np.asarray(s[k]) for s in steps], axis=1)

    def col_boot(k, final):
        return np.concatenate([col(k), np.asarray(final)[:, None]],
                              axis=1)

    if isinstance(steps[0]["obs_image"], jax.Array):
        cols = _device_stacker(icfg.discount)(
            [s["obs_image"] for s in steps] + [boot["obs_image"]],
            [s["reward"] for s in steps], [s["done"] for s in steps],
            [s["action"] for s in steps],
            [s["behaviour_logprob"] for s in steps],
            {k: steps[0][k] for k in ("last_action", "last_reward",
                                      "done_in")},
            tuple(init_lstm))
    else:
        step_dones = col("done")
        cols = {
            "actions": col("action"),
            "rewards": col("reward"),
            "discounts": (icfg.discount *
                          (1.0 - step_dones.astype(np.float32))
                          ).astype(np.float32),
            "behaviour_logprob": col("behaviour_logprob"),
            "done": step_dones,
            "obs_image": col_boot("obs_image", boot["obs_image"]),
            "last_action": col_boot("last_action", boot["last_action"]),
            "last_reward": col_boot("last_reward", boot["last_reward"]),
            "done_in": col_boot("done_in", boot["done"]),
            "lstm_state": (np.asarray(init_lstm[0]),
                           np.asarray(init_lstm[1])),
        }
    # the layout's key order (a jitted program returns its dict sorted)
    return {k: cols[k] for k in _TRAJ_KEYS}


@functools.lru_cache(maxsize=None)
def _device_stacker(discount: float):
    """The device half of ``assemble_inference_traj``: one program per
    (envs, unroll) shape stacks an unroll's frames (bootstrap frame
    last), rewards, dones, actions and log-probs batch-major, derives
    the discounts and the shifted last_action/last_reward/done_in
    columns from the same stacks, and passes the initial LSTM state
    through."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def stack(frames, rewards, dones, actions, logprobs, first, lstm):
        rewards = jnp.stack(rewards, axis=1)
        dones = jnp.stack(dones, axis=1)
        actions = jnp.stack(actions, axis=1)

        def shifted(first, col):
            return jnp.concatenate([first[:, None], col], axis=1)

        return {
            "actions": actions,
            "rewards": rewards,
            "discounts": (discount * (1.0 - dones.astype(jnp.float32))
                          ).astype(jnp.float32),
            "behaviour_logprob": jnp.stack(logprobs, axis=1),
            "done": dones,
            "obs_image": jnp.stack(frames, axis=1),
            "last_action": shifted(first["last_action"], actions),
            "last_reward": shifted(first["last_reward"], rewards),
            "done_in": shifted(first["done_in"], dones),
            "lstm_state": lstm,
        }

    return stack


class _ActingState:
    """Per logical-actor (or per pipeline-stream) carry for the
    inference acting loops — everything the threaded layout would keep
    on an actor thread's stack."""

    __slots__ = ("uid", "client", "state", "obs_image", "last_action",
                 "last_reward", "done", "h", "c", "key", "ukey", "t",
                 "steps", "version", "handle")


def _make_inference_env_fns(env, n: int):
    """The two jitted env drivers every inference acting loop shares."""
    import jax

    @jax.jit
    def reset_batch(key):
        keys = jax.random.split(key, n)
        state = jax.vmap(env.reset)(keys)
        return state, jax.vmap(env.observe)(state)

    @jax.jit
    def step_batch(state, action, key, t):
        # fold the step index in here: deriving per-step keys outside
        # would cost one extra device op on every step's critical path.
        # The index comes back incremented, so it stays on the device
        # from step to step instead of crossing from the host each time
        keys = jax.random.split(jax.random.fold_in(key, t), n)
        state, ts = jax.vmap(env.step)(state, action, keys)
        # only what the service request / trajectory needs: XLA dead-
        # code-eliminates the rest of the TimeStep (e.g. obs_token)
        return state, t + 1, (ts.obs_image, ts.reward, ts.done)

    return reset_batch, step_batch


def _init_acting_state(uid, base_key, reset_batch, arch_cfg, n: int,
                       conv, client=None) -> _ActingState:
    """``conv`` places the carried leaves: numpy for the serialized
    loop, identity for the thread driver, whose every request leaf is
    then a device array from the first step on (so the service answers
    it on the device)."""
    import jax
    import jax.numpy as jnp

    from repro.models import lstm as lstm_lib

    st = _ActingState()
    st.uid = uid
    st.client = client
    st.state, ts = reset_batch(jax.random.fold_in(base_key, 1))
    st.obs_image = conv(ts.obs_image)
    st.last_action = conv(jnp.zeros((n,), jnp.int32))
    st.last_reward = conv(jnp.zeros((n,), jnp.float32))
    st.done = conv(jnp.zeros((n,), bool))
    st.h, st.c = (conv(x) for x in
                  lstm_lib.lstm_zero_state(n, arch_cfg.lstm_width))
    st.key = jax.random.fold_in(base_key, 2)
    return st


def _acting_request(st: _ActingState) -> dict:
    return {"obs_image": st.obs_image, "last_action": st.last_action,
            "last_reward": st.last_reward, "done": st.done,
            "lstm_h": st.h, "lstm_c": st.c}


def _acting_boot(st: _ActingState) -> dict:
    return {"obs_image": st.obs_image, "last_action": st.last_action,
            "last_reward": st.last_reward, "done": st.done}


def _start_unroll(st: _ActingState, unroll_idx: int) -> None:
    """Reset the per-unroll carry: the unroll's key, its step index
    (a device zero, the type ``step_batch`` returns it as, so both hit
    one compiled program) and its records."""
    import jax
    import jax.numpy as jnp

    st.steps = []
    st.version = None
    st.ukey = jax.random.fold_in(st.key, unroll_idx)
    st.t = jnp.zeros((), jnp.int32)


def _record_reply_and_step(st: _ActingState, reply, step_batch,
                           conv) -> None:
    """The shared per-step bookkeeping: stamp the first-step version,
    advance the recurrent state from the reply, step the envs, record
    the step, carry forward."""
    if st.version is None:
        st.version = reply.param_version
    action = conv(reply.action)
    st.h = conv(reply.lstm_state[0])
    st.c = conv(reply.lstm_state[1])
    st.state, st.t, (obs_image, reward, step_done) = step_batch(
        st.state, action, st.ukey, st.t)
    st.steps.append({
        "obs_image": st.obs_image, "last_action": st.last_action,
        "last_reward": st.last_reward, "done_in": st.done,
        "action": action, "reward": conv(reward),
        "done": conv(step_done),
        "behaviour_logprob": conv(reply.logprob)})
    st.obs_image = conv(obs_image)
    st.last_action = action
    st.last_reward = st.steps[-1]["reward"]
    st.done = st.steps[-1]["done"]


def run_inference_actor_loop(
    *,
    actor_id: int,
    env,
    arch_cfg,
    icfg,
    num_envs: int,
    seed: int,
    clients: List[Any],
    emit: Callable[[Any], bool],
    should_stop: Callable[[], bool],
    on_unroll: Optional[Callable[[], None]] = None,
    trace_every: Optional[int] = None,
) -> None:
    """Drive one *inference-mode* actor: host-side env stepping against
    the shared batched-inference service.

    ``clients`` is one service client per **pipeline stream**: the env
    batch is split evenly across them, and the streams are software-
    pipelined — while one stream's inference request is in flight (in a
    flush on the learner's device), the actor env-steps the other
    stream. With a single client the loop degenerates to the plain
    submit/step alternation. Each client must expose
    ``submit_async(request) -> handle | None`` and
    ``wait(handle) -> InferenceReply | None`` (None = service shut
    down) plus ``pause``/``resume``.

    The caller's ``emit`` should pause/resume the clients around *long*
    blocks (transport backpressure): the service stops counting paused
    clients towards its all-clients-ready flush rule, so a
    learner-throttled actor never holds the others' batches hostage to
    the flush deadline. Short gaps (trajectory assembly, numpy stacks
    of the unroll's steps here) deliberately do NOT pause: fracturing
    the bucket costs more than the others waiting out a brief
    straggler.

    The trajectory emitted recombines the streams along the batch axis
    and is bit-compatible with the unroll actor's layout
    (``assemble_inference_traj``). The item is stamped with the oldest
    param version of the unroll's first step across streams, so
    measured lag stays conservative. Per-step state is materialized
    numpy — the requests cross a serde wire anyway.

    ``trace_every`` samples every Nth unroll for the flight recorder,
    exactly like the unroll actor: the ``u0``/``u1`` stamps bracket the
    whole acting round (env steps + inference round-trips), so the
    7-span lifecycle covers inference-mode items too. Defaults to the
    ``REPRO_TRACE_EVERY`` env var; 0 disables.
    """
    import os

    import jax
    import numpy as np

    from repro.distributed.serde import TrajectoryItem

    if trace_every is None:
        try:
            trace_every = int(os.environ.get("REPRO_TRACE_EVERY", "0"))
        except ValueError:
            trace_every = 0

    t_len = icfg.unroll_length
    n_streams = len(clients)
    if num_envs % n_streams:
        raise ValueError(f"num_envs={num_envs} must divide evenly over "
                         f"{n_streams} pipeline streams")
    n_sub = num_envs // n_streams
    base = jax.random.fold_in(jax.random.key(seed), actor_id)
    conv = np.asarray
    reset_batch, step_batch = _make_inference_env_fns(env, n_sub)

    streams = [
        _init_acting_state(s, jax.random.fold_in(base, s), reset_batch,
                           arch_cfg, n_sub, conv, client=client)
        for s, client in enumerate(clients)]

    unroll_idx = 0
    while not should_stop():
        unroll_idx += 1
        sampled = bool(trace_every) and unroll_idx % trace_every == 0
        u0 = time.monotonic() if sampled else 0.0
        init_lstm = [(st.h, st.c) for st in streams]
        for st in streams:
            _start_unroll(st, unroll_idx)
            if n_streams > 1:
                st.handle = st.client.submit_async(_acting_request(st))
        for t in range(t_len):
            for st in streams:
                if n_streams > 1:
                    # while this wait blocks, the other streams'
                    # requests are pending service-side and our env
                    # step below overlaps their flush
                    reply = st.client.wait(st.handle)
                else:
                    # single stream: the blocking path keeps
                    # leader-executed flushes (no service-thread wake
                    # on the critical path)
                    reply = st.client.infer(_acting_request(st))
                if reply is None:
                    return              # service shut down mid-unroll
                _record_reply_and_step(st, reply, step_batch, conv)
                if n_streams > 1 and t + 1 < t_len:
                    st.handle = st.client.submit_async(_acting_request(st))

        trajs = [assemble_inference_traj(st.steps, _acting_boot(st),
                                         init_lstm[s], icfg)
                 for s, st in enumerate(streams)]
        traj = (trajs[0] if n_streams == 1 else
                jax.tree.map(lambda *xs: np.concatenate(xs, axis=0),
                             *trajs))
        version = min(st.version for st in streams)
        if on_unroll is not None:
            on_unroll()
        now = time.monotonic()
        tr = {"u0": u0, "u1": now} if sampled else None
        if not emit(TrajectoryItem(traj, version, actor_id, now, tr)):
            break


def run_inference_driver_loop(
    *,
    actor_ids: List[int],
    env,
    arch_cfg,
    icfg,
    num_envs: int,
    seed: int,
    service,
    emit: Callable[[int, Any], bool],
    should_stop: Callable[[], bool],
    on_unroll: Optional[Callable[[int], None]] = None,
    trace_every: Optional[int] = None,
) -> None:
    """Drive ALL thread-mode inference actors from one thread.

    Under the GIL, per-actor threads buy an inference-mode actor
    nothing: the service does the policy compute, env-step dispatches
    are brief, and what remains is pure glue — which N threads only
    serialize anyway, paying an Event wake-up per actor per step on the
    critical path. This driver multiplexes the logical actors instead:
    submit every actor's per-step request, execute the flush inline
    (``service.drive_flushes``), dispatch every env step (its results
    stay on the device, where the next flush and the unroll assembly
    consume them), repeat. A full acting cycle has zero cross-thread
    handoffs.

    Each logical actor keeps exactly the identity it has under the
    per-thread layout: its own env batch, its own
    ``fold_in(seed, actor_id)`` RNG stream, its own trajectory stream
    stamped with its ``actor_id``. Emits block on transport
    backpressure, which stalls all acting — the same throttling the
    thread-per-actor layout converges to, reached sooner.

    ``trace_every`` samples every Nth unroll (per logical actor) for
    the flight recorder, mirroring the other loop bodies.
    """
    import os

    import jax

    from repro.distributed.serde import TrajectoryItem

    if trace_every is None:
        try:
            trace_every = int(os.environ.get("REPRO_TRACE_EVERY", "0"))
        except ValueError:
            trace_every = 0

    t_len = icfg.unroll_length
    reset_batch, step_batch = _make_inference_env_fns(env, num_envs)
    # identity conv: env-step outputs and replies stay device values —
    # the next flush concatenates them on the device, the next env step
    # takes the reply's actions there, and the unroll assembly stacks
    # them there. No acting step copies to the host or waits for one.
    conv = (lambda x: x)

    actors = [
        _init_acting_state(
            aid, jax.random.fold_in(jax.random.key(seed), aid),
            reset_batch, arch_cfg, num_envs, conv)
        for aid in actor_ids]

    unroll_idx = 0
    while not should_stop():
        unroll_idx += 1
        sampled = bool(trace_every) and unroll_idx % trace_every == 0
        u0 = time.monotonic() if sampled else 0.0
        init_lstm = {a.uid: (a.h, a.c) for a in actors}
        for a in actors:
            _start_unroll(a, unroll_idx)
        for _ in range(t_len):
            with span("acting.step"):
                for a in actors:
                    a.handle = service.submit_async(_acting_request(a))
                    if a.handle is None:
                        return              # service shut down
                service.drive_flushes()
                with span("acting.env_step"):
                    for a in actors:
                        if not a.handle.event.is_set():  # frontend raced
                            reply = service.wait(a.handle)
                        else:
                            reply = a.handle.slot[0]
                        if reply is None:
                            return
                        _record_reply_and_step(a, reply, step_batch, conv)

        for a in actors:
            # the env-step leaves recorded above are device arrays:
            # assemble_inference_traj stacks them in one device program,
            # and the trajectory reaches the learner without a host trip
            with span("acting.assemble"):
                traj = assemble_inference_traj(a.steps, _acting_boot(a),
                                               init_lstm[a.uid], icfg)
            if on_unroll is not None:
                on_unroll(a.uid)
            now = time.monotonic()
            tr = {"u0": u0, "u1": now} if sampled else None
            with span("acting.emit"):
                emitted = emit(a.uid, TrajectoryItem(traj, a.version,
                                                     a.uid, now, tr))
            if not emitted:
                return


# ---------------------------------------------------------------------------
# serialized-actor scaffolding, shared by the pipe (process) and socket
# (remote) backends: the loop bodies above never see the wire — what
# varies is only how params arrive (``pull_msg``) and where encoded
# trajectory buffers go (``send_buf``)


def run_serialized_unroll_actor(*, actor_id: int, env_name: str,
                                arch_cfg, icfg, num_envs: int,
                                seed: int,
                                send_buf: Callable[[bytes], bool],
                                pull_msg: Callable[[int],
                                                   Optional[Tuple]],
                                stop,
                                wire_codec: str = "none") -> None:
    """One unroll-mode actor on the far side of a serialized boundary.

    ``pull_msg(have_version)`` returns ``("params", version, buf)``,
    ``("keep",)``, ``("stop",)`` or None — a pipe wrapper or a socket
    pull; raising any channel error also means stop. ``send_buf(buf)``
    blocks until the encoded trajectory is accepted by the wire (its
    retry/backpressure/reconnect discipline lives with the channel) and
    returns False only when shutting down. ``stop`` is any Event-alike
    with ``is_set``/``wait``.

    The unroll stays on the critical path alone: a *subscriber* thread
    refreshes params in the background (the loop never waits on the
    channel once the first version has landed), and a *sender* thread
    owns encode + send behind a depth-1 buffer — enough to overlap the
    send with the next unroll, shallow enough that wire backpressure
    still stalls the actor within two trajectories."""
    import queue as stdlib_queue
    import threading

    import jax
    import numpy as np

    from repro.core import actor as actor_lib
    from repro.data.envs import make_env
    from repro.distributed import serde

    env = make_env(env_name)
    builder = actor_lib.build_actor(env, arch_cfg, icfg, num_envs)
    cache = {"params": None, "version": -1, "dead": False}
    cache_lock = threading.Lock()
    fresh = threading.Event()

    def subscribe():
        # version-gated pub/sub: ask for anything newer than we hold
        # (a "keep" reply costs one tiny message), at a bounded rate —
        # the throttle caps both server traffic and this child's
        # decode+upload work; params are at most ``interval`` stale,
        # which is exactly the off-policy gap V-trace corrects
        interval = 0.1
        # steady state decodes into one reused host mirror instead
        # of allocating a fresh params-sized tree per pull; the
        # first pull — or a structure change — takes the allocating
        # path. The device upload MUST be jnp.array (guaranteed
        # copy): jnp.asarray zero-copy *aliases* 64-byte-aligned
        # host buffers on the CPU backend (measured), and an
        # aliased param leaf would be torn by the next publish's
        # decode while the unroll reads it
        mirror = None
        while not stop.is_set():
            try:
                msg = pull_msg(cache["version"])
            except (EOFError, OSError, BrokenPipeError, ValueError):
                # includes the channel closing under us during shutdown
                break
            if msg is None or msg[0] == "stop":
                break
            if msg[0] == "params":
                _, version, buf = msg
                # a retried pull can deliver a stale queued reply:
                # installing an older version than we hold would step
                # the behaviour policy backwards
                if version > cache["version"]:
                    if mirror is not None:
                        try:
                            serde.decode_tree_into(buf, mirror)
                        except serde.SerdeError:
                            mirror = None
                    if mirror is None:
                        mirror, _ = serde.decode_tree(buf, copy=True)
                    params = jax.tree.map(jax.numpy.array, mirror)
                    with cache_lock:
                        cache["params"] = params
                        cache["version"] = version
                    fresh.set()
            if stop.wait(interval):
                break
        with cache_lock:
            cache["dead"] = True
        fresh.set()

    def pull_params():
        while not fresh.wait(timeout=0.2):
            if stop.is_set():
                return None
        with cache_lock:
            if cache["dead"] and cache["params"] is None:
                return None
            return cache["params"], cache["version"]

    outbox: stdlib_queue.Queue = stdlib_queue.Queue(maxsize=1)

    def send_loop():
        while True:
            try:
                item = outbox.get(timeout=0.1)
            except stdlib_queue.Empty:
                if stop.is_set():
                    return
                continue
            if item is None:
                return
            tr = item.trace
            if tr is not None:
                tr = dict(tr)
                tr["e0"] = time.monotonic()     # encode start; serde
                # stamps e1 itself once the payload bytes are built
            buf = serde.encode_item(serde.TrajectoryItem(
                jax.tree.map(np.asarray, item.data),
                item.param_version, item.actor_id, item.produced_at,
                tr), codec=wire_codec)
            if not send_buf(buf):
                return                  # channel says we are done

    def emit(item):
        while not stop.is_set():
            try:
                outbox.put(item, timeout=0.1)
                return True
            except stdlib_queue.Full:
                continue                # wire backpressure reached us
        return False

    sub = threading.Thread(target=subscribe, daemon=True,
                           name="param-subscriber")
    snd = threading.Thread(target=send_loop, daemon=True,
                           name="traj-sender")
    sub.start()
    snd.start()
    try:
        run_actor_loop(actor_id=actor_id, builder=builder, seed=seed,
                       pull_params=pull_params, emit=emit,
                       should_stop=stop.is_set)
    finally:
        try:
            outbox.put_nowait(None)
        except stdlib_queue.Full:
            pass
        snd.join(timeout=5.0)


def run_serialized_inference_actor(*, actor_id: int, env_name: str,
                                   arch_cfg, icfg, num_envs: int,
                                   seed: int,
                                   send_buf: Callable[[bytes], bool],
                                   infer_clients: List[Any],
                                   stop,
                                   wire_codec: str = "none") -> None:
    """One inference-mode actor on the far side of a serialized
    boundary: no parameters, no policy network — env stepping plus
    frames both ways (observation requests up, action replies down,
    finished trajectories out through ``send_buf``). ``infer_clients``
    is one service client per pipeline stream (pipe- or socket-backed;
    same surface). The trajectory sender runs behind the same depth-1
    outbox as the unroll worker, overlapping encode+send with the next
    unroll's inference round-trips."""
    import queue as stdlib_queue
    import threading

    from repro.data.envs import make_env
    from repro.distributed import serde

    for cl in infer_clients:
        cl.bind_stop(stop)
    env = make_env(env_name)
    outbox: stdlib_queue.Queue = stdlib_queue.Queue(maxsize=1)

    def send_loop():
        while True:
            try:
                item = outbox.get(timeout=0.1)
            except stdlib_queue.Empty:
                if stop.is_set():
                    return
                continue
            if item is None:
                return
            buf = serde.encode_item(item, codec=wire_codec)
            if not send_buf(buf):           # leaves already numpy
                return

    def emit(item):
        blocked = False
        try:
            while not stop.is_set():
                try:
                    outbox.put(item, timeout=0.1)
                    return True
                except stdlib_queue.Full:
                    # wire backpressure reached us: drop out of the
                    # service's ready rule while we wait
                    if not blocked:
                        blocked = True
                        for cl in infer_clients:
                            cl.pause()
                    continue
        finally:
            if blocked:
                for cl in infer_clients:
                    cl.resume()
        return False

    snd = threading.Thread(target=send_loop, daemon=True,
                           name="traj-sender")
    snd.start()
    try:
        run_inference_actor_loop(
            actor_id=actor_id, env=env, arch_cfg=arch_cfg, icfg=icfg,
            num_envs=num_envs, seed=seed, clients=infer_clients,
            emit=emit, should_stop=stop.is_set)
    finally:
        try:
            outbox.put_nowait(None)
        except stdlib_queue.Full:
            pass
        snd.join(timeout=5.0)
        for cl in infer_clients:
            cl.close()


# ---------------------------------------------------------------------------
# process worker entry point (spawn target — must be module-level)


def use_host_cpu() -> None:
    """Run this process's JAX on the host CPU, whatever platform its
    environment names. Every spawned actor child calls this before its
    first JAX use: a chip belongs to one process, and the learner that
    spawned the child holds it."""
    import jax
    jax.config.update("jax_platforms", "cpu")


def _tune_child_scheduling(actor_id: int) -> None:
    """Best-effort OS tuning for an actor child on a shared box: actors
    yield to the learner (the learner is the throughput constraint under
    backpressure — a niced actor loses nothing, it would have stalled on
    the queue anyway) and each child sticks to one core so four children
    don't migrate across, and thrash the caches of, every core the
    learner's train step is using. Pinning keys off the *global* slot
    id, so the actor shards of a learner group land on disjoint cores
    by construction (modulo wraparound on small hosts)."""
    import os
    # a small niceness wins: +3 keeps the learner ahead in the scheduler
    # without starving acting (larger values over-throttle producers on
    # small hosts); override via env for experiments
    nice_step = int(os.environ.get("REPRO_ACTOR_NICE", "3"))
    if nice_step:
        try:
            os.nice(nice_step)
        except OSError:  # pragma: no cover
            pass
    if os.environ.get("REPRO_ACTOR_PIN", "1") == "1":
        try:
            ncpu = os.cpu_count() or 1
            os.sched_setaffinity(0, {actor_id % ncpu})
        except (AttributeError, OSError):  # pragma: no cover
            pass


def _wire_send_buf(producer, stop_event) -> Callable[[bytes], bool]:
    """Adapt a ``ShmProducer``-style offer-with-timeout handle to the
    blocking ``send_buf`` contract the serialized actor bodies use."""
    def send_buf(buf: bytes) -> bool:
        while not stop_event.is_set():
            if producer.send(buf, timeout=0.1):
                return True
        return False
    return send_buf


def process_actor_main(actor_id: int, env_name: str, arch_cfg, icfg,
                       num_envs: int, seed: int, producer,
                       param_conn, stop_event,
                       wire_codec: str = "none") -> None:
    """Entry point of one actor *process*. Builds its own env batch and
    jit cache (nothing jax crosses the process boundary), subscribes to
    params by version from the parent's param server over the pipe, and
    ships serde-encoded trajectories through the wire — the loop,
    subscriber, and sender all live in ``run_serialized_unroll_actor``,
    shared verbatim with the socket (remote) backend."""
    try:
        use_host_cpu()
        _tune_child_scheduling(actor_id)

        def pull_msg(have_version):
            param_conn.send(("pull", actor_id, have_version))
            return param_conn.recv()

        run_serialized_unroll_actor(
            actor_id=actor_id, env_name=env_name, arch_cfg=arch_cfg,
            icfg=icfg, num_envs=num_envs, seed=seed,
            send_buf=_wire_send_buf(producer, stop_event),
            pull_msg=pull_msg, stop=stop_event, wire_codec=wire_codec)
    except BaseException:
        try:
            param_conn.send(("error", actor_id, traceback.format_exc()))
        except (EOFError, OSError, BrokenPipeError):
            pass
    finally:
        try:
            param_conn.close()
        except OSError:
            pass


def inference_actor_main(actor_id: int, env_name: str, arch_cfg, icfg,
                         num_envs: int, seed: int, producer,
                         infer_clients, ctrl_conn, stop_event,
                         wire_codec: str = "none") -> None:
    """Entry point of one *inference-mode* actor process: no parameters,
    no policy network — just env stepping plus serde frames both ways
    (observation requests up the shared wire, action replies back down
    per-stream private pipes, finished trajectories through the
    transport wire). ``infer_clients`` is one ``PipeInferenceClient``
    per pipeline stream; ``ctrl_conn`` is the control pipe to the
    parent's server thread, used only for error reports here (nothing
    to pull — the service owns the params). The loop body is
    ``run_serialized_inference_actor``, shared verbatim with the socket
    (remote) backend."""
    try:
        use_host_cpu()
        _tune_child_scheduling(actor_id)
        run_serialized_inference_actor(
            actor_id=actor_id, env_name=env_name, arch_cfg=arch_cfg,
            icfg=icfg, num_envs=num_envs, seed=seed,
            send_buf=_wire_send_buf(producer, stop_event),
            infer_clients=infer_clients, stop=stop_event,
            wire_codec=wire_codec)
    except BaseException:
        try:
            ctrl_conn.send(("error", actor_id, traceback.format_exc()))
        except (EOFError, OSError, BrokenPipeError):
            pass
    finally:
        try:
            ctrl_conn.close()
        except OSError:
            pass
