"""The flight recorder (repro.obs): metrics registry semantics, the
Prometheus /metrics + /healthz endpoint, trajectory lifecycle tracing
with cross-clock normalization, the JSONL sink and profile-window
parsing — plus one end-to-end async run with the whole stack on,
curled mid-run through the real HTTP server."""
import json
import re
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.obs import ObsConfig
from repro.obs.http import MetricsServer, health, render_prometheus
from repro.obs.metrics import Counter, Gauge, IntHistogram, Registry
from repro.obs.sink import JsonlSink, parse_profile_steps
from repro.obs.trace import (EXCHANGE_SPAN_NAMES, HOST_SPAN_NAMES,
                             SPAN_NAMES, TraceRecorder, span)


# ---------------------------------------------------------------------------
# Registry


def test_registry_create_or_get_identity():
    reg = Registry()
    c1 = reg.counter("q.pushed")
    c2 = reg.counter("q.pushed")
    assert c1 is c2
    c1.inc(3)
    c2.inc()
    assert reg.collect()["q.pushed"] == 4
    g = reg.gauge("q.size")
    g.set(7.5)
    h = reg.int_histogram("lag")
    h.observe(0, 2)
    h.counts[3] += 1              # hot paths write the Counter directly
    col = reg.collect()
    assert col["q.size"] == 7.5
    assert col["lag"] == {0: 2, 3: 1}
    # the collected histogram is a copy, not the live storage
    col["lag"][9] = 99
    assert 9 not in reg.collect()["lag"]


def test_registry_type_mismatch_raises():
    reg = Registry()
    reg.counter("x")
    with pytest.raises(ValueError):
        reg.gauge("x")
    with pytest.raises(ValueError):
        reg.int_histogram("x")


def test_registry_producers_none_omitted_and_errors_captured():
    reg = Registry()
    reg.register_producer("queue", lambda: {"depth": 2})
    reg.register_producer("inference", lambda: None)
    def boom():
        raise RuntimeError("snapshot torn")
    reg.register_producer("exchange", boom)
    col = reg.collect()
    assert col["queue"] == {"depth": 2}
    assert "inference" not in col
    assert "snapshot torn" in col["exchange"]["error"]
    # re-registering replaces (components are rebuilt per run)
    reg.register_producer("queue", lambda: {"depth": 5})
    assert reg.collect()["queue"]["depth"] == 5


# ---------------------------------------------------------------------------
# Prometheus rendering + health


_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"(,[a-zA-Z_][a-zA-Z0-9_]*='
    r'"[^"]*")*\})? -?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?$')


def test_render_prometheus_names_buckets_and_learner_label():
    snap = {
        "frames_per_sec": 1234.5,
        "queue": {"mean_occupancy": 1.25, "dropped": 0,
                  "policy": "block"},          # str: skipped
        "lag": {"hist": {0: 10, 3: 2}, "mean": 0.5},
        "learners": {
            "learner_0": {"frames_per_sec": 600.0},
            "learner_1": {"frames_per_sec": 634.5},
        },
        "learner.lag_hist": {1: 4},            # producer-namespaced key
        "actor_mode": "unroll",                # str: skipped
        "donate": True,
    }
    text = render_prometheus(snap)
    lines = [ln for ln in text.splitlines() if ln]
    for ln in lines:
        assert _PROM_LINE.match(ln), ln
    assert "repro_frames_per_sec 1234.5" in lines
    assert 'repro_lag_hist{bucket="0"} 10' in lines
    assert 'repro_lag_hist{bucket="3"} 2' in lines
    # learners.learner_<k> collapses to a learner="k" label
    assert 'repro_frames_per_sec{learner="0"} 600' in lines
    assert 'repro_frames_per_sec{learner="1"} 634.5' in lines
    # dotted producer keys split like nesting
    assert 'repro_learner_lag_hist{bucket="1"} 4' in lines
    assert "repro_donate 1" in lines
    assert not any("actor_mode" in ln or "policy" in ln for ln in lines)


def test_health_ok_degraded_unhealthy():
    code, body = health({"queue": {"dropped": 0}, "lag": {"mean": 0.0}})
    assert (code, body["status"]) == (200, "ok")
    code, body = health({"queue": {"dropped": 3},
                         "socket": {"reconnects": 1}})
    assert (code, body["status"]) == (200, "degraded")
    assert any("dropped=3" in r for r in body["reasons"])
    code, body = health({"group": {"dead_learners": [2]},
                         "queue": {"dropped": 3}})
    assert (code, body["status"]) == (503, "unhealthy")
    code, body = health({"exchange": {"hub_gone": True}})
    assert code == 503
    code, body = health({"group": {"replicas_identical": False}})
    assert code == 503


def test_health_supervisor_tri_state():
    # a healthy supervised run: counters present, all quiet
    code, body = health({"supervisor": {
        "restarts": 0, "failovers": 0, "restart_in_flight": 0,
        "failover_in_flight": 0, "restarts_exhausted": []}})
    assert (code, body["status"]) == (200, "ok")
    # mid-respawn / mid-failover / solo: degraded, still serving 200
    for key in ("restart_in_flight", "failover_in_flight"):
        code, body = health({"supervisor": {key: 1}})
        assert (code, body["status"]) == (200, "degraded"), key
        assert any(key in r for r in body["reasons"])
    code, body = health({"exchange": {"degraded_solo": True}})
    assert (code, body["status"]) == (200, "degraded")
    # completed restarts are history, not a live condition
    code, body = health({"supervisor": {"restarts": 4, "failovers": 1}})
    assert (code, body["status"]) == (200, "ok")
    # an exhausted restart budget means a child is down for good: 503
    code, body = health({"supervisor": {
        "restarts": 5, "restarts_exhausted": ["actor-3"]}})
    assert (code, body["status"]) == (503, "unhealthy")
    assert any("actor-3" in r for r in body["reasons"])


# ---------------------------------------------------------------------------
# MetricsServer (real sockets, loopback)


def _get(addr, route):
    url = f"http://{addr[0]}:{addr[1]}{route}"
    with urllib.request.urlopen(url, timeout=5) as r:
        return r.status, r.read().decode("utf-8")


def test_metrics_server_routes():
    state = {"snap": {"frames_per_sec": 10.0, "queue": {"dropped": 0}}}
    srv = MetricsServer(lambda: state["snap"], port=0).start()
    try:
        code, text = _get(srv.address, "/metrics")
        assert code == 200 and "repro_frames_per_sec 10" in text
        code, text = _get(srv.address, "/healthz")
        assert code == 200 and json.loads(text)["status"] == "ok"
        code, text = _get(srv.address, "/telemetry")
        assert code == 200
        assert json.loads(text) == state["snap"]
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.address, "/nope")
        assert ei.value.code == 404
        # degraded flips the /healthz body but not the status code
        state["snap"] = {"queue": {"dropped": 9}}
        code, text = _get(srv.address, "/healthz")
        assert code == 200 and json.loads(text)["status"] == "degraded"
        # unhealthy is a real 503 (load balancers understand it)
        state["snap"] = {"exchange": {"hub_gone": True}}
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.address, "/healthz")
        assert ei.value.code == 503
        assert json.loads(ei.value.read().decode())["status"] == \
            "unhealthy"
    finally:
        srv.stop()


def test_metrics_server_snapshot_failure_is_500_not_crash():
    def boom():
        raise RuntimeError("mid-teardown")
    srv = MetricsServer(boom, port=0).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.address, "/metrics")
        assert ei.value.code == 500
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# TraceRecorder


class _Item:
    def __init__(self, trace, actor_id=0, param_version=5):
        self.trace = trace
        self.actor_id = actor_id
        self.param_version = param_version


def _spans_by_name(events):
    return {e["name"]: e for e in events if e.get("ph") == "X"}


def test_trace_recorder_emits_all_seven_spans():
    rec = TraceRecorder()
    t = 100.0
    tr = {"u0": t, "u1": t + 1, "e0": t + 1.1, "e1": t + 1.2,
          "r": t + 1.3}
    rec.record_item(_Item(tr), dequeued=t + 1.5, collected=t + 1.6,
                    step0=t + 1.7, step1=t + 1.9, published=t + 2.0,
                    lag=2)
    spans = _spans_by_name(rec.chrome_events())
    assert set(spans) == set(SPAN_NAMES)
    assert rec.recorded == 1
    # spans tile the lifecycle: each starts where the previous ended
    assert spans["env_unroll"]["dur"] == pytest.approx(1e6)
    assert spans["transport"]["ts"] == pytest.approx((t + 1.2) * 1e6)
    assert spans["queue_wait"]["ts"] == pytest.approx((t + 1.3) * 1e6)
    assert spans["publish"]["dur"] == pytest.approx(0.1e6, rel=1e-3)
    assert spans["train_step"]["args"]["lag"] == 2
    # actor spans on the actor row, learner spans on the learner row
    assert spans["env_unroll"]["pid"] == 1000
    assert spans["train_step"]["pid"] == 1
    names = [e for e in rec.chrome_events() if e["ph"] == "M"]
    assert {e["args"]["name"] for e in names} == {"actor-0", "learner"}


def test_trace_recorder_cross_clock_normalization():
    """Actor stamps from a clock 1000s behind the learner's: the send
    (e1) must land at the learner's receive (r) and all actor spans
    must come out on the learner's clock."""
    rec = TraceRecorder()
    lr = 5000.0                       # learner clock
    ar = 4000.0                       # actor clock, 1000s behind
    tr = {"u0": ar, "u1": ar + 1, "e0": ar + 1, "e1": ar + 1.1, "r": lr}
    rec.record_item(_Item(tr), dequeued=lr + 0.2, collected=lr + 0.3,
                    step0=lr + 0.3, step1=lr + 0.4, published=lr + 0.45)
    spans = _spans_by_name(rec.chrome_events())
    # e1 shifted onto r: transport span is zero-length, not -1000s
    assert spans["transport"]["ts"] == pytest.approx(lr * 1e6)
    assert spans["transport"]["dur"] == 0.0
    # u0 was 1.1s before e1 on the actor's clock; shifted it sits 1.1s
    # before the learner-side receive
    assert spans["env_unroll"]["ts"] == pytest.approx((lr - 1.1) * 1e6)
    assert spans["env_unroll"]["dur"] == pytest.approx(1e6)


def test_trace_recorder_partial_stamps_and_bound():
    rec = TraceRecorder(max_trajectories=2)
    # no trace dict at all: ignored entirely
    rec.record_item(_Item(None), dequeued=1, collected=1, step0=1,
                    step1=1, published=1)
    assert rec.recorded == 0
    # only u-stamps (inproc transport, encode never ran): no exception,
    # missing stamps degrade to zero-length spans
    rec.record_item(_Item({"u0": 10.0, "u1": 10.5}), dequeued=10.6,
                    collected=10.7, step0=10.7, step1=10.8,
                    published=10.9)
    spans = _spans_by_name(rec.chrome_events())
    assert set(spans) == set(SPAN_NAMES)
    assert spans["serde_encode"]["dur"] == 0.0
    rec.record_item(_Item({"u0": 11.0, "u1": 11.5}), dequeued=11.6,
                    collected=11.7, step0=11.7, step1=11.8,
                    published=11.9)
    rec.record_item(_Item({"u0": 12.0, "u1": 12.5}), dequeued=12.6,
                    collected=12.7, step0=12.7, step1=12.8,
                    published=12.9)
    assert rec.recorded == 2 and rec.dropped == 1


def test_trace_recorder_exchange_round_spans():
    rec = TraceRecorder(max_trajectories=2)
    t = 50.0
    rec.record_exchange_round(3, enter=t, gathered=t + 0.2,
                              reduced=t + 0.25, done=t + 0.3)
    events = rec.chrome_events()
    spans = [e for e in events if e["ph"] == "X"]
    # three spans tiling the round, all on the exchange row
    assert [s["name"] for s in spans] == list(EXCHANGE_SPAN_NAMES)
    assert all(s["pid"] == 2 for s in spans)
    assert all(s["args"] == {"round": 3} for s in spans)
    assert spans[0]["ts"] == pytest.approx(t * 1e6)
    assert spans[0]["dur"] == pytest.approx(0.2e6)          # hub_wait
    assert spans[1]["ts"] == pytest.approx((t + 0.2) * 1e6)  # reduce
    assert spans[2]["dur"] == pytest.approx(0.05e6)         # broadcast
    rows = [e for e in events if e["ph"] == "M"]
    assert any(e["args"]["name"] == "exchange" for e in rows)
    # rounds share the trajectory budget: bounded, drops counted
    rec.record_exchange_round(4, enter=t, gathered=t, reduced=t, done=t)
    rec.record_exchange_round(5, enter=t, gathered=t, reduced=t, done=t)
    assert rec.recorded == 2 and rec.dropped == 1


def test_trace_export_loads_as_chrome_trace(tmp_path):
    rec = TraceRecorder()
    rec.record_item(_Item({"u0": 1.0, "u1": 2.0}), dequeued=2.1,
                    collected=2.2, step0=2.2, step1=2.3, published=2.4)
    path = tmp_path / "trace.json"
    assert rec.export(str(path)) == 1
    doc = json.loads(path.read_text())
    assert isinstance(doc["traceEvents"], list)
    assert {e["name"] for e in doc["traceEvents"]
            if e["ph"] == "X"} == set(SPAN_NAMES)


# ---------------------------------------------------------------------------
# serde carries the trace across the wire


def test_serde_roundtrips_trace_and_stamps_e1():
    from repro.distributed import serde

    traj = {"obs": np.zeros((3, 2), np.float32),
            "rewards": np.ones((3,), np.float32)}
    before = time.monotonic()
    item = serde.TrajectoryItem(traj, param_version=4, actor_id=1,
                                produced_at=123.0,
                                trace={"u0": 1.0, "u1": 2.0, "e0": 2.5})
    out = serde.decode_item(serde.encode_item(item))
    assert out.trace is not None
    assert out.trace["u0"] == 1.0 and out.trace["e0"] == 2.5
    # encode stamped e1 itself, after building the payload
    assert before <= out.trace["e1"] <= time.monotonic()
    # the sender's dict was not mutated
    assert "e1" not in item.trace
    # and a traceless item still round-trips with trace None
    plain = serde.TrajectoryItem(traj, 4, 1, 123.0)
    assert serde.decode_item(serde.encode_item(plain)).trace is None


# ---------------------------------------------------------------------------
# sink + profiling window


def test_jsonl_sink_writes_lines(tmp_path):
    path = tmp_path / "tel.jsonl"
    sink = JsonlSink(str(path), lambda: {"x": 1}, interval_s=0.05)
    sink.start()
    time.sleep(0.2)
    sink.stop()
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert sink.lines_written == len(lines) >= 2
    assert all(ln["telemetry"] == {"x": 1} and "t" in ln
               for ln in lines)


def test_profile_hook_failures_fail_the_run(monkeypatch, tmp_path):
    """A profiler that cannot start or stop raises out of the hook (and
    so out of the learner loop) instead of leaving a run that exits 0
    without the trace it was started for."""
    import jax

    from repro.obs.sink import ProfileHook

    def refuse(*a, **k):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    hook = ProfileHook("1:2", str(tmp_path))
    hook.on_step(0)                 # before the window: nothing to do
    with pytest.raises(RuntimeError, match="unavailable"):
        hook.on_step(1)

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", refuse)
    hook = ProfileHook("1:2", str(tmp_path))
    hook.on_step(1)
    assert hook.active
    with pytest.raises(RuntimeError, match="unavailable"):
        hook.on_step(3)
    hook.stop()                     # already stopped: no second raise
    assert hook.done and not hook.active


def test_profile_hook_traces_annotations_without_the_python_tracer(
        monkeypatch, tmp_path):
    """The profile carries the host's annotations (the program spans)
    and no Python tracer, whose event per Python call jax turns on by
    default."""
    import jax

    from repro.obs.sink import ProfileHook

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append((d, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    hook = ProfileHook("0:0", str(tmp_path))
    hook.on_step(0)
    hook.stop()
    [(out_dir, kw)] = calls
    opts = kw["profiler_options"]
    assert out_dir == str(tmp_path)
    assert opts.python_tracer_level == 0 and opts.host_tracer_level == 1


@pytest.mark.parametrize("name", [
    "acting.step", "acting.env_step", "acting.assemble", "acting.emit",
    "acting.unroll", "infer.flush", "learner.wait", "learner.stage",
    "learner.reshard", "learner.step", "learner.publish"])
def test_span_is_a_profiler_annotation_of_a_known_name(name):
    import jax

    assert len(set(HOST_SPAN_NAMES)) == len(HOST_SPAN_NAMES)
    assert name in HOST_SPAN_NAMES
    with span(name) as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)
    with pytest.raises(ValueError, match="HOST_SPAN_NAMES"):
        span(name + "p")


def test_parse_profile_steps():
    assert parse_profile_steps("3:10") == (3, 10)
    assert parse_profile_steps("0:0") == (0, 0)
    for bad in ("10", "5:2", "-1:4", "a:b"):
        with pytest.raises(ValueError):
            parse_profile_steps(bad)


# ---------------------------------------------------------------------------
# end to end: the whole stack on one async run


def test_async_run_with_full_observability(tmp_path):
    """One real async run with metrics server, trace sampling on every
    trajectory, and the JSONL sink — /metrics and /healthz are curled
    mid-run through the live server, the exported trace has all seven
    lifecycle spans, and telemetry gains the phase-timing section."""
    from repro.configs.base import ImpalaConfig
    from repro.distributed import run_async_training

    trace_path = tmp_path / "trace.json"
    sink_path = tmp_path / "tel.jsonl"
    obs = ObsConfig(metrics_port=0, trace_path=str(trace_path),
                    trace_every=1, sink_path=str(sink_path),
                    sink_interval_s=0.1)
    mid = {}

    def on_update(step, params, metrics, snapshot_fn):
        if step == 3 and obs.bound_address is not None:
            code, text = _get(obs.bound_address, "/metrics")
            mid["metrics"] = (code, text)
            mid["healthz"] = _get(obs.bound_address, "/healthz")

    icfg = ImpalaConfig(num_actions=3, unroll_length=8,
                        learning_rate=1e-3, entropy_cost=0.003,
                        rmsprop_eps=0.01)
    tracker, metrics, tel = run_async_training(
        "bandit", icfg, num_envs=4, steps=6, num_actors=1,
        queue_capacity=4, queue_policy="block", max_batch_trajs=2,
        seed=0, on_update=on_update, obs=obs)
    assert tel["learner_updates"] == 6

    # the mid-run curl saw live counters in valid Prometheus format
    code, text = mid["metrics"]
    assert code == 200
    lines = [ln for ln in text.splitlines() if ln]
    assert lines and all(_PROM_LINE.match(ln) for ln in lines)
    assert any(ln.startswith("repro_learner_updates ") for ln in lines)
    assert any(ln.startswith("repro_frames_per_sec ") for ln in lines)
    code, text = mid["healthz"]
    assert code == 200 and json.loads(text)["status"] in ("ok",
                                                          "degraded")

    # phase timing rode along (obs enables it) without breaking the
    # pinned telemetry keys the other tests rely on
    ph = tel["phases"]
    assert ph["updates_timed"] == 6
    assert set(ph["total_s"]) == {"collect", "host_stage", "device_put",
                                  "step", "publish"}
    assert all(v >= 0.0 for v in ph["total_s"].values())

    # exported trace: all seven spans, parseable as chrome trace JSON
    doc = json.loads(trace_path.read_text())
    spans = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert spans == set(SPAN_NAMES)

    # sink left a time series behind
    sl = [json.loads(ln) for ln in sink_path.read_text().splitlines()]
    assert sl and sl[-1]["telemetry"]["learner_updates"] == 6
