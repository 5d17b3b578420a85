"""The program's host spans reach a profiler trace, and the readers of
the per-layer metrics built on them: a tiny traced run of each actor
mode on the CPU carries its spans on the host plane, and each reader on
a trace built by hand gives the expected number, leaves out spans
clipped at the window's edges, handles nested spans and finds nothing
where its span is absent."""
from __future__ import annotations

import os
import types

import pytest

from chipbench import run, trace_reduce
from chipbench.tests import tiny
from chipbench.tests.test_run import SEED
from repro.obs.trace import HOST_SPAN_NAMES

MS = 1_000_000          # nanoseconds
WINDOW = (0, 1000 * MS)

LEARNER = {"learner.wait", "learner.stage", "learner.step",
           "learner.publish"}


@pytest.mark.parametrize("traffic, spans, metrics", [
    ("inference-8x32",
     LEARNER | {"acting.step", "acting.env_step", "acting.assemble",
                "acting.emit", "infer.flush"},
     ["acting.step_ms", "acting.assemble_ms", "acting.emit_ms",
      "infer.flush_host_ms", "learner.stage_ms", "learner.wait_share"]),
    ("unroll-4x32", LEARNER | {"acting.unroll", "acting.emit"},
     ["acting.emit_ms", "learner.stage_ms", "learner.wait_share"]),
])
def test_traced_run_carries_the_program_spans(tmp_path, monkeypatch,
                                              traffic, spans, metrics):
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path))
    f = tiny.files("impala-shallow-72x96", traffic)
    f["per_layer"] = [{"name": m, "unit": "x"} for m in metrics]
    res = run.run_cell(f, SEED, 0.5, True, tiny.DEVICE)
    assert res["correct"], res["checks"]
    assert all(res["metrics"][m]["value"] > 0 for m in metrics)
    tr = trace_reduce.load(os.path.join(str(tmp_path), f["cell"]["name"]))
    seen = {n.split(": ")[-1] for _s, _e, n in tr.host}
    assert spans <= seen & set(HOST_SPAN_NAMES)


def _trace(host, busy=()):
    ops = trace_reduce.Events(["op"] * len(busy), [s for s, _ in busy],
                              [e for _, e in busy])
    return types.SimpleNamespace(trace=trace_reduce.Trace(
        WINDOW, {0: ops}, {0: trace_reduce.Events([], [], [])}, host))


def _compute(metric, ctx):
    return run.load_module("metrics", metric + ".py").compute(ctx)


@pytest.mark.parametrize("metric, name", [
    ("acting.step_ms", "acting.step"),
    ("acting.assemble_ms", "acting.assemble"),
    ("acting.emit_ms", "acting.emit"),
    ("infer.flush_host_ms", "infer.flush"),
    ("learner.stage_ms", "learner.stage"),
])
def test_mean_span_readers(metric, name):
    w0, w1 = WINDOW
    host = [
        (w0, w0 + 2 * MS, f"python3: {name}"),          # clipped: left out
        (10 * MS, 14 * MS, f"python3: {name}"),
        (11 * MS, 12 * MS, "python3: learner.publish"),  # nested, other
        (20 * MS, 26 * MS, f"python3: {name}"),
        (21 * MS, 22 * MS, f"python3: {name}.x"),       # not this span
        (30 * MS, 90 * MS, f"python3: harness: {name}"),
        (w1 - MS, w1, f"python3: {name}"),              # clipped: left out
    ]
    assert _compute(metric, _trace(host)) == pytest.approx(5.0)
    assert _compute(metric, _trace(host[2:3])) is None


def test_wait_share_is_the_union_of_waits_in_the_window():
    w0, w1 = WINDOW
    host = [(w0, 100 * MS, "python3: learner.wait"),     # clipped: its part
            (200 * MS, 300 * MS, "python3: learner.wait"),
            (250 * MS, 280 * MS, "python3: learner.wait"),
            (250 * MS, 400 * MS, "python3: learner: wait for a trajectory"),
            (900 * MS, w1, "python3: learner.wait")]
    assert _compute("learner.wait_share", _trace(host)) == \
        pytest.approx(30.0)
    assert _compute("learner.wait_share", _trace(host[3:4])) is None


def test_idle_unattributed_share():
    busy = [(100 * MS, 200 * MS), (600 * MS, 700 * MS)]   # idle 800 ms
    host = [(150 * MS, 400 * MS, "python3: acting.step"),  # 200 ms of idle
            (160 * MS, 170 * MS, "python3: infer.flush"),  # nested, busy
            (350 * MS, 450 * MS, "python3: acting.emit"),  # 50 ms more
            (0, 1000 * MS, "python3: learner.wait"),       # not a cover
            (700 * MS, 1000 * MS, "python3: learner: step and publish")]
    assert _compute("device.idle_unattributed_share",
                    _trace(host, busy)) == pytest.approx(100 * 550 / 800)
    assert _compute("device.idle_unattributed_share",
                    _trace(host[3:], busy)) is None
