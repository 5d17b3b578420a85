"""The trace reduction on a trace recorded on a TPU v5e: a traced run of
the deep agent on one chip with 4 unroll actors (the deep-unroll cell of
PERF.md's Open questions), a window of 0.84 s with 12 updates, gzipped."""
from __future__ import annotations

import os

import pytest

from chipbench import run, trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "deep-unroll.xplane.pb.gz")


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.load(TRACE)


def test_window_and_busy_time(trace):
    assert trace.devices == [0]
    assert 0.8 < trace.window_s < 1.1
    busy = trace.busy_s(0)
    assert 0 < busy < trace.window_s
    spans = trace.busy_intervals(0)
    assert all(a <= b for a, b in spans)
    assert all(b1 <= a2 for (_a1, b1), (a2, _b2) in zip(spans, spans[1:]))


def test_programs_the_readers_look_for(trace):
    mods = trace.modules[0]
    step = run.load_module("metrics", "step.device_ms.py").PATTERN
    unroll = run.load_module("metrics", "actors.unroll_ms.py").PATTERN
    assert mods.count(step) >= 12 and mods.total_s(step) > 0
    assert mods.count(unroll) >= 1 and mods.total_s(unroll) > 0
    # one fused loss/V-trace launch per train step (the window's edges
    # may cut a step before its kernel)
    kernel = run.load_module("metrics", "vtrace_roofline.py").PATTERN
    assert abs(trace.ops[0].count(kernel) - mods.count(step)) <= 1


def test_breakdown_names_programs_and_labels_gaps(trace):
    bd = trace_reduce.breakdown(trace)
    assert 0 < len(bd["device_ops"]) <= 10
    assert 0 < len(bd["idle_gaps"]) <= 10
    secs = [s for _n, s in bd["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) <= trace.window_s - trace.busy_s(0) + 1e-9
    assert bd["device_ops"][0][0].startswith("jit_train_step")
