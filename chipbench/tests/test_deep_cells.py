"""The deep agent's cell ``deep-unroll`` (one chip) and the files of its
four-chip SPMD cell, ``deep-spmd4``, which waits for its limits to be set
on four chips: the cell's files load by name and the SPMD traffic is the
one that cell will run; the program matches the reference on the CPU at
a tiny size through those files, and on four devices the gradient
exchange left out is caught; the readers of the SPMD learner's metrics
give the expected numbers on traces built by hand."""
from __future__ import annotations

import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from chipbench import params_count, reference, run, trace_reduce
from chipbench.tests import tiny
from chipbench.tests.test_files import \
    test_cell_files_load_by_name as check_cell_files
from chipbench.tests.test_run import SEED

DEEP_CONFIG = "impala-deep-72x96"
SPMD4 = (DEEP_CONFIG, "spmd4-unroll-8x32")
MS = 1_000_000          # nanoseconds
WINDOW = (0, 1000 * MS)

# ops named as the ``XLA Ops`` line names them, by their HLO text: here
# from the SPMD step compiled for a TPU v5e 2x2 (the all-reduce's tuple
# of 47 shapes and its operands cut short)
ALLREDUCE = ("%all-reduce.5 = (f32[256,1024]{1,0:T(8,128)S(1)}, "
             "f32[1024]{0:T(1024)S(1)}, f32[]{:T(128)}) all-reduce("
             "%custom-call.22, %copy-done.60, %reduce_sum.706), "
             "channel_id=1, replica_groups=[1,4]<=[4], "
             "use_global_device_ids=true, to_apply=%region_3.1")
NOT_ALLREDUCE = (
    "%get-tuple-element.1049 = f32[3456,256]{1,0:T(8,128)S(1)} "
    "get-tuple-element(%all-reduce.5), index=8",
    "%fusion.388 = f32[32]{0:T(128)S(1)} fusion(pred[101,32] "
    "%get-tuple-element.1364), kind=kLoop, calls=%fused_computation.46")


@pytest.fixture(scope="module")
def bench():
    import json

    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("workload", ["deep-unroll"])
def test_deep_cell_files_load_by_name(bench, workload):
    check_cell_files(bench, workload)
    f = run.load_cell(workload, bench)
    assert f["config"]["name"] == DEEP_CONFIG
    assert f["config"]["torso"] == "deep"


def test_spmd4_traffic_is_the_cells():
    tr = run.load_json("traffic", SPMD4[1] + ".json")
    tr.pop("why")
    assert tr == {"actor_backend": "thread", "actor_mode": "unroll",
                  "transport": "inproc", "num_actors": 8, "num_envs": 32,
                  "max_batch_trajs": 4, "queue_capacity": 8,
                  "queue_policy": "block", "infer_flush_timeout_s": 0.02,
                  "spmd_devices": 4}
    # every bucket's rows (128, 64, 32) split over the four chips
    assert all(b * tr["num_envs"] % tr["spmd_devices"] == 0
               for b in (4, 2, 1))


SPMD4_SCRIPT = r"""
import sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from chipbench import run
from chipbench.tests import tiny
f = tiny.files({config!r}, {traffic!r})
assert f["cell"]["chips"] == 4 and len(jax.devices()) == 4
device = dict(tiny.DEVICE, count=4)
sound = run.run_cell(f, {seed}, 0.5, False, device)
jax.lax.pmean = lambda x, axis_name, **kw: x   # the exchange left out
broken = run.run_cell(f, {seed}, 0.5, False, device)
print("RESULT", sound["correct"], broken["correct"])
"""


def test_spmd4_files_match_the_reference_and_catch_the_exchange_left_out():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = SPMD4_SCRIPT.format(root=run.ROOT,
                                 src=os.path.join(run.ROOT, "src"),
                                 config=SPMD4[0], traffic=SPMD4[1],
                                 seed=SEED)
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=840)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "RESULT True False" in p.stdout, p.stdout[-2000:]


def test_deep_unroll_files_match_the_reference_on_one_device():
    res = run.run_cell(tiny.files(*tiny.DEEP), SEED, 0.5, False,
                       tiny.DEVICE)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_parameter_count_is_the_reference_weights():
    cfg = run.load_json("configs", DEEP_CONFIG + ".json")
    sizes = [x.size for x in jax.tree.leaves(reference.init_params(cfg, 1))]
    assert params_count.count(cfg) == sum(sizes) == 1_581_382


def _events(spans):
    return trace_reduce.Events([n for n, _s, _e in spans],
                               [s for _n, s, _e in spans],
                               [e for _n, _s, e in spans])


def _ctx(ops_by_dev, host=(), updates=1):
    empty = trace_reduce.Events([], [], [])
    tr = trace_reduce.Trace(WINDOW, {d: _events(o) for d, o in
                                     ops_by_dev.items()},
                            {d: empty for d in ops_by_dev}, list(host))
    return types.SimpleNamespace(
        trace=tr, updates=updates, chips=len(ops_by_dev),
        config=run.load_json("configs", DEEP_CONFIG + ".json"))


def _compute(metric, ctx):
    return run.load_module("metrics", metric + ".py").compute(ctx)


def test_chip0_busy_ratio():
    # chip 0 busy 400 ms (two overlapping ops merge), the others 200 ms
    ops = {0: [("a", 0, 300 * MS), ("b", 100 * MS, 400 * MS)]}
    ops.update({d: [("a", 0, 150 * MS), ("c", 500 * MS, 550 * MS)]
                for d in (1, 2, 3)})
    assert _compute("spmd.chip0_busy_ratio", _ctx(ops)) == \
        pytest.approx(2.0)
    assert _compute("spmd.chip0_busy_ratio", _ctx({0: ops[0]})) is None


def test_allreduce_readers_match_the_op_as_the_chip_names_it():
    pattern = run.load_module("metrics", "spmd.collective_ms.py").PATTERN
    ev = _events([(ALLREDUCE, 0, 1)] + [(n, 0, 1) for n in NOT_ALLREDUCE])
    assert ev.count(pattern) == 1
    # two updates; chips 0-2 wait in the all-reduce (1 and 1.5 ms), chip
    # 3 arrives last (0.5 ms each)
    ops = {d: [(ALLREDUCE, 0, MS), (NOT_ALLREDUCE[0], MS, 9 * MS),
               (ALLREDUCE, 10 * MS, 11 * MS + MS // 2)]
           for d in (0, 1, 2)}
    ops[3] = [(ALLREDUCE, 0, MS // 2), (ALLREDUCE, 10 * MS, 10 * MS + MS // 2)]
    ctx = _ctx(ops, updates=2)
    # exposed: the mean over the chips, waits included
    assert _compute("spmd.collective_ms", ctx) == \
        pytest.approx((3 * 2.5 + 1.0) / 4 / 2)
    # a ring all-reduce moves 2 (n - 1) / n of the gradient's bytes, in
    # the time of the chip that waits least
    floor = 1.5 * 4 * 1_581_382 / 200e9
    assert _compute("spmd.allreduce_roofline", ctx) == \
        pytest.approx(100 * floor / 0.5e-3)
    one = _ctx({0: ops[0]})
    assert _compute("spmd.collective_ms", one) is None
    assert _compute("spmd.allreduce_roofline", one) is None
    none = _ctx({d: [(NOT_ALLREDUCE[1], 0, MS)] for d in range(4)})
    assert _compute("spmd.collective_ms", none) is None
    assert _compute("spmd.allreduce_roofline", none) is None


def test_an_async_all_reduce_pair_is_one_call():
    start = ALLREDUCE.replace("%all-reduce.5 =", "%all-reduce-start.5 =") \
        .replace(") all-reduce(", ") all-reduce-start(")
    done = ("%all-reduce-done.5 = (f32[256,1024]{1,0:T(8,128)S(1)}) "
            "all-reduce-done(%all-reduce-start.5)")
    # per chip one call: 0.1 ms to start, 0.4 ms to finish
    ops = {d: [(start, 0, MS // 10), (done, MS // 10, MS // 2)]
           for d in range(4)}
    floor = 1.5 * 4 * 1_581_382 / 200e9
    assert _compute("spmd.allreduce_roofline", _ctx(ops)) == \
        pytest.approx(100 * floor / 0.5e-3)
    assert _compute("spmd.collective_ms", _ctx(ops)) == pytest.approx(0.5)


def test_reshard_reader_takes_the_spans_inside_the_window():
    w0, w1 = WINDOW
    host = [(w0, w0 + MS, "python3: learner.reshard"),       # clipped
            (10 * MS, 20 * MS, "python3: learner.stage"),
            (12 * MS, 15 * MS, "python3: learner.reshard"),   # nested
            (30 * MS, 35 * MS, "python3: learner.reshard"),
            (w1 - MS, w1, "python3: learner.reshard")]        # clipped
    ops = {d: [("op", 0, MS)] for d in range(4)}
    assert _compute("learner.reshard_ms", _ctx(ops, host)) == \
        pytest.approx(4.0)
    assert _compute("learner.reshard_ms", _ctx(ops, host[1:2])) is None


def test_roofline_floor_counts_each_chip_share_of_the_ring():
    mod = run.load_module("metrics", "spmd.allreduce_roofline.py")
    cfg = run.load_json("configs", DEEP_CONFIG + ".json")
    assert mod.ICI_BYTES_PER_S == 200e9
    assert mod.floor_s(cfg, 4) == pytest.approx(
        1.5 * 4 * 1_581_382 / 200e9)
    assert mod.floor_s(cfg, 2) == pytest.approx(4 * 1_581_382 / 200e9)
    assert np.isclose(mod.floor_s(cfg, 1), 0.0)
