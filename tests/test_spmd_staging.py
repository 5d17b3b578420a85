"""How the SPMD learner's batches reach the mesh: on 4 forced host
devices, in a process of its own (the device count is fixed when JAX
starts), a learner with thread unroll actors trains under each batch
bucket. Thread actors hand device arrays, so every batch is resharded
(once per update, in a ``learner.reshard`` span) and runs the
batch-sharded step; a row count the mesh cannot split runs the
replicated fallback and is counted so. Host (numpy) trajectories take
the per-shard staging path instead."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import collections, json, sys
sys.path[:0] = [{src!r}]
import numpy as np
import jax
from repro.configs.base import ImpalaConfig
from repro.distributed import learner as learner_mod
from repro.distributed.runtime import _setup
from repro.distributed.serde import TrajectoryItem

spans = collections.Counter()
real_span = learner_mod.span


def counting_span(name):
    spans[name] += 1
    return real_span(name)


learner_mod.span = counting_span
STEPS = 3
out = {{}}
# (label, envs per actor, largest bucket): 4 envs split over 4 devices
# in every bucket; 3 rows do not split
for label, envs, bucket in (("1", 4, 1), ("2", 4, 2), ("4", 4, 4),
                            ("uneven", 3, 1)):
    icfg = ImpalaConfig(num_actions=3, unroll_length=4,
                        learning_rate=1e-3, rmsprop_eps=0.01)
    spans.clear()
    # a long linger fills the largest bucket every update
    learner = _setup("catch", icfg, envs, num_actors=2,
                     max_batch_trajs=bucket, batch_linger_s=60.0,
                     spmd_devices=4, seed=0)
    _m, tel = learner.run(STEPS)
    out[label] = {{"batches": tel["group"]["batches"],
                   "batch_hist": {{str(k): v for k, v in
                                  tel["batch_size_hist"].items()}},
                   "reshard_spans": spans["learner.reshard"],
                   "stage_spans": spans["learner.stage"]}}

# host trajectories: one device_put per shard
stager = learner_mod._HostStager(mesh=learner._spmd_mesh)
rng = np.random.default_rng(0)
items = [TrajectoryItem(data={{"x": rng.normal(size=(4, 5))}},
                        param_version=0, actor_id=0, produced_at=0.0)
         for _ in range(2)]
routes = {{k: learner.obs_registry.counter("host." + k)
           for k in ("sharded", "resharded")}}
batch = learner_mod._stack(items, stager, routes)
out["host"] = {{"routes": {{k: c.value for k, c in routes.items()}},
                "shards": len(batch["x"].sharding.device_set)}}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def staged():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(src=os.path.join(ROOT, "src"))],
        env=env, capture_output=True, text=True, timeout=500)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    assert line, p.stdout[-2000:]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.timeout_s(600)
@pytest.mark.parametrize("bucket", ["1", "2", "4"])
def test_device_batches_are_resharded_and_run_the_sharded_step(staged,
                                                                bucket):
    got = staged[bucket]
    assert got["batch_hist"] == {bucket: 3}
    assert got["batches"] == {"sharded": 0, "resharded": 3,
                              "replicated": 0}
    # once per update, nested in the stage span
    assert got["reshard_spans"] == 3 == got["stage_spans"]


@pytest.mark.timeout_s(600)
def test_rows_the_mesh_cannot_split_are_counted_replicated(staged):
    got = staged["uneven"]
    assert got["batch_hist"] == {"1": 3}
    # the routes count every batch; replicated counts the fallback steps
    # among them
    assert got["batches"] == {"sharded": 0, "resharded": 3,
                              "replicated": 3}


@pytest.mark.timeout_s(600)
def test_host_trajectories_are_staged_one_shard_per_device(staged):
    assert staged["host"] == {"routes": {"sharded": 1, "resharded": 0},
                              "shards": 4}
