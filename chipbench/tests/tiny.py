"""A cell's files cut to a size a CPU test can run: 8x8 frames from a
4x4 chase grid, unroll 5, a few envs and actors. Everything else (the
agent's widths, the learning constants, the traffic's shape) is the
cell's own."""
from __future__ import annotations

import copy

from chipbench import run

# the comparison's limits at this size on the CPU's float32
LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-3, "update_gap": 1e-3,
          "update_diff": 1e-3, "act_gap": 1e-4}
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1,
          "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def files(config: str, traffic: str, spmd_devices=None) -> dict:
    """A configuration's and a traffic mix's files, found by name as
    ``run.load_cell`` finds them, cut to the tiny size."""
    cfg = copy.deepcopy(run.load_json("configs", config + ".json"))
    cfg["env_args"] = {"grid": [4, 4], "cell_px": 2, "horizon": 7}
    cfg["frame"] = [8, 8, 3]
    cfg["unroll_length"] = 5
    tr = dict(run.load_json("traffic", traffic + ".json"))
    tr.update(num_envs=4, num_actors=2, queue_capacity=4)
    if spmd_devices is not None:
        tr["spmd_devices"] = spmd_devices
    return {"cell": {"name": f"{config}.{traffic}",
                     "chips": max(1, tr["spmd_devices"])},
            "config": cfg, "traffic": tr, "limits": dict(LIMITS),
            "flops": run.load_module("flops", config + ".py"),
            "env": run.load_module("envs", cfg["env"] + ".py"),
            "per_layer": []}


# the cells' files, and the deep agent's, whose cell (PERF.md, Open
# questions) waits for chip readings to set its limits from
SHALLOW = ("impala-shallow-72x96", "inference-8x32")
DEEP = ("impala-deep-72x96", "unroll-4x32")
