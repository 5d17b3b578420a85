"""The SPMD cell's exchange left out is caught: on two CPU devices, in a
process of its own (the device count is fixed when JAX starts), the
harness drives the deep cell cut to a tiny size twice, as it is and with
the gradient all-reduce replaced by the identity."""
from __future__ import annotations

import os
import subprocess
import sys

from chipbench import run
from chipbench.tests.test_run import SEED

SPMD_SCRIPT = r"""
import sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from chipbench import run
from chipbench.tests import tiny
f = tiny.files(*tiny.DEEP, spmd_devices=2)
device = dict(tiny.DEVICE, count=2)
sound = run.run_cell(f, {seed}, 0.5, False, device)
jax.lax.pmean = lambda x, axis_name, **kw: x   # the exchange left out
broken = run.run_cell(f, {seed}, 0.5, False, device)
print("RESULT", sound["correct"], broken["correct"])
"""


def test_spmd_exchange_left_out_is_caught():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    script = SPMD_SCRIPT.format(root=run.ROOT,
                                src=os.path.join(run.ROOT, "src"),
                                seed=SEED)
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "RESULT True False" in p.stdout, p.stdout[-2000:]
