"""Readings that the limits in ``chipbench/limits/<cell>.json`` are set
from, for many seeds in one process on the cell's own chips:

    python3 chipbench/calibrate.py --workload <cell> --seeds 101 102 ... \
        [--faults 3]

For each seed it builds the cell exactly as a run does, lets the learner
take the first ``compare.STEPS`` updates (no measured window: training's
readings need none), and prints one JSON line with the program's
numbers. For the first ``--faults`` seeds it also puts the reference in
the program's place and reads:

  control   the reference with every matmul and conv input rounded to
            float8 (e4m3), the precision below the one the program's
            float32 matmuls take on a TPU (one bfloat16 pass);
  half      half of each batch left out: its rows replaced by the other
            half's, so the sum-loss is the mean over the rest, doubled;
  exchange  (SPMD cells) the all-reduce left out: each step applies the
            first chip's gradient alone, as its published copy would;
  token     the first env's logged action altered at every step, where
            the actor produced it;
  token1    the first env's first action alone altered.

A step that returns its state unchanged reads exactly 1 as ``grad_gap``
and ``update_gap`` by the measure itself and needs no run.

With ``--limits-out <file>`` it then sets each number's limit from the
readings, by one rule: the lower reading is the largest over the
program's seeds; the upper is the least of the control's smallest
reading, where that is three times the lower or more, and each fault's
smallest, where that is ten times the lower or more (a state left
unchanged: three times); the limit lies 60% of the way from the lower
to the upper on a log scale, rounded down to two digits. A number with
no upper reading gets no limit.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import compare  # noqa: E402
from chipbench import reference as ref_lib  # noqa: E402
from chipbench.run import (Failed, build_learner,  # noqa: E402
                           capture_first_steps, device_check, load_cell)


def program_capture(files, seed):
    learner = build_learner(files, seed)
    cap = capture_first_steps(learner, compare.STEPS)
    learner.run(compare.STEPS, warm_buckets=True)
    del learner
    gc.collect()
    return cap


def as_captured(cfg, out):
    """A reference run in the form the harness captures from the
    program."""
    import jax
    import numpy as np

    decay = cfg["learning"]["rmsprop_decay"]
    ms = jax.tree.map(lambda g: (1 - decay) * np.square(
        np.asarray(g, np.float64)), out["first_grads"])
    return {"losses": out["losses"], "ms1": {"ms": ms},
            "params_last": out["params"],
            "first_logprob": out["first_tlp"]}


def seed_readings(files, seed, cap, shards, with_faults):
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = files["config"]
    batches = cap["batches"]
    ref = ref_lib.run_steps(cfg, seed, batches, shards)
    leaves = {}
    out = {"program": compare.numbers(cfg, ref, dict(
        cap, first_logprob=batches[0]["behaviour_logprob"]), leaves),
        "program_leaves": leaves}
    if not with_faults:
        return out
    out["control"] = compare.numbers(cfg, ref, as_captured(
        cfg, ref_lib.run_steps(cfg, seed, batches, shards,
                               round_to=jnp.float8_e4m3fn)))

    def halved(b):
        n = b["actions"].shape[0] // 2
        return jax.tree.map(lambda x: np.concatenate([x[:n], x[:n]]), b)

    # a fault in the train step leaves the acting alone: the logged
    # behaviour log-probs stay the honest ones
    half = as_captured(cfg, ref_lib.run_steps(
        cfg, seed, [halved(b) for b in batches], shards))
    half["first_logprob"] = ref["first_tlp"]
    out["half"] = compare.numbers(cfg, ref, half)
    if shards > 1:
        def first_chip(b):
            n = b["actions"].shape[0] // shards
            return jax.tree.map(lambda x: x[:n], b)

        ex = as_captured(cfg, ref_lib.run_steps(
            cfg, seed, [first_chip(b) for b in batches], 1))
        ex["first_logprob"] = ref["first_tlp"]
        out["exchange"] = compare.numbers(cfg, ref, ex)
    # the first env's action altered at every step after the actor
    # scored it: the trajectory logs the altered action beside the
    # log-prob of the one it took
    for kind, steps in (("token", slice(None)), ("token1", slice(0, 1))):
        tok = copy.deepcopy(batches)
        a = tok[0]["actions"]
        a[0, steps] = (a[0, steps] + 1) % cfg["num_actions"]
        ref_tok = ref_lib.run_steps(cfg, seed, tok, shards)
        honest = as_captured(cfg, ref_tok)
        honest["first_logprob"] = ref["first_tlp"]
        out[kind] = compare.numbers(cfg, ref_tok, honest)
    return out


FAULTS = ("half", "exchange", "token", "token1")


def set_limits(lines) -> dict:
    """Each number's limit from the readings of ``seed_readings`` lines,
    by the rule in the module's docstring."""
    limits = {}
    for k in compare.NUMBERS:
        lower = max(ln["program"][k] for ln in lines)
        ups = [min(ln["control"][k] for ln in lines if "control" in ln)]
        ups = [u for u in ups if u >= 3 * lower]
        for kind in FAULTS:
            got = [ln[kind][k] for ln in lines if kind in ln]
            if got and min(got) >= 10 * lower:
                ups.append(min(got))
        if k in ("grad_gap", "update_gap") and 1.0 >= 3 * lower:
            ups.append(1.0)
        if not ups or lower <= 0:
            continue
        raw = lower ** 0.4 * min(ups) ** 0.6
        exp = math.floor(math.log10(raw)) - 1
        limits[k] = float(f"{math.floor(raw / 10.0 ** exp)}e{exp}")
    return limits


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--faults", type=int, default=0)
    p.add_argument("--limits-out", default=None)
    args = p.parse_args(argv)
    try:
        files = load_cell(args.workload)
        device_check(files["cell"]["chips"])
    except Failed as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    import jax

    from repro.launch.train import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    shards = max(1, files["traffic"]["spmd_devices"])
    lines = []
    for i, seed in enumerate(args.seeds):
        cap = program_capture(files, seed)
        line = {"seed": seed}
        line.update(seed_readings(files, seed, cap, shards,
                                  with_faults=i < args.faults))
        print(json.dumps(line), flush=True)
        lines.append(line)
    if args.limits_out:
        limits = set_limits(lines)
        with open(args.limits_out, "w") as f:
            f.write(json.dumps(limits, indent=1) + "\n")
        for kind in ("program", "control") + FAULTS:
            for ln in lines:
                if kind in ln:
                    print(f"{kind} seed {ln['seed']}: correct "
                          f"{compare.judge(ln[kind], limits)}",
                          file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
