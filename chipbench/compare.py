"""What decides ``correct``: the program's first training steps, as the
timed path ran them, against the reference's own steps from the same
seed over the same trajectories.

The harness hands in what it saw of the program's first ``STEPS``
updates: each update's batch (host copy), the loss the step reported,
the RMSProp state after the first step, and the published weights after
the last. Five numbers come out, each a gap against the reference:

  loss_gap    the relative gap between the first step's losses;
  grad_gap    the first step's clipped gradient as the optimizer got it,
              read back from its mean-square state (``ms = (1 - decay)
              g^2`` after one step), leaf by leaf: the gap between the two
              norms over the larger of the reference's norm of that leaf
              and of the median leaf; the median over the leaves;
  update_gap  the same for each leaf's change over the steps;
  update_diff each leaf's change over the steps against the reference's
              as vectors: the norm of their difference over the larger of
              the reference's norm of that leaf's change and of the median
              leaf's; the median over the leaves. Norms alone cannot see
              a gradient that points elsewhere with the same length, as
              half a batch of alike rows gives;
  act_gap     the widest gap in nats between the behaviour log-probs
              the acting forward logged in the first batch (acted before
              any update) and the reference's log-probs of those actions.

The first step's loss and the median leaf, and not the worst step or
leaf, because on the chip the worst leaf is a 16- or 32-element conv
bias whose gradient sums some 10^5 terms of either sign, so one bfloat16
pass moves it by up to a tenth from seed to seed, and the later steps'
losses carry the first update's rounding forward (PERF.md, Findings).
Leaves whose reference gradient is under a thousandth of the median
leaf's move by round-off alone and are left out of both leaf gaps.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

STEPS = 3
NUMBERS = ("loss_gap", "grad_gap", "update_gap", "update_diff", "act_gap")


def _leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
               keep: List[str]) -> Dict[str, float]:
    med = float(np.median([ref[k] for k in keep]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep}


def worst(gaps: Dict[str, float]) -> str:
    k = max(gaps, key=gaps.get)
    return f"{k} {gaps[k]:.3g}"


def readings(cfg: Dict, seed: int, captured: Dict, shards: int,
             leaves: Dict = None) -> Dict[str, float]:
    """The numbers for one run of the program."""
    from chipbench import reference as ref_lib

    ref = ref_lib.run_steps(cfg, seed, captured["batches"], shards)
    prog = dict(captured,
                first_logprob=captured["batches"][0]["behaviour_logprob"])
    return numbers(cfg, ref, prog, leaves)


def numbers(cfg: Dict, ref: Dict, prog: Dict,
            leaves: Dict = None) -> Dict[str, float]:
    """The gaps between the reference's steps ``ref`` and what the
    program (or a stand-in) did, ``prog``: its losses, its RMSProp state
    after the first step (``ms1``), its weights after the last step
    (``params_last``) and the first batch's behaviour log-probs
    (``first_logprob``). ``leaves``, when given, receives each step's
    loss gap and the worst leaf of each leaf gap, which are not
    compared."""
    import jax

    from chipbench import reference as ref_lib

    decay = cfg["learning"]["rmsprop_decay"]
    loss_gaps = [abs(p - r) / max(abs(r), 1e-30)
                 for p, r in zip(prog["losses"], ref["losses"])]
    ref_g = ref_lib.leaf_norms(ref["first_grads"])
    med = float(np.median(list(ref_g.values())))
    keep = [k for k, v in ref_g.items() if v >= 1e-3 * med]
    # after one step ms = (1 - decay) g^2, so |g| = sqrt(ms / (1 - decay))
    prog_g = ref_lib.leaf_norms(jax.tree.map(
        lambda m: np.sqrt(np.maximum(np.asarray(m, np.float64), 0.0)
                          / (1 - decay)), prog["ms1"]["ms"]))
    grad_gaps = _leaf_gaps(prog_g, ref_g, keep)
    ref_d = ref_lib.leaf_norms(ref_lib.tree_delta(ref["params"],
                                                  ref["params0"]))
    prog_d = ref_lib.leaf_norms(ref_lib.tree_delta(prog["params_last"],
                                                   ref["params0"]))
    update_gaps = _leaf_gaps(prog_d, ref_d, keep)
    diff = ref_lib.leaf_norms(ref_lib.tree_delta(prog["params_last"],
                                                 ref["params"]))
    med_d = float(np.median([ref_d[k] for k in keep]))
    update_diff = float(np.median([diff[k] / max(ref_d[k], med_d)
                                   for k in keep]))
    if leaves is not None:
        leaves.update(loss_gaps=loss_gaps, grad=worst(grad_gaps),
                      update=worst(update_gaps),
                      dropped=sorted(set(ref_g) - set(keep)))
    act_gap = float(np.max(np.abs(
        np.asarray(prog["first_logprob"], np.float64)
        - np.asarray(ref["first_tlp"], np.float64))))
    return {"loss_gap": float(loss_gaps[0]),
            "grad_gap": float(np.median(list(grad_gaps.values()))),
            "update_gap": float(np.median(list(update_gaps.values()))),
            "update_diff": update_diff,
            "act_gap": act_gap}


def judge(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number the cell's limits name is finite and within its
    limit; a number a cell has no limit for is read, not compared."""
    return all(math.isfinite(values[k]) and values[k] <= lim
               for k, lim in limits.items())


def report_lines(values: Dict[str, float],
                 limits: Dict[str, float]) -> List[str]:
    return [f"{k} {values[k]!r} limit {limits[k]!r}" for k in limits]
