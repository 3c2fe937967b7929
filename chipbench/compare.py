"""The comparison that decides `correct`: the program against the reference.

What is compared is what the timed path produced over the first
``compare_chunks`` chunks of a run (set-up drives them through the window's
own chunk program, on chunks that all differ), and the privacy ledger of
the whole run. Each number has its own limit, in the configuration file,
set from readings of sound runs and of the control (PERF.md gives them).
`judge` holds a run to every name that the configuration's ``limits`` gives;
`readings` gives the linear learner's numbers (`chipbench.drivers`' default
driver):

  loss_gap        widest |loss| gap over the rounds and nodes
  w_bar_loss_gap  widest |w_bar_loss| gap over the rounds
  sparsity_gap    widest sparsity gap over the rounds, in entries of w
  correct_flips   (round, node) predictions that differ
  w_gap           widest |w| gap after the last compared round
  eps_gap         widest gap of the cumulative eps ledger, whole run
"""
from __future__ import annotations

import math

import numpy as np

NUMBERS = ("loss_gap", "w_bar_loss_gap", "sparsity_gap", "correct_flips",
           "w_gap", "eps_gap")


def readings(prog: dict, ref: dict, *, entries: int) -> dict:
    """The numbers compared, for program outputs ``prog`` and reference
    outputs ``ref`` (per-round arrays over the same rounds, ``w`` both on
    the device or both on the host, ``eps`` the two ledgers)."""
    import jax.numpy as jnp

    def gap(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        if a.shape != b.shape:
            return math.inf
        return float(np.max(np.abs(a - b))) if a.size else 0.0

    w_gap = (float(jnp.max(jnp.abs(prog["w"] - ref["w"])))
             if prog["w"].shape == ref["w"].shape else math.inf)
    flips = (int(np.count_nonzero(np.asarray(prog["correct"])
                                  != np.asarray(ref["correct"])))
             if np.shape(prog["correct"]) == np.shape(ref["correct"])
             else math.inf)
    return {
        "loss_gap": gap(prog["loss"], ref["loss"]),
        "w_bar_loss_gap": gap(prog["w_bar_loss"], ref["w_bar_loss"]),
        "sparsity_gap": gap(prog["sparsity"], ref["sparsity"]) * entries,
        "correct_flips": flips,
        "w_gap": w_gap,
        "eps_gap": gap(prog["eps"], ref["eps"]),
    }


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value": v, "limit": l}}) for every name in
    ``limits``: each number within its limit; a number that is not finite,
    or that ``values`` lacks (read as inf), fails."""
    checks, ok = {}, True
    for name, lim in limits.items():
        v, lim = values.get(name, math.inf), float(lim)
        passed = math.isfinite(v) and v <= lim
        ok = ok and passed
        checks[name] = {"value": v, "limit": lim}
    return ok, checks
