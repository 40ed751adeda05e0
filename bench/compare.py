"""The comparison that decides ``correct``, and the trace's breakdown.

Numbers compared between what the program's first rounds produced and
what the plain reference produced from the same seed (all relative, so
they carry over between sizes; the rounds are ``run.check_rounds``):

  loss     worst round of |loss_p - loss_r| / |loss_r|
  client_loss  worst client of the same, over each client's loss in
           round 1 (the state's ``losses``)
  grad     worst leaf of | ||v_p|| - ||v_r|| | / max(||v_r||, median leaf's)
           for round 1's momentum: the first gradients as the optimizer
           accumulated them, read from the state after one round
  change   the same for the change of the bank after round 3, over the
           leaves whose round-1 reference momentum is at least a
           thousandth of the median leaf's (a leaf the reference leaves
           still moves by round-off alone)
  eval     |test_loss_p - test_loss_r| / |test_loss_r|, the first in-scan
           eval
  weights  worst client of |w_p - w_r| / w_r, push-sum weights at the end

A leaf's norm is taken over all clients.  The gap of two norms, not the
norm of a difference, is compared: the program and the reference do not
follow one trajectory to the last bit (a ReLU network amplifies round-off),
but they move every leaf by the same amount.
"""
from __future__ import annotations

import numpy as np

from bench import devtrace

__all__ = ["gaps", "judge", "breakdown"]

QUIET_LEAF = 1e-3  # a leaf's reference momentum under this share of the median's


def _leaf_gap(p, r, keep=None):
    p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
    if keep is not None:
        p, r = p[keep], r[keep]
    scale = np.maximum(r, np.median(r))
    return float(np.max(np.abs(p - r) / np.maximum(scale, 1e-30)))


def gaps(prog: dict, ref: dict) -> dict:
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    cr = np.asarray(ref["client_losses"], np.float64)
    v_r = np.asarray(ref["v"], np.float64)
    keep = v_r >= QUIET_LEAF * np.median(v_r)
    w_p = np.asarray(prog["w"], np.float64)
    w_r = np.asarray(ref["w"], np.float64)
    out = {
        "loss": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "client_loss": float(np.max(
            np.abs(np.asarray(prog["client_losses"], np.float64) - cr)
            / np.abs(cr))),
        "eval": float(abs(prog["test_loss"] - ref["test_loss"])
                      / abs(ref["test_loss"])),
        "grad": _leaf_gap(prog["v"], v_r),
        "change": _leaf_gap(prog["dx"], ref["dx"], keep),
        "weights": float(np.max(np.abs(w_p - w_r) / np.abs(w_r))),
    }
    # A non-finite reading fails every limit.
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}


def judge(numbers: dict, limits: dict):
    """({name: {"value", "limit"}} for every number that has a limit,
    whether all are within their limits)."""
    checks = {k: {"value": numbers[k], "limit": float(limits[k])}
              for k in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return checks, ok


def breakdown(t: devtrace.Trace, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps by what the host was doing, on the first device."""
    d = t.devices[0]
    ops = sorted(devtrace.op_totals(t, d).items(), key=lambda kv: -kv[1])
    gaps = devtrace.idle_gaps(t, d, top)
    return {"device_ops": [[name, ns / 1e9] for name, ns in ops[:top]],
            "idle_gaps": [[name, (b - a) / 1e9] for a, b, name in gaps[:top]]}
