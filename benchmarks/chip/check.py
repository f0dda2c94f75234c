"""The comparison that decides ``correct``.

Every job of the window is checked for what it says of itself: finite
singular values in descending order and a finite certificate.  A
uniform sample of the jobs, drawn from the seed, is compared with the
float32 reference (``reference.srsvd`` at ``Precision.HIGHEST``) run on
the same key, once the window has closed.  The numbers, each the worst
over the sampled jobs:

``sv_gap``
    ``max_i |S_i - S_ref_i| / S_ref_i`` over the k singular values.
``cert_short``
    ``(true - certificate) / true``: how far the job's posterior
    certificate falls below the true relative residual
    ``||Xbar - U S Vt||_F / ||Xbar||_F`` of its own factors.  The
    certificate's guarantee is that this is never above 0.
``resid_excess``
    ``(true - true_ref) / true_ref``: how much more residual the job's
    factors leave than the reference's.

The cell's ``workloads/<cell>.json`` lists which of them are compared,
each with its limit; :func:`readings` gives them all, for calibration.
"""
from __future__ import annotations

import math
import sys

import numpy as np

from benchmarks.chip import reference


def finite(v: float) -> float:
    """A reading as a finite number (JSON has no infinity or NaN): a
    NaN or infinite reading becomes the largest float, failing any
    limit."""
    return v if math.isfinite(v) else sys.float_info.max


def self_consistent(S, cert) -> bool:
    S = np.asarray(S, np.float64)
    c = float(cert)
    return bool(np.all(np.isfinite(S)) and math.isfinite(c) and c >= 0.0
                and S[-1] >= 0.0 and np.all(np.diff(S) <= 0.0))


def target(ds, traffic: dict):
    """The matrix the jobs factorized, as the reference reads it."""
    X = ds.dense()
    return reference.centred(X) if traffic["center"] else X


def readings(Xbar, traffic: dict, kept) -> dict:
    """The compared numbers, each the worst over the kept jobs."""
    worst: dict[str, float] = {}
    for _, key, (res, rep) in kept:
        ref, ref_rep = reference.srsvd(Xbar, key, k=traffic["k"],
                                       K=traffic["K"], q=traffic["q"])
        S, S_ref = np.asarray(res.S, np.float64), np.asarray(ref.S,
                                                             np.float64)
        r2, f2 = (float(v) for v in reference.residual2(
            Xbar, res.U, res.S, res.Vt))
        r2_ref, _ = (float(v) for v in reference.residual2(
            Xbar, ref.U, ref.S, ref.Vt))
        true, true_ref = math.sqrt(r2 / f2), math.sqrt(r2_ref / f2)
        cert = float(rep.posterior_rel_err)
        job = {"sv_gap": float(np.max(np.abs(S - S_ref) / S_ref)),
               "cert_short": (true - cert) / true,
               "resid_excess": (true - true_ref) / true_ref}
        for name, v in job.items():
            worst[name] = max(worst.get(name, -math.inf), finite(v))
    return worst


def compare(Xbar, cell, summaries, kept):
    """``({name: (value, limit)}, jobs failing their own checks, every
    reading)``: the compared numbers are those the cell has limits for."""
    bad = sum(not self_consistent(S, c) for _, S, c in summaries)
    limits = cell.workload["limits"]
    got = readings(Xbar, cell.traffic, kept) if kept else {}
    checks = {n: (got.get(n, finite(math.inf)), float(lim))
              for n, lim in limits.items()}
    return checks, bad, got
