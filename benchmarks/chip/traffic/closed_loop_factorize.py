"""Closed loop: one caller factorizes the cell's matrix again and again.

Each job calls the program with a fresh key, ``fold_in(job_key, j)``,
and ends in ``jax.block_until_ready`` on the whole result, factors and
report.  The next job starts when the last one has returned.  The
traffic file gives ``k``, ``K``, ``q``, ``center`` and ``warmup`` (jobs
run before the window, with keys of their own, so every shape the
window uses is compiled in set-up).

The loop keeps every job's singular values and certificate, and the
whole output of a uniform sample of ``keep`` jobs (reservoir sampling
from the run's seed), for the check that follows the window.  A job
that enters the sample is copied to host memory there, so the sample
holds no device memory; the copies' time is inside the window and is
reported as ``kept_copy_s``.  It measures ``factorize_ms``: the window
over the jobs completed in it.  Each job's latency, submission to
``block_until_ready``, is kept in the log.
"""
from __future__ import annotations

import random
import time

import jax


def run(program, ds, traffic: dict, *, key, seed: int, seconds: float,
        keep: int, annotate, on_warm=None):
    """Warm up, then run jobs for ``seconds``; returns the job log."""
    kw = dict(K=traffic["K"], q=traffic["q"], center=traffic["center"],
              **ds.kwargs)
    k = traffic["k"]
    job_key, warm_key = jax.random.split(key)

    def job(jkey):
        out = program(ds.operand, k, key=jkey, **kw)
        jax.block_until_ready(out)
        return out

    for i in range(traffic["warmup"]):
        job(jax.random.fold_in(warm_key, i))
        if on_warm is not None:
            on_warm(i)
    rng = random.Random(seed)
    kept, summaries, latencies, errors = [], [], [], []
    copy_s = 0.0
    t0 = time.perf_counter()
    j = 0
    while time.perf_counter() - t0 < seconds:
        jkey = jax.random.fold_in(job_key, j)
        ts = time.perf_counter()
        with annotate(j):
            try:
                out = job(jkey)
            except Exception as e:   # a failed job is counted, not fatal
                out = None
                errors.append(f"job {j}: {type(e).__name__}: {e}")
        latencies.append(time.perf_counter() - ts)
        if out is not None:
            res, rep = out
            summaries.append((j, res.S, rep.posterior_rel_err))
            n = len(summaries)
            r = n - 1 if n <= keep else rng.randrange(n)
            if r < keep:
                tc = time.perf_counter()
                entry = (j, jkey, jax.device_get(out))
                copy_s += time.perf_counter() - tc
                if r < len(kept):
                    kept[r] = entry
                else:
                    kept.append(entry)
        j += 1
    window = time.perf_counter() - t0
    metrics = {} if not summaries else {
        "factorize_ms": 1e3 * window / len(summaries)}
    return {"attempted": j, "errors": errors, "latencies": latencies,
            "window_s": window, "kept_copy_s": copy_s,
            "summaries": summaries, "metrics": metrics,
            "kept": sorted(kept, key=lambda e: e[0])}
