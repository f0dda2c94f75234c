"""Chip benchmark of the shifted randomized SVD: one run of one cell.

    python3 benchmarks/chip/run.py --workload lfw_faces.q2 --seed 7 \
        --seconds 30 --trace 0

Run from the root of a checkout, on a machine holding the chips the
cell asks for.  The run makes the cell's matrix on the device from the
seed, warms up every shape it will use (set-up, which ends where the
first timed job starts), factorizes for ``--seconds``, then checks a
sample of the timed jobs against the plain reference.  The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``, each compared number with its limit.  The same numbers
close standard error.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.chip import harness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_proc=T_PROC, log=log)
    except harness.NoChip as e:
        log(f"run.py: {e}")
        return 2
    for name, c in out["checks"].items():
        log(f"check {name}={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
