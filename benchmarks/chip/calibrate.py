"""Readings that the limits of ``correct`` are set from.

    python3 benchmarks/chip/calibrate.py --workload lfw_faces.q2 \
        --seeds 12 --control-seeds 3 --seconds 3

In one process, on the chip: a short run of the cell (the same set-up,
window and check as ``run.py``, at the cell's own size) for each of
``--seeds`` seeds, then the same runs with the control (the bfloat16
reference put in the program's place) for ``--control-seeds`` more.
Prints one JSON line per run with every reading of ``check.py``; a
limit lies above the largest program reading and below the smallest
control reading.  The benchmark's own runs never run the control.
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
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--first-seed", type=int, default=1_000_003)
    args = ap.parse_args(argv)

    from benchmarks.chip import harness, reference

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    runs = [(args.first_seed + i, "program") for i in range(args.seeds)]
    runs += [(args.first_seed + 1000 + i, "control")
             for i in range(args.control_seeds)]
    for seed, who in runs:
        t0 = time.perf_counter()
        out = harness.run_cell(
            args.workload, seed, args.seconds, False, t_proc=t0, log=log,
            program=reference.control if who == "control" else None)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "who": who, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "factorize_ms": out["metrics"].get(
                              "factorize_ms", {}).get("value"),
                          "readings": out["readings"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
