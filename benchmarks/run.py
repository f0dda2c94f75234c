"""Benchmark driver: one section per paper table/figure + framework
benches.  Prints ``name,value,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run [--only fig1,schedule,...]
      [--smoke] [--json out.json]

``--smoke`` runs sections that support it (``schedule``, ``stream``) at
tiny sizes — the CI guard that keeps benches importable and runnable.
``--json`` additionally writes every row machine-readably, which is what
``benchmarks/check_regression.py`` gates against the committed baselines
in ``benchmarks/baselines/`` (the bench trajectory: rel-err must never
silently regress).
"""
from __future__ import annotations

import argparse
import inspect
import json
import sys
import time

from benchmarks import (compress_bench, dist_svd_bench, fig1_random,
                        incremental_bench, schedule_bench, serve_bench,
                        sparse_bench, stream_bench, table1_images,
                        table1_words, tol_bench)
from benchmarks.common import use_compile_cache

SECTIONS = {
    "fig1": fig1_random.main,
    "table1_images": table1_images.main,
    "table1_words": table1_words.main,
    "compress": compress_bench.main,
    "dist_svd": dist_svd_bench.main,
    "incremental": incremental_bench.main,
    "schedule": schedule_bench.main,
    "serve": serve_bench.main,
    "sparse": sparse_bench.main,
    "stream": stream_bench.main,
    "tol": tol_bench.main,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated section names")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes (sections that support it)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write all rows as JSON (for the "
                         "regression gate)")
    args = ap.parse_args()
    use_compile_cache()
    names = (args.only.split(",") if args.only else list(SECTIONS))

    print("name,value,derived")
    failures = 0
    all_rows: list[dict] = []
    for name in names:
        t0 = time.time()
        rows: list[tuple] = []
        try:
            fn = SECTIONS[name]
            if "smoke" in inspect.signature(fn).parameters:
                fn(rows, smoke=args.smoke)
            else:
                fn(rows)
        except Exception as e:  # report loudly, keep going
            failures += 1
            rows.append((f"{name}_ERROR", type(e).__name__, str(e)[:200]))
        wall = time.time() - t0
        rows.append((f"{name}_wall_s", f"{wall:.1f}", ""))
        for row in rows:
            print(",".join(str(x) for x in row), flush=True)
            r = (tuple(row) + ("", ""))[:3]
            all_rows.append({"section": name, "name": str(r[0]),
                             "value": str(r[1]), "derived": str(r[2])})
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"meta": {"smoke": args.smoke, "sections": names},
                       "rows": all_rows}, f, indent=1)
        print(f"# wrote {len(all_rows)} rows to {args.json}",
              file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
