"""Small copies of the benchmark's cells, for runs on the CPU: the same
configuration files, traffic mixes and limits, at shapes a test holds."""
from __future__ import annotations

import time

from benchmarks.chip import harness

#: Only the size is cut.  Below about this size the program's
#: certificate can fall under the true residual (PERF.md, Open
#: questions), so a sound run would read not correct and every fault
#: would be caught by that alone.
SMALL = {
    "lfw_faces.q2": dict(m=2500, n=800, image=[50, 50, 1], tile_cols=100),
}


def small_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    cell.config = dict(cell.config, **SMALL[name])
    cell.traffic = dict(cell.traffic, k=10, K=20)
    return cell


def run_small(name: str, seed: int = 11, program=None,
              seconds: float = 0.3) -> dict:
    """A whole run of the small cell, skipping only the look for a chip."""
    return harness.run_cell(name, seed, seconds, False,
                            t_proc=time.perf_counter(), require_tpu=False,
                            program=program, cell=small_cell(name),
                            log=lambda msg: None)
