"""lowerings_per_job: how many programs the host lowers to MLIR in a
timed job.

The program writes a ``repro.lowered`` marker span on each lowering
(``repro.obs``); the count of markers in the traced window over the
jobs in it, 0.0 when there are none.  A program that writes no
``repro.factorize`` span (one without ``repro.obs``) reads nothing.
Moves ``factorize_ms``: a lowering in a job is host time the device
waits through, and a read from the compile cache besides.
"""
from __future__ import annotations

MARKER = "repro.lowered"
JOB = "repro.factorize"


def read(summary: dict, info: dict) -> float | None:
    names = [h[0] for h in summary["host"]]
    if JOB not in names or not summary["jobs"]:
        return None
    return names.count(MARKER) / summary["jobs"]
