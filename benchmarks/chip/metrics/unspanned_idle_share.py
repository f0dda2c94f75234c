"""unspanned_idle_share: the share of the traced window in which the
device is idle and the host is in no ``repro.*`` span, in %.

The device's idle intervals (as ``device_idle_share`` finds them)
intersected with the intervals no ``repro.*`` host span covers,
averaged over the devices, over the window.  What it counts is idle
time the program's spans cannot name: the caller's code between jobs,
or a path of the program without a span.  A program without
``repro.*`` spans reads nothing.  Moves ``factorize_ms``.
"""
from __future__ import annotations

from benchmarks.chip import tracefile

PREFIX = "repro."


def read(summary: dict, info: dict) -> float | None:
    w0, w1 = window = summary["window"]
    devs = summary["devices"]
    spans = [h for h in summary["host"] if h[0].startswith(PREFIX)]
    if not spans or w1 <= w0 or not any(devs.values()):
        return None
    outside = tracefile.gaps(spans, window)
    idle = [tracefile.overlap_ns(tracefile.gaps(ops, window), outside)
            for ops in devs.values()]
    return 100.0 * sum(idle) / len(idle) / (w1 - w0)
