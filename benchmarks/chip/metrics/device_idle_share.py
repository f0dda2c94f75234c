"""device_idle_share: the share of the traced window in which no
operation ran on the device, averaged over the devices the cell uses.

1 - (union of the device's operation intervals) / (the window), in %.
Moves ``factorize_ms``: idle device time is time a job waits on the
host (dispatch, lowering, host-to-device copies).
"""
from __future__ import annotations

from benchmarks.chip import tracefile


def read(summary: dict, info: dict) -> float | None:
    w0, w1 = summary["window"]
    devs = summary["devices"]
    if w1 <= w0 or not any(devs.values()):
        return None
    idle = [1.0 - tracefile.busy_ns(ops, (w0, w1)) / (w1 - w0)
            for ops in devs.values()]
    return 100.0 * sum(idle) / len(idle)
