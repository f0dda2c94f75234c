"""shift_update_idle_ms: device idle time inside the shift update
(Algorithm 1, lines 5-7: the Givens ``qr_rank1_update``), in ms a job.

The device's idle intervals in the traced window (as
``device_idle_share`` finds them) that overlap the program's
``repro.shift_update`` host spans, averaged over the devices, over the
jobs in the window.  A program without ``repro.*`` spans reads
nothing.  Moves ``factorize_ms``: the update runs two eager
``lax.fori_loop`` calls, which are lowered again on every job while
the device waits.
"""
from __future__ import annotations

from benchmarks.chip import tracefile

SPAN = "repro.shift_update"
JOB = "repro.factorize"


def read(summary: dict, info: dict) -> float | None:
    host, window = summary["host"], summary["window"]
    devs = summary["devices"]
    if not any(h[0] == JOB for h in host) or not any(devs.values()):
        return None
    spans = tracefile.union(tracefile.clipped(
        [h for h in host if h[0] == SPAN], window))
    idle = [tracefile.overlap_ns(tracefile.gaps(ops, window), spans)
            for ops in devs.values()]
    return sum(idle) / len(idle) * 1e-6 / summary["jobs"]
