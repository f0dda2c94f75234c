"""From a profiler trace to the compact form the metric readers take.

``summarize`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
keeps, on one clock in nanoseconds:

- ``window``: from the start of the first job's host span to the end of
  the last one (the benchmark wraps each job in a ``TraceAnnotation``);
- ``jobs``: the number of those spans;
- ``devices``: for each device the run used, its operations as
  ``[name, start, duration]``, the name cut to the HLO instruction and
  its result type (``%matmul_rank1.1 = f32[13312,256]{1,0:T(8,128)}``);
- ``host``: the host's events inside the window as
  ``[name, start, duration]``, for naming idle gaps.

A summary is plain JSON, so a small recorded one can be kept beside the
readers' tests.  The readers and :func:`breakdown` work on it alone.
"""
from __future__ import annotations

import gzip
import json
from pathlib import Path

JOB_SPAN = "chipbench.job"

#: Lines of a TPU device plane that hold one event per XLA operation.
OP_LINES = ("XLA Ops",)


def find_xplane(trace_dir: Path) -> Path:
    paths = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _device_index(plane_name: str) -> int | None:
    # "/device:TPU:3" -> 3; other planes (host, TPU cores' sub-planes
    # such as "/device:TPU:0 SparseCore") are not devices of the run
    parts = plane_name.split(":")
    if len(parts) != 3 or not plane_name.startswith("/device:"):
        return None
    if parts[1] == "CPU" or not parts[2].isdigit():
        return None
    return int(parts[2])


def op_name(hlo: str) -> str:
    """An XLA op event's name (its whole HLO text) cut to the
    instruction and its result type."""
    head, sep, rest = hlo.partition(" = ")
    return head + sep + rest.split(" ", 1)[0] if sep else hlo


def summarize(path: Path, device_ids) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    jobs, host = [], []
    devices = {str(d): [] for d in device_ids}
    for plane in pd.planes:
        idx = _device_index(plane.name)
        if idx is not None and str(idx) in devices:
            ops = devices[str(idx)]
            for line in plane.lines:
                if line.name not in OP_LINES:
                    continue
                for ev in line.events:
                    ops.append([op_name(ev.name), int(ev.start_ns),
                                int(ev.duration_ns)])
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == JOB_SPAN:
                        jobs.append((int(ev.start_ns), int(ev.end_ns)))
                    else:
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    if not jobs:
        raise ValueError(f"no {JOB_SPAN!r} spans in {path}")
    w0, w1 = min(s for s, _ in jobs), max(e for _, e in jobs)
    host = [h for h in host if h[1] < w1 and h[1] + h[2] > w0]
    return {"window": [w0, w1], "jobs": len(jobs), "devices": devices,
            "host": host}


def save(summary: dict, path: Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(summary, f)


def load(path: Path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# -- interval arithmetic on the summary -------------------------------------

def clipped(ops, window):
    """(start, end) of each op, clipped to the window, empty ones out."""
    w0, w1 = window
    out = []
    for op in ops:
        s, e = max(op[1], w0), min(op[1] + op[2], w1)
        if e > s:
            out.append((s, e))
    return out


def union(intervals):
    """Disjoint, sorted union of (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(ops, window) -> int:
    return sum(e - s for s, e in union(clipped(ops, window)))


def gaps(ops, window):
    """Idle intervals of one device inside the window."""
    w0, w1 = window
    out, t = [], w0
    for s, e in union(clipped(ops, window)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if w1 > t:
        out.append((t, w1))
    return out


def overlap_ns(a, b) -> int:
    """Total length of the intersection of two disjoint sorted unions."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def breakdown(summary: dict, top: int = 10) -> dict:
    """The device operations that took most time (seconds, averaged over
    the devices) and the longest idle gaps of the first device, each
    named by the host event that overlaps it most."""
    window = summary["window"]
    devs = summary["devices"]
    per_name: dict[str, float] = {}
    for ops in devs.values():
        for name, s, d in ops:
            e = min(s + d, window[1])
            s = max(s, window[0])
            if e > s:
                per_name[name] = per_name.get(name, 0.0) + (e - s) * 1e-9
    ops_top = sorted(((n, t / len(devs)) for n, t in per_name.items()),
                     key=lambda x: -x[1])[:top]
    first = devs[min(devs, key=int)]
    host = summary["host"]
    named = []
    for g0, g1 in sorted(gaps(first, window), key=lambda g: g[0] - g[1])[
            :top]:
        best, best_ov, best_len = "host: no event", 0, 0
        for name, s, d in host:
            ov = min(g1, s + d) - max(g0, s)
            if ov > best_ov or (ov == best_ov and ov > 0 and d < best_len):
                best, best_ov, best_len = name, ov, d
        named.append((best, (g1 - g0) * 1e-9))
    return {"device_ops": [list(x) for x in ops_top],
            "idle_gaps": [list(x) for x in named]}
