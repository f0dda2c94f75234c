"""matmul_rank1_roofline: the dense contact kernel's share of its
roofline, in %.

For each of the kernel's events in the traced window, the least time
the chip could take is the larger of its logical bytes over the HBM
bandwidth and its operations over the bf16 peak (the kernel multiplies
float32 operands at bf16 precision); the share is the sum of least
times over the sum of the events' device times.  Bytes and operations
come from the cell's logical shapes (the unpadded X, its factor B, the
shift vectors and the output), not from the arrays the kernel is given,
so the share reads the same work whatever pads or tiles it.  At these
widths (about K/2 operations per byte of float32 X) the bound is HBM.
Moves ``factorize_ms``: the contacts are the largest item of a dense
job's device time.  The kernel is found by its HLO instruction name,
``matmul_rank1``, in the TPU trace.
"""
from __future__ import annotations

import re

#: The kernel's HLO instruction in a TPU trace: ``%matmul_rank1.1 =
#: f32[13312,256]{...} custom-call(...)`` (the op's own name, not an op
#: that reads its result).
KERNEL = re.compile(r"%?matmul_rank1(\.\d+)?\s*=\s*\w+\[(\d+),\d+\]")


def cost(rows: int, cols: int, K: int, transpose: bool,
         itemsize: int = 4) -> tuple[int, int]:
    """(operations, bytes) of one contact ``op(X) @ B - u w^T`` with X
    of shape (rows, cols): ``X @ B`` when not ``transpose``, else
    ``X^T @ B``."""
    inner, outer = (rows, cols) if transpose else (cols, rows)
    flops = 2 * rows * cols * K + 2 * outer * K
    nbytes = itemsize * (rows * cols          # X
                         + inner * K          # B
                         + outer + K          # u, w
                         + outer * K)         # output
    return flops, nbytes


def least_seconds(rows: int, cols: int, K: int, transpose: bool,
                  peaks: dict) -> float:
    flops, nbytes = cost(rows, cols, K, transpose)
    return max(nbytes / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])


def orientation(name: str, rows: int, cols: int) -> bool | None:
    """Whether a kernel event is the transposed contact (``X^T @ B``),
    told by its output's leading dimension (padded to tiles: the one of
    rows and cols it lies nearer); None for an event of another op."""
    hit = KERNEL.match(name)
    if hit is None:
        return None
    lead = int(hit.group(2))
    return abs(lead - cols) < abs(lead - rows)


def read(summary: dict, info: dict) -> float | None:
    cfg, traffic = info["cell"].config, info["cell"].traffic
    m, n, K = cfg["m"], cfg["n"], traffic["K"]
    devs = summary["devices"]
    w0, w1 = summary["window"]
    least = measured = 0.0
    for name, start, dur in devs[min(devs, key=int)]:
        t = orientation(name, m, n)
        if t is None or start < w0 or start + dur > w1:
            continue
        least += least_seconds(m, n, K, t, info["peaks"])
        measured += dur * 1e-9
    if measured <= 0:
        return None
    return 100.0 * least / measured
