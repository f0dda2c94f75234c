"""x_passes_per_job: how many times a timed job reads X.

Each ``repro.contact`` host span marks one of the contact engine's
products or reductions over X: the operator contact points, the column
mean and ``||X||_F^2``.  A contact composed of others
(``shifted_gram_matmat`` of two passes on a dense operator,
``project_residual`` of one) holds their spans, and its passes are the
contacts inside it, so only the leaf spans, those that hold no other
contact, are counted; a single-pass contact, such as the blocked Gram
product, is a leaf and counts once.  The count of leaf contact spans in
the traced window over the jobs in it.  A program without
``repro.factorize`` spans reads nothing.  Moves ``factorize_ms``: the
dense contacts are bound by HBM, so a pass saved is a read of X saved.
"""
from __future__ import annotations

SPAN = "repro.contact"
JOB = "repro.factorize"


def read(summary: dict, info: dict) -> float | None:
    host = summary["host"]
    if not any(h[0] == JOB for h in host) or not summary["jobs"]:
        return None
    w0, w1 = summary["window"]
    # one host thread makes the contacts, so spans nest: after sorting by
    # start (longest first), a span's first child comes right after it
    contacts = sorted(((s, s + d) for name, s, d in host
                       if name == SPAN and w0 <= s < w1),
                      key=lambda c: (c[0], -c[1]))
    leaves = sum(1 for (_, e), nxt in zip(contacts, contacts[1:] + [None])
                 if nxt is None or nxt[0] >= e)
    return leaves / summary["jobs"]
