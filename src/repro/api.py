"""repro.api — the one front door for factorization (DESIGN.md §15).

Every caller-facing path — batch scripts, examples, and the serving
layer (``launch/factor_serve.py``) — factors matrices through this
module; the entry points underneath (``srsvd`` / ``dist_srsvd`` /
``dist_srsvd_streamed`` / ``svd_jit``) are the plumbing layer.  The
seam this erases: ``srsvd(stop=None)`` returns a bare ``SVDResult``
while ``srsvd(stop=...)`` returns a pair — :func:`factorize` **always**
returns ``(SVDResult, ConvergenceReport)``, attaching a bit-for-bit
``FixedIters`` monitor when the caller brings no rule, so every
factorization carries its posterior error certificate (the per-request
quality SLA of the serving layer).

Routing, by operator family:

  dense arrays / DenseOp / SparseOp /       ``srsvd`` (single device)
  CSRMatrix / BlockedOp / ChainedOp
  (CSR)ShardedBlockedOp        + mesh       ``dist_srsvd_streamed``
  RowShardedBlockedOp          + mesh       ``dist_srsvd_streamed``
                                            (``shard_axis="rows"``)
  large dense array            + mesh       ``dist_srsvd`` (size >=
                                            ``REPRO_DIST_DENSE_MIN_SIZE``
                                            elements, default 16384;
                                            smaller arrays take the
                                            single-device path even
                                            when a mesh is offered —
                                            the collective overhead
                                            dominates below that)

``tol=`` replaces ``k`` with a target certified residual: the adaptive
range finder discovers the rank (DESIGN.md §16).  Same routing table —
sharded blocked operators stream through ``dist_srsvd_tol_streamed``,
everything else runs ``srsvd_tol`` (a dense array always fits on the
single device that would drive the adaptive host loop anyway).

:class:`FactorizationRequest` / :class:`FactorizationResult` live here
— not in the server — so offline scripts and the server serialize the
same objects; :func:`run_request` executes one request through exactly
the routing above.  :func:`factorize_batched` is the device-batching
primitive (vmapped ``srsvd`` over stacked same-shape operators) the
server's coalescing loop uses, and :func:`refresh_block` /
:func:`refresh_rank1` are the cache-adjacent fast paths: refresh a
cached factorization after a declared rank-b update (plus the
mean-shift correction when the column mean itself moved) via the
Givens thin-QR block update (``core/qr_update.py``) plus one
projection contact — no fresh sample, no power passes.  For coarser
drift, ``factorize(warm_start=prior)`` seeds a fresh sketch from the
prior basis instead (DESIGN.md §17).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as onp

from repro import obs
from repro.core import contact
from repro.core.distributed import (dist_col_mean, dist_srsvd,
                                    dist_srsvd_streamed,
                                    dist_srsvd_tol_streamed)
from repro.core.fingerprint import Fingerprint, array_token, fingerprint
from repro.core.linop import (LinOp, RowShardedBlockedOp,
                              ShardedBlockedOp, as_linop)
from repro.core.qr_update import qr_block_update
from repro.core.schedule import ShiftSchedule, resolve_shift
from repro.core.srsvd import (SVDResult, batched_trace_count,
                              srsvd, srsvd_batched, srsvd_tol)
from repro.core.stopping import (ConvergenceReport, FixedIters, StopRule,
                                 as_rule, posterior_rel_err)

__all__ = [
    "FactorizationRequest", "FactorizationResult", "Fingerprint",
    "batched_trace_count", "factorize", "factorize_batched",
    "fingerprint", "refresh_block", "refresh_rank1",
    "request_cache_key", "run_request", "split_batched",
]


def _resolve_key(key, seed: int):
    return jax.random.PRNGKey(seed) if key is None else key


def _warm_vt(warm_start):
    """Normalize a ``warm_start`` argument down to a prior ``Vt`` (or
    None): accepts a :class:`FactorizationResult`, the ``(SVDResult,
    report)`` pair :func:`factorize` returns, a bare ``SVDResult``, or
    a raw ``(k_prior, n)`` array."""
    if warm_start is None:
        return None
    if isinstance(warm_start, FactorizationResult):
        if warm_start.result is None:
            raise ValueError(
                "warm_start FactorizationResult carries no factors "
                f"(failed request: {warm_start.error!r})")
        warm_start = warm_start.result
    if isinstance(warm_start, tuple):
        warm_start = warm_start[0]
    return getattr(warm_start, "Vt", warm_start)


#: Dense arrays smaller than this many elements stay on the single
#: device even when a mesh is offered — below it the collective setup
#: costs more than the factorization.  Env-overridable per process.
DIST_DENSE_MIN_SIZE = 16384


def _dist_dense_min_size() -> int:
    v = os.environ.get("REPRO_DIST_DENSE_MIN_SIZE")
    return DIST_DENSE_MIN_SIZE if v is None else int(v)


@obs.spanned("repro.factorize")
def factorize(x_or_op, k: int | None = None, *, K: int | None = None,
              q: int = 0, tol: float | None = None, b: int = 8,
              max_K: int | None = None, mu=None, center: bool = False,
              shift: ShiftSchedule | jax.Array | None = None,
              stop: StopRule | int | None = None,
              mesh=None, key: jax.Array | None = None, seed: int = 0,
              row_axis: str = "model", col_axis: str = "data",
              warm_start=None,
              engine: contact.ContactEngine | None = None,
              ) -> tuple[SVDResult, ConvergenceReport]:
    """Factorization of ``X - mu 1^T`` for any operator family: rank-k
    with ``k=``, or tolerance-first adaptive rank with ``tol=``
    (exactly one of the two).

    Args:
      x_or_op: dense array, ``CSRMatrix``, BCOO, any ``LinOp``
        (including the out-of-core blocked / sharded operators) — the
        family picks the execution path, the caller never does.
      k / K / q: target rank, sampling rank (default 2k), power-
        iteration ceiling.
      tol / b / max_K: instead of ``k``, a target certified relative
        residual — the adaptive range finder (DESIGN.md §16) grows the
        basis ``b`` columns at a time (capped at ``max_K``) until the
        certificate clears ``tol``, and the report's ``k_found`` is
        the discovered rank.  Mutually exclusive with ``k``, ``K``,
        and ``stop`` (the certificate IS the stop rule).
      mu: (m,) shifting vector, or None.  ``center=True`` computes the
        column mean through the operator protocol instead (sparse- and
        stream-safe) and shifts by it — implicit-centering PCA.
      shift: a :class:`~repro.core.schedule.ShiftSchedule` for the
        power iterations, or a shifting vector (equivalent to ``mu``).
      stop: a :class:`~repro.core.stopping.StopRule`, an int
        (``FixedIters`` shorthand), or None — None attaches a
        bit-for-bit ``FixedIters`` monitor, so the return value is
        **always** the pair ``(SVDResult, ConvergenceReport)`` and
        every caller gets the posterior certificate.  (Operators with
        no ``fro_norm2`` probe — e.g. a bare ``CallableOp`` — must
        pass ``FixedIters(certificate=False)`` explicitly.)
      mesh: route distributed: sharded blocked operators stream via
        ``dist_srsvd_streamed`` (``dist_srsvd_tol_streamed`` under
        ``tol=``; each host reads its own range); a dense global array
        runs the resident-shard ``dist_srsvd`` over (``row_axis``,
        ``col_axis``) when it has at least ``REPRO_DIST_DENSE_MIN_SIZE``
        elements (default 16384) — smaller arrays take the
        single-device path, byte-identical to calling with no mesh.
      key / seed: PRNG key for the Gaussian test matrix; ``key`` wins,
        else ``PRNGKey(seed)``.  Same key => same factors as the
        underlying path, which is what the serving layer's cache and
        parity gates lean on.
      warm_start: a prior factorization of a nearby matrix to seed the
        sketch from (DESIGN.md §17) — a prior
        :class:`FactorizationResult`, the ``(SVDResult, report)`` pair
        this function returns, a bare ``SVDResult``, or a raw ``Vt``
        (k_prior, n).  The sketch's leading columns become the prior
        right singular vectors padded with ``fold_in`` fresh
        Gaussians, so a refresh of slightly-changed data converges in
        ~1 power pass (~1 disk pass per host range on the streamed
        sharded paths) with the stop rule certifying when.  Fixed-k
        only (``tol=`` grows its own residual-directed basis —
        ``ValueError``); the resident-shard dense+mesh path above the
        size threshold runs cold with the warm start dropped (its
        sketch is collective-internal) — the forced-cold cases are
        listed in DESIGN.md §17.
      engine: contact engine override (single-device paths).
    """
    if (k is None) == (tol is None):
        raise ValueError(
            "pass exactly one of k (fixed rank) or tol (adaptive rank)"
            f" — got k={k!r}, tol={tol!r}")
    if tol is not None and (K is not None or stop is not None):
        raise ValueError(
            "tol= discovers the rank under its own certificate — K and "
            "stop rules belong to the fixed-k path")
    if tol is not None and warm_start is not None:
        raise ValueError(
            "warm_start seeds a fixed-K sketch; the tol= path grows "
            "its basis against the residual instead — pass k= to "
            "warm-start a refresh (DESIGN.md §17)")
    warm_start = _warm_vt(warm_start)
    rule = as_rule(stop)
    if rule is None:
        rule = FixedIters()
    key = _resolve_key(key, seed)
    if center and mu is not None:
        raise ValueError("pass either center=True or an explicit mu, "
                         "not both")
    mu, sched = resolve_shift(mu, shift)
    if mesh is not None:
        if isinstance(x_or_op, RowShardedBlockedOp):
            if center and mu is None:
                with obs.span("repro.center"):
                    mu = x_or_op.col_mean()
            if tol is not None:
                return dist_srsvd_tol_streamed(
                    x_or_op, mu, tol, b=b, max_K=max_K, mesh=mesh,
                    key=key, shift=sched, shard_axis="rows",
                    row_axis=row_axis, engine=engine)
            return dist_srsvd_streamed(
                x_or_op, mu, k, K, q, mesh=mesh, key=key, shift=sched,
                stop=rule, shard_axis="rows", row_axis=row_axis,
                warm_start=warm_start, engine=engine)
        if isinstance(x_or_op, ShardedBlockedOp):
            if center and mu is None:
                with obs.span("repro.center"):
                    mu = x_or_op.col_mean()
            if tol is not None:
                return dist_srsvd_tol_streamed(
                    x_or_op, mu, tol, b=b, max_K=max_K, mesh=mesh,
                    key=key, shift=sched, col_axis=col_axis,
                    row_axis=row_axis, engine=engine)
            return dist_srsvd_streamed(
                x_or_op, mu, k, K, q, mesh=mesh, key=key, shift=sched,
                stop=rule, col_axis=col_axis, row_axis=row_axis,
                warm_start=warm_start, engine=engine)
        if isinstance(x_or_op, LinOp):
            raise TypeError(
                "factorize(mesh=...) routes sharded blocked operators "
                "or dense global arrays; got "
                f"{type(x_or_op).__name__} — drop mesh for the "
                "single-device paths or wrap per-host ranges in a "
                "(Row)ShardedBlockedOp")
        # Dense + mesh: worth the collectives only at scale.  Small
        # arrays fall through to the single-device path below —
        # byte-identical factors to a no-mesh call (the routing gate
        # test pins this).  The adaptive path always falls through: a
        # dense array fits on the single device that would have to
        # drive the adaptive host loop anyway.
        if tol is None and int(onp.prod(jnp.shape(x_or_op))) \
                >= _dist_dense_min_size():
            if center and mu is None:
                with obs.span("repro.center"):
                    mu = dist_col_mean(x_or_op, mesh, row_axis, col_axis)
            # Forced-cold case (DESIGN.md §17): the resident-shard
            # collective draws its sketch inside the shard_map, so the
            # warm start is dropped and the solve runs cold.
            return dist_srsvd(x_or_op, mu, k, K, q, mesh=mesh, key=key,
                              shift=sched, stop=rule, row_axis=row_axis,
                              col_axis=col_axis)
    op = as_linop(x_or_op)
    eng = engine if engine is not None else contact.get_engine()
    if center and mu is None:
        with obs.span("repro.center"):
            mu = eng.col_mean(op)
    if tol is not None:
        return srsvd_tol(op, mu, tol=tol, b=b, q=q, key=key,
                         max_K=max_K, shift=sched, engine=eng)
    return srsvd(op, mu, k, K, q, key=key, shift=sched, stop=rule,
                 warm_start=warm_start, engine=eng)


def factorize_batched(Xs, mus, k: int, *, K: int | None = None,
                      q: int = 0, keys: jax.Array,
                      shift: ShiftSchedule | None = None,
                      stop: StopRule | int | None = None,
                      ) -> tuple[SVDResult, ConvergenceReport]:
    """Batched :func:`factorize` over (B, m, n) stacked dense jobs.

    One vmapped trace serves every batch with the same static signature
    (shape, dtype, B, k, K, q, shift, stop) — the coalescing primitive
    behind the serving layer's small-job slots.  Always returns the
    ``(SVDResult, ConvergenceReport)`` pair with a leading batch axis
    on every leaf, exactly like :func:`factorize` per slice.
    """
    rule = as_rule(stop)
    if rule is None:
        rule = FixedIters()
    return srsvd_batched(Xs, mus, k, K, q, keys=keys, shift=shift,
                         stop=rule)


def refresh_block(base: SVDResult, x_new, U_b, W_b, *, mu=None,
                  mu_prev=None,
                  engine: contact.ContactEngine | None = None,
                  ) -> tuple[SVDResult, ConvergenceReport]:
    """Refresh a rank-k factorization after ``X_new = X_old + U_b W_b^T``
    (a declared rank-b update), folding in the mean-shift correction
    when the shifting vector itself moved.

    The cache-adjacent fast path (DESIGN.md §15, §17): instead of a
    fresh Gaussian sample plus q power passes over ``X_new``, fold the
    declared update into the cached basis with the Givens thin-QR block
    update — ``Y_new V = U diag(S) + U_b (Vt W_b)`` — then run ONE
    projection contact against the new operator.  Total cost: O(m k b)
    for the QR updates + one ``shifted_rmatmat``; for blocked/streamed
    operators that is one disk pass instead of ``2 + 2q``.

    ``mu`` is the shifting vector for the NEW matrix and ``mu_prev``
    the one the cached ``base`` was factored against.  When
    ``mu_prev`` is given, the correction ``-(mu - mu_prev) 1^T`` is
    folded in as one more update column (DESIGN.md §17) — the cached
    basis is rotated from the old centering to the new one without
    recomputing, so appended rows that moved the column mean cost
    nothing extra.  ``U_b=None`` (with ``W_b=None``) runs the pure
    mean-shift refresh.

    Accuracy: exact when ``span(U, U_b, mu - mu_prev)`` contains the
    range of ``X_new - mu 1^T`` (e.g. a low-rank matrix plus a rank-b
    edit); otherwise the returned report's ``posterior_rel_err``
    certifies exactly how much the refreshed basis captures — a caller
    seeing it degrade resubmits a full :func:`factorize`.

    ``b=1`` with vector ``U_b``/``W_b`` and no ``mu_prev`` is exactly
    :func:`refresh_rank1` (which delegates here).
    """
    op = as_linop(x_new)
    eng = engine if engine is not None else contact.get_engine()
    U, S, Vt = base.U, base.S, base.Vt
    k = int(S.shape[0])
    m, n = U.shape[0], Vt.shape[1]
    if (U_b is None) != (W_b is None):
        raise ValueError("pass U_b and W_b together (or both None for "
                         "a pure mean-shift refresh)")
    if U_b is None:
        U_b = jnp.zeros((m, 0), U.dtype)
        W_b = jnp.zeros((n, 0), Vt.dtype)
    U_b = jnp.asarray(U_b, U.dtype)
    W_b = jnp.asarray(W_b, Vt.dtype)
    if U_b.ndim == 1:
        U_b = U_b[:, None]
    if W_b.ndim == 1:
        W_b = W_b[:, None]
    if U_b.shape[1] != W_b.shape[1]:
        raise ValueError("refresh_block needs matching update widths, "
                         f"got U_b {U_b.shape} vs W_b {W_b.shape}")
    if mu_prev is not None:
        # Xbar_new = Xbar_old + U_b W_b^T - (mu - mu_prev) 1^T: the
        # mean shift IS one more rank-1 update column (DESIGN.md §17).
        d = ((jnp.zeros((m,), U.dtype) if mu is None
              else jnp.asarray(mu, U.dtype))
             - jnp.asarray(mu_prev, U.dtype))
        U_b = jnp.concatenate([U_b, -d[:, None]], axis=1)
        W_b = jnp.concatenate([W_b, jnp.ones((n, 1), Vt.dtype)], axis=1)
    b = int(U_b.shape[1])
    if b == 0:
        raise ValueError("refresh_block got an empty update: pass "
                         "U_b/W_b, mu_prev, or both")
    # U diag(S) is already a thin QR (diag is upper triangular), so the
    # update lands directly on the cached factors, column by column.
    Q, _ = qr_block_update(U, jnp.diag(S), U_b,
                           contact.basis_matmul(Vt, W_b))
    # Q spans (X_new) V_old — k dims.  Append an orthonormal basis of
    # the update block's component orthogonal to it so the final basis
    # spans span(U, U_b) ⊇ range(X_new) whenever the base was
    # (numerically) exact; the subsequent truncation is then the
    # *optimal* rank-k of X_new.  Two deflation passes (CGS2 — "twice
    # is enough", as in the adaptive range finder), then an SVD of the
    # residual block instead of per-column normalization: the Givens
    # update already rotated most of each update column into Q, so
    # in-span columns leave residuals of pure float32 cancellation
    # noise — normalizing those would feed basis-destroying junk into
    # Q (after which the certificate identity silently over-counts
    # captured energy).  The SVD pushes noise into trailing singular
    # values, which the eps^(2/3)-scaled gate zeroes; zero columns are
    # harmless in the projection below.
    mm = contact.basis_matmul
    Rb = U_b - mm(Q, mm(Q.T, U_b))
    Rb = Rb - mm(Q, mm(Q.T, Rb))
    Ub_o, sv, _ = jnp.linalg.svd(Rb, full_matrices=False)
    tau = jnp.finfo(U.dtype).eps ** (2.0 / 3.0) * jnp.linalg.norm(U_b)
    Q = jnp.concatenate([Q, Ub_o * (sv > tau)[None, :].astype(U.dtype)],
                        axis=1)
    Y = eng.shifted_rmatmat(op, Q, mu).T                    # (k+b, n)
    U1, S2, Vt2 = jnp.linalg.svd(Y, full_matrices=False)
    res = SVDResult(mm(Q, U1)[:, :k], S2[:k], Vt2[:k, :])
    try:
        fro2 = eng.xbar_fro_norm2(op, mu)
    except NotImplementedError:
        fro2 = None
    post = None if fro2 is None else posterior_rel_err(
        res.S, fro2, op.shape[0], K=k)
    real = jnp.zeros((), res.S.dtype).real.dtype
    report = ConvergenceReport(
        iters_run=jnp.zeros((), jnp.int32),
        pve_trace=jnp.full((0, k), jnp.nan, real),
        sigma_estimates=S2,
        posterior_rel_err=post,
        xbar_fro2=None if fro2 is None else jnp.asarray(fro2),
        qmax=0, k_found=k)
    return res, report


def refresh_rank1(base: SVDResult, x_new, u, w, *, mu=None,
                  engine: contact.ContactEngine | None = None,
                  ) -> tuple[SVDResult, ConvergenceReport]:
    """Refresh a rank-k factorization after ``X_new = X_old + u w^T`` —
    the b=1 case of :func:`refresh_block` (a thin delegation, kept as
    the named entry point the serving layer's rank-1 declarations and
    older scripts call)."""
    U = base.U
    u = jnp.asarray(u, U.dtype).reshape(U.shape[0])
    w = jnp.asarray(w, base.Vt.dtype).reshape(base.Vt.shape[1])
    return refresh_block(base, x_new, u, w, mu=mu, engine=engine)


def split_batched(res: SVDResult, rep: ConvergenceReport,
                  ) -> list[tuple[SVDResult, ConvergenceReport]]:
    """Split a batched pair (leading batch axis on every leaf, as
    :func:`factorize_batched` returns) into per-slice pairs shaped
    exactly like single :func:`factorize` responses — what the serving
    layer hands each request in a coalesced batch."""
    out = []
    for i in range(res.U.shape[0]):
        out.append((
            SVDResult(res.U[i], res.S[i], res.Vt[i]),
            ConvergenceReport(
                iters_run=rep.iters_run[i],
                pve_trace=rep.pve_trace[i],
                sigma_estimates=rep.sigma_estimates[i],
                posterior_rel_err=None if rep.posterior_rel_err is None
                else rep.posterior_rel_err[i],
                xbar_fro2=None if rep.xbar_fro2 is None
                else rep.xbar_fro2[i],
                qmax=rep.qmax,
                k_eff=None if rep.k_eff is None else rep.k_eff[i],
                k_found=rep.k_found)))
    return out


@dataclasses.dataclass
class FactorizationRequest:
    """One factorization job — the object batch scripts submit to
    :func:`run_request` and the server admits into its queue, so both
    paths serialize the same thing.

    ``matrix`` is any operator spec :func:`factorize` accepts.  ``seed``
    derives the PRNG key (``PRNGKey(seed)``) so a request names its
    randomness — equal requests are cacheable.  ``refresh_of`` +
    ``update=(U_b, W_b)`` declare the matrix as a rank-b update of a
    previously factored base (by fingerprint; vectors for b=1): the
    server then takes the :func:`refresh_block` fast path when the
    base is still cached.  ``mu_prev`` is the shifting vector the base
    was factored against — pass it when the update moved the column
    mean so the refresh folds in the mean-shift correction
    (DESIGN.md §17).  ``tag`` is an opaque caller correlation id,
    echoed on the response.

    Exactly one of ``k`` / ``tol`` — a tol request rides the server's
    serial lane (its discovered rank makes it non-coalescable) and its
    response carries ``k_found``.
    """

    matrix: Any
    k: int | None = None
    K: int | None = None
    q: int = 0
    tol: float | None = None
    b: int = 8
    max_K: int | None = None
    mu: Any = None
    center: bool = False
    shift: ShiftSchedule | Any = None
    stop: StopRule | int | None = None
    seed: int = 0
    refresh_of: Fingerprint | None = None
    update: tuple[Any, Any] | None = None
    mu_prev: Any = None
    tag: Any = None


@dataclasses.dataclass
class FactorizationResult:
    """One factorization response: factors + the per-request quality
    SLA (:class:`~repro.core.stopping.ConvergenceReport`) + serving
    observability.

    ``cache_hit`` marks a result served from the fingerprint cache
    (bit-identical to the cold computation it stored).  ``refreshed``
    marks the rank-b refresh fast path (False on the evicted-base
    fallback to a full solve).  ``batch_width`` is how many requests
    shared this result's device batch (1 = solo).  ``queue_ms`` /
    ``compute_ms`` split time-in-queue from device time; cache hits
    carry the lookup cost in ``compute_ms``.  A failed request (e.g. a
    poisoned operator under ``REPRO_DEBUG=nans``) carries ``error``
    and ``result is None`` — failures are per-request, never
    queue-wide.
    """

    result: SVDResult | None
    report: ConvergenceReport | None
    tag: Any = None
    cache_hit: bool = False
    refreshed: bool = False
    batch_width: int = 1
    queue_ms: float = 0.0
    compute_ms: float = 0.0
    error: str | None = None

    @property
    def ok(self) -> bool:
        return self.error is None


def run_request(req: FactorizationRequest, *, mesh=None,
                engine: contact.ContactEngine | None = None,
                ) -> tuple[SVDResult, ConvergenceReport]:
    """Execute one request through :func:`factorize` — the offline
    (serverless) execution of exactly what the server computes, which
    is what the serving parity gates compare against."""
    return factorize(req.matrix, req.k, K=req.K, q=req.q, tol=req.tol,
                     b=req.b, max_K=req.max_K, mu=req.mu,
                     center=req.center, shift=req.shift, stop=req.stop,
                     mesh=mesh, seed=req.seed, engine=engine)


def request_cache_key(req: FactorizationRequest) -> tuple:
    """Hashable identity of a request's *result*: the matrix
    fingerprint plus every field that changes the factors.

    Fields in the key: fingerprint(matrix), k, the adaptive triple
    (tol, b, max_K), K, q, center, a content token of ``mu``
    (None-safe), the shift schedule (hashable frozen dataclass) or a
    content token of a shift *vector*, the normalized stop rule, and
    the seed.  ``tag`` and the refresh declaration (``refresh_of``,
    ``update``, ``mu_prev``) are deliberately excluded — they do not
    change the factors, only how fast the server may get them.
    """
    fp = fingerprint(req.matrix)
    mu_tok = None if req.mu is None else array_token(req.mu)
    shift_key: Any = req.shift
    if shift_key is not None and not isinstance(shift_key,
                                               ShiftSchedule):
        shift_key = array_token(shift_key)
    return (fp, req.k, req.tol, req.b, req.max_K, req.K, req.q,
            req.center, mu_tok, shift_key, as_rule(req.stop), req.seed)
