"""Pluggable range finders for the shifted randomized SVD (DESIGN.md §16).

PR 9 splits ``srsvd`` into two phases: a **range finder** builds the
orthonormal basis Q of the sample space, and the existing shift-corrected
post-process (``Y = (Xbar^T Q)^T``, small SVD, ``U = Q U1``) turns that
basis into factors.  Two finders ship:

  ``FixedRangeFinder``            the paper's one-shot sketch + scheduled
                                  power loop, bit-for-bit the pre-split
                                  ``srsvd`` body (lines 2-11 of
                                  Algorithm 1).  Jittable — it is the body
                                  ``svd_jit`` / ``srsvd_batched`` trace.
  ``WarmStartRangeFinder``        the fixed finder with the sketch seeded
                                  from a prior basis: omega's leading
                                  columns are the prior ``V`` (APGL's
                                  ``svd(omega=...)`` pattern), padded to
                                  width K with ``fold_in`` fresh Gaussian
                                  columns — a refresh of a slightly-
                                  changed matrix converges in ~1 power
                                  pass with the PVE stop certifying when
                                  (DESIGN.md §17).  Bit-compatible with
                                  ``FixedRangeFinder`` when no prior is
                                  given.
  ``BlockedAdaptiveRangeFinder``  the blocked adaptive scheme of
                                  Halko/Martinsson/Shkolnisky/Tygert
                                  (arXiv:1007.5510): grow the basis in
                                  blocks of ``b`` columns drawn against
                                  the *residual* ``(I - Q Q^T) Xbar``
                                  (the engine's ``project_residual``
                                  contact — prior blocks are never
                                  re-materialized), stopping when the
                                  certified posterior residual from PR
                                  5's exact identity clears ``tol``.
                                  Host-driven (the discovered rank is a
                                  Python int), so not jittable.

The certificate is free: each accepted block pays one
``shifted_rmatmat`` whose result serves **twice** — its squared norm is
the block's captured energy (``||Xbar - Q Q^T Xbar||^2 = ||Xbar||^2 -
sum_blocks ||Xbar^T Q_b||^2``, additive because the blocks are mutually
orthonormal), and its transpose is that block's rows of the final
projection ``Y = Q^T Xbar``, so the adaptive post-process skips the
final contact entirely (``GrowthState.Y``).

Every finder's ``find`` returns the ``(Q, GrowthState)`` protocol pair —
lint rule RF010 holds implementations to that shape.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as onp

from repro import obs
from repro.core import contact, stopping as _stopping
from repro.core.qr_update import qr_rank1_update


def _qr(A):
    return jnp.linalg.qr(A, mode="reduced")


def warm_omega(key, n: int, K: int, dt, prior_Vt=None):
    """The (n, K) sample matrix of a possibly warm-started fixed-K
    sketch (DESIGN.md §17).

    With no prior this is exactly ``jax.random.normal(key, (n, K))`` —
    bit-identical to the cold draw, which is the
    ``WarmStartRangeFinder``-degenerates-to-``FixedRangeFinder``
    contract.  With a prior ``Vt`` (k_prior, n) the leading columns of
    omega are the prior right singular vectors (APGL's
    ``RandomisedSVD.svd(omega=...)`` pattern): for an evolved matrix
    ``X' = X + dX`` the sample ``X'bar omega`` then already contains
    ``U diag(S) + O(||dX||)`` — the basis starts converged up to the
    drift, so a PVE/residual stop fires after ~1 power pass.  The
    remaining ``K - k_used`` columns are *fresh* Gaussians drawn from
    ``fold_in(key, k_used)``: they chase whatever new directions the
    update opened.  At least one fresh column is always kept (the prior
    is truncated to K - 1 columns when wider) — a sketch with no
    Gaussian component would never see range directions the prior
    missed.
    """
    if prior_Vt is None:
        return jax.random.normal(key, (n, K), dtype=dt)
    Vp = jnp.asarray(prior_Vt, dt)
    if Vp.ndim != 2 or Vp.shape[1] != n:
        raise ValueError(
            "warm_omega needs the prior as Vt rows over the operator's "
            f"n={n} columns, got shape {Vp.shape}")
    k_used = min(int(Vp.shape[0]), max(K - 1, 0))
    fresh = jax.random.normal(jax.random.fold_in(key, k_used),
                              (n, K - k_used), dtype=dt)
    if k_used == 0:
        return fresh
    return jnp.concatenate([Vp[:k_used].T, fresh], axis=1)


def work_dtype(op):
    """The dtype all basis/QR/SVD algebra runs in: the operator's own
    inexact dtype, or the float result type of an integer/bool operator
    (the operator itself stays integer — products promote)."""
    dt = op.dtype
    if not jnp.issubdtype(dt, jnp.inexact):
        dt = contact.result_dtype(dt, jnp.float32)
    return dt


@dataclasses.dataclass
class GrowthState:
    """What a range finder did, for the post-process and the report.

    Attributes:
      k_found: basis width actually built (host int — it shapes the
        factors).  The fixed finder's is its sampling width K; the
        adaptive finder's is the discovered rank.
      rounds: growth rounds run (1 for the one-shot fixed sketch).
      qmax: the iteration ceiling the run was allowed (feeds the
        report's ``stopped_early``).
      contact_cols: total columns of X touched across all engine
        contacts (sample + power iterations + certificates + probes) —
        the unit ``benchmarks/tol_bench.py`` gates adaptive savings in.
      fro2: ``||Xbar||_F^2`` when the finder computed it, else None.
      captured2: energy captured by the basis, ``||Q^T Xbar||_F^2``
        (adaptive only — it is the certificate's running sum).
      Y: pre-assembled final projection ``Q^T Xbar`` of shape
        (k_found, n) when the finder already paid for it (adaptive —
        the certificate contacts double as Y's rows), else None and the
        post-process runs one ``shifted_rmatmat``.
      tstate: the stop rule's final :class:`~repro.core.stopping
        .StopState` (fixed finder, when a rule ran), else None.
      sched_state: the shift schedule's final state, else None.
      resid_trace: per-round certified relative residual (adaptive),
        else None.
    """

    k_found: int
    rounds: int
    qmax: int
    contact_cols: int
    fro2: jax.Array | None
    captured2: jax.Array | None
    Y: jax.Array | None
    tstate: _stopping.StopState | None
    sched_state: object
    resid_trace: jax.Array | None = None


class RangeFinder:
    """Protocol: build an orthonormal basis of the sample space.

    ``find(eng, op, mu, sched, rule, *, key, k, q)`` returns the pair
    ``(Q, GrowthState)`` — Q an (m, k_found) orthonormal basis of
    (an approximation to) the range of ``Xbar = X - mu 1^T``, and the
    growth record the post-process and report consume.  ``mu`` arrives
    already canonicalized ((m,) in the work dtype) or None; ``rule``
    is a resolved :class:`~repro.core.stopping.StopRule` or None.
    Implementations must return that 2-tuple shape from every return
    path (lint rule RF010).
    """

    def find(self, eng, op, mu, sched, rule, *, key, k, q):
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class FixedRangeFinder(RangeFinder):
    """The paper's one-shot sketch + scheduled power loop (Algorithm 1
    lines 2-11), bit-for-bit the pre-refactor ``srsvd`` body: draw a
    (n, K) Gaussian, one engine matmat, QR, the O(mK) rank-1 shift
    correction (Givens update or re-factorization), then the scheduled
    power loop under the optional stop rule.  Fully traceable — this is
    the finder ``svd_jit`` and the server's batched solver jit."""

    K: int
    use_qr_update: bool = True
    shift_mode: str = "exact"
    loop: str = "python"

    def _draw(self, key, n, K, dt):
        """The line-2 sample draw — the one seam
        :class:`WarmStartRangeFinder` overrides."""
        return jax.random.normal(key, (n, K), dtype=dt)

    def find(self, eng, op, mu, sched, rule, *, key, k, q):
        m, n = op.shape
        dt = work_dtype(op)
        K = self.K

        with obs.span("repro.sample"):
            omega = self._draw(key, n, K, dt)                   # line 2
            X1 = eng.matmat(op, omega)                          # line 3
        with obs.span("repro.qr"):
            Q1, R1 = _qr(X1)                                    # line 4

        if mu is not None:                                      # lines 5-7
            with obs.span("repro.shift_update"):
                v = (omega.sum(axis=0) if self.shift_mode == "exact"
                     else jnp.ones(K, dt))
                if self.use_qr_update:
                    Q, _ = qr_rank1_update(Q1, R1, -mu, v)      # line 6
                else:
                    Q, _ = _qr(contact.rank1_correct(
                        contact.basis_matmul(Q1, R1), mu, v))
        else:
            Q = Q1

        # lines 8-11 under the shift schedule and the stop rule: line 9
        # / Eq. 7 then line 10 / Eq. 8 (or the spectral Gram body),
        # every product through the engine's fused rank-1-epilogue
        # contact points.  One driver serves both loop spellings, so
        # the (schedule state, stop state) init order is identical
        # whichever loop runs — including the q = 0 degenerate case
        # (pinned by tests/test_stopping.py parity tests).
        qmax = q if rule is None else rule.resolve_q(q)
        state = sched.init(dt)
        tstate = None
        # ||Xbar||_F^2 for the residual criterion / the posterior
        # certificate: the fro_norm2 probe + one K=1 matmat, once.
        fro2 = _stopping.resolve_fro2(rule, eng, op, mu)
        if rule is not None:
            tstate = rule.init(dt, K, qmax, k, fro2)
        Q, state, tstate = _stopping.run_power_loop(
            sched, rule, eng, op, Q, mu, qmax, state, tstate,
            loop=self.loop)
        return Q, GrowthState(
            k_found=K, rounds=1, qmax=qmax,
            contact_cols=(2 + 2 * qmax) * K + (0 if fro2 is None else 1),
            fro2=fro2, captured2=None, Y=None, tstate=tstate,
            sched_state=state)


@dataclasses.dataclass(frozen=True, eq=False)
class WarmStartRangeFinder(FixedRangeFinder):
    """:class:`FixedRangeFinder` with the sketch seeded from a prior
    basis (DESIGN.md §17): omega's leading columns are ``prior_Vt``'s
    rows transposed — the right singular vectors of a previous
    factorization of a nearby matrix — padded to width K with
    ``fold_in`` fresh Gaussian columns (see :func:`warm_omega`).
    Everything after the draw (engine sample contact, QR, the rank-1
    shift correction, the scheduled power loop under the stop rule) is
    the fixed finder's body verbatim, so a warm refresh composes with
    every schedule/rule and a ``PVEStop``/``ResidualStop`` certifies
    *when* the warm basis has converged — typically after ~1 pass
    instead of q.

    ``prior_Vt=None`` degenerates to :class:`FixedRangeFinder`
    bit-for-bit (same draw, same body) — the property suite pins it.
    ``eq=False``: the prior is a concrete array; these finders are
    built per call, never used as jit cache keys.
    """

    prior_Vt: jax.Array | None = None

    def _draw(self, key, n, K, dt):
        return warm_omega(key, n, K, dt, self.prior_Vt)


@dataclasses.dataclass(frozen=True)
class BlockedAdaptiveRangeFinder(RangeFinder):
    """Blocked adaptive range finder (arXiv:1007.5510, adapted to the
    shifted operator): grow the basis ``b`` columns at a time against
    the residual, stop when the certified relative Frobenius residual

        sqrt(max(0, ||Xbar||^2 - sum_blocks ||Xbar^T Q_b||^2)) / ||Xbar||

    clears ``tol``.  Each round costs one ``project_residual`` contact
    (the sample, deflated against the accumulated Q inside the engine),
    ``q`` deflated power iterations (2 contacts each — since the new
    block is orthogonal to Q, ``Xbar^T Q_b`` *is* the deflated rmatmat),
    and one ``shifted_rmatmat`` whose result is both the certificate
    and the block's rows of the final projection.  Host-driven: the
    loop breaks on a concrete residual, so the finder is not jittable
    (dynamic discovered rank) — exactly like the streamed drivers'
    host loops.

    ``max_K`` caps the basis (default min(m, n)); the finder returns
    what it has when the cap is hit, and the report's certificate says
    honestly how far that is from ``tol``.
    """

    tol: float = 1e-2
    b: int = 8
    max_K: int | None = None

    def __post_init__(self):
        if not (self.tol >= 0.0):
            raise ValueError(f"need tol >= 0, got {self.tol=}")
        if self.b < 1:
            raise ValueError(f"need a block of >= 1 columns, got {self.b=}")

    def find(self, eng, op, mu, sched, rule, *, key, k=None, q=0):
        m, n = op.shape
        dt = work_dtype(op)
        _stopping.validate_certified_schedule(
            sched, mu is not None, what="BlockedAdaptiveRangeFinder")
        kmax = min(m, n) if self.max_K is None else min(self.max_K,
                                                        min(m, n))
        fro2 = jnp.maximum(jnp.asarray(eng.xbar_fro_norm2(op, mu), dt),
                           jnp.finfo(dt).tiny)
        Q = jnp.zeros((m, 0), dt)
        Zs = []                        # per-block (n, b) rows of Xbar^T Q_b
        resid = []
        captured2 = jnp.zeros((), dt)
        cols = 1                       # the fro2 probe's K=1 matmat
        rounds = 0
        while Q.shape[1] < kmax:
            b = min(self.b, kmax - Q.shape[1])
            sub = jax.random.fold_in(key, rounds)
            omega = jax.random.normal(sub, (n, b), dtype=dt)
            Yb = eng.project_residual(op, Q, omega, mu)         # sample
            cols += b
            Qb = _orth_against(Q, Yb)
            for _ in range(q):
                # Power iteration on the deflated operator: Q_b ⟂ Q
                # makes Xbar^T Q_b the deflated rmatmat already, so
                # each iteration is one rmatmat + one project_residual.
                Zb = eng.shifted_rmatmat(op, Qb, mu)
                Yb = eng.project_residual(op, Q, Zb, mu)
                cols += 2 * b
                Qb = _orth_against(Q, Yb)
            Zb = eng.shifted_rmatmat(op, Qb, mu)    # certificate + Y rows
            cols += b
            Q = jnp.concatenate([Q, Qb], axis=1)
            Zs.append(Zb)
            captured2 = captured2 + jnp.sum(Zb * Zb)
            rounds += 1
            rel = float(jnp.sqrt(
                jnp.clip(fro2 - captured2, 0.0, None) / fro2))
            resid.append(rel)
            if rel <= self.tol:
                break
        Y = jnp.concatenate(Zs, axis=1).T
        return Q, GrowthState(
            k_found=int(Q.shape[1]), rounds=rounds, qmax=rounds,
            contact_cols=cols, fro2=fro2, captured2=captured2, Y=Y,
            tstate=None, sched_state=None,
            resid_trace=jnp.asarray(onp.asarray(
                resid, onp.dtype(jnp.zeros((), dt).real.dtype))))


def _orth_against(Q, Yb):
    """Orthonormalize a new block against the accumulated basis: one
    more deflation pass (the engine already deflated the sample once),
    QR, then a re-orthogonalization pass — classic twice-is-enough
    block Gram-Schmidt, which keeps the *existing* Q columns untouched
    bit-for-bit (a concat-and-re-QR would re-mix and sign-flip them)."""
    mm = contact.basis_matmul
    if Q.shape[1]:
        Yb = Yb - mm(Q, mm(Q.T, Yb))
    Qb, _ = _qr(Yb)
    if Q.shape[1]:
        Qb = Qb - mm(Q, mm(Q.T, Qb))
        Qb, _ = _qr(Qb)
    return Qb


def build_adaptive_report(growth: GrowthState, S,
                          m: int) -> _stopping.ConvergenceReport:
    """Report for an adaptive run.  ``iters_run``/``qmax`` count growth
    rounds; ``pve_trace`` is the (rounds, 1) certified-residual trace
    (there is no per-component PVE — nothing iterates in place);
    ``k_eff`` counts the components resolved above the certified
    residual floor, i.e. distinguishable from what the basis missed."""
    floor2 = jnp.clip(growth.fro2 - growth.captured2, 0.0, None)
    k_eff = jnp.sum(S * S > floor2).astype(jnp.int32)
    return _stopping.ConvergenceReport(
        iters_run=jnp.asarray(growth.rounds, jnp.int32),
        pve_trace=growth.resid_trace.reshape(-1, 1),
        sigma_estimates=S,
        posterior_rel_err=_stopping.posterior_rel_err(
            S, growth.fro2, m, K=growth.k_found),
        xbar_fro2=growth.fro2, qmax=growth.qmax, k_eff=k_eff,
        k_found=growth.k_found)
