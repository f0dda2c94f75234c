"""Shifted Randomized SVD (Basirat 2019, Algorithm 1) and the Halko et al.
(2011) randomized SVD baseline, in JAX.

``srsvd`` computes a rank-k SVD of ``X - mu 1^T`` touching X only through
products — the shifted (dense) matrix never exists.  ``rsvd`` is the
original algorithm (identical to ``srsvd`` with ``mu=None``), implemented
as the paper's comparison baseline.

Every matrix contact point routes through a
:class:`repro.core.contact.ContactEngine`, which dispatches to the fused
rank-1-epilogue Pallas matmul on TPU (and to plain XLA dot on other
backends / for sparse and streamed operands).  Passing ``mu=None`` to an
engine contact point means "unshifted", so the algorithm body below has
no shifted-vs-plain branching.

The power iterations run under a :class:`repro.core.schedule.ShiftSchedule`
(``shift=``): the default ``FixedShift`` is the paper's constant ``mu``,
``DynamicShift`` is the Feng et al. (arXiv:2404.09276) per-iteration
accelerator, ``DecayingShift`` anneals the centering (DESIGN.md §9)::

    srsvd(X, mu, k=10, q=2, key=key, shift=DynamicShift())
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import (contact, rangefinder as _rangefinder,
                        schedule as _schedule, stopping as _stopping)
from repro.core.linop import as_linop
from repro.core.schedule import ShiftSchedule
from repro.core.stopping import StopRule


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SVDResult:
    U: jax.Array    # (m, k)
    S: jax.Array    # (k,)
    Vt: jax.Array   # (k, n)

    def reconstruct(self) -> jax.Array:
        return (self.U * self.S) @ self.Vt

    def tree_flatten(self):
        return (self.U, self.S, self.Vt), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


ShiftMode = Literal["exact", "paper"]


PowerLoop = Literal["python", "fori"]


def srsvd(X, mu, k: int, K: int | None = None, q: int = 0, *,
          key: jax.Array, use_qr_update: bool = True,
          shift_mode: ShiftMode = "exact",
          shift: ShiftSchedule | jax.Array | None = None,
          stop: StopRule | int | None = None,
          loop: PowerLoop = "python",
          warm_start=None,
          engine: contact.ContactEngine | None = None):
    """Rank-k SVD of ``X - mu 1^T`` (Algorithm 1).

    Args:
      X: (m, n) array, BCOO sparse matrix, or LinOp (including the
        out-of-core ``BlockedOp`` / ``ChainedOp``).
      mu: (m,) shifting vector, or None for the unshifted algorithm.
      k: target rank.  K: sampling rank (default 2k).  q: power iterations.
      key: PRNG key for the Gaussian test matrix.
      use_qr_update: line 6 via the O(mK) Givens rank-1 QR update (paper)
        instead of a fresh O(mK^2) QR re-factorization (same math).
      shift_mode: "exact" uses v = Omega^T 1 so line 6 produces the basis
        of the true sample (X - mu 1^T) Omega; "paper" uses v = 1_K,
        literally as printed in Algorithm 1 (see DESIGN.md §8).
      shift: a :class:`~repro.core.schedule.ShiftSchedule` governing the
        power iterations (``FixedShift`` — the default — reproduces the
        constant-``mu`` path exactly; ``DynamicShift`` is the dashSVD
        accelerator; ``DecayingShift`` anneals the centering), or a
        shifting *vector* — equivalent to passing it as ``mu``.  The
        sample (lines 3-7) and final projection (line 12) always use the
        target ``mu``; the schedule governs lines 8-11 only, so every
        schedule factorizes the same matrix (DESIGN.md §9).
      stop: a :class:`~repro.core.stopping.StopRule` governing *when
        the power loop ends* (``FixedIters`` — exactly ``q``
        iterations, bit-for-bit the unruled path; ``PVEStop`` — the
        dashSVD per-vector-error early stop; ``ResidualStop`` — the
        certified Frobenius-residual stop), or an int (shorthand for
        ``FixedIters``), or None.  With a rule attached the return
        value becomes the pair ``(SVDResult,``
        :class:`~repro.core.stopping.ConvergenceReport```)`` —
        iterations actually run, per-component PVE trace, posterior
        error certificate (DESIGN.md §12).  ``q`` stays the iteration
        ceiling unless the rule carries its own.
      warm_start: a prior factorization of a nearby matrix — an
        :class:`SVDResult` or its raw ``Vt`` (k_prior, n) — to seed
        the sketch from (DESIGN.md §17): omega's leading columns
        become the prior right singular vectors, padded to width K
        with ``fold_in`` fresh Gaussians
        (:class:`~repro.core.rangefinder.WarmStartRangeFinder`), so a
        refresh of a slightly-changed matrix converges in ~1 power
        pass with a ``PVEStop``/``ResidualStop`` certifying when.
        ``None`` (the default) is the cold draw, bit-for-bit.
      loop: "python" unrolls the power loop (required for the streaming
        ``BlockedOp``, whose block iteration is host-side; a firing
        stop rule breaks the host loop, saving the skipped iterations'
        disk passes); "fori" runs it as a ``lax.fori_loop`` with
        ``(Q, schedule state)`` carry — the jit-friendly form
        ``svd_jit`` uses — or, when a rule can fire early, a
        ``lax.while_loop`` whose carry also holds the stop state, so
        jit gets true early exit.
      engine: contact engine to route every product through (default:
        the hardware-resolved backend — Pallas on TPU, XLA elsewhere).
    """
    op = as_linop(X)
    eng = engine if engine is not None else contact.get_engine()
    m, n = op.shape
    dt = op.dtype
    if not jnp.issubdtype(dt, jnp.inexact):
        # Integer / bool operators: draw omega (and run all QR/SVD
        # algebra) in the float result type of the operator dtype; the
        # operator itself stays integer — products promote.
        dt = contact.result_dtype(dt, jnp.float32)
    if K is None:
        K = 2 * k
    if not (k <= K <= min(m, n)):
        raise ValueError(f"need k <= K <= min(m, n), got {k=} {K=} {m=} {n=}")
    mu, sched = _schedule.resolve_shift(mu, shift)
    if mu is not None:
        mu = jnp.asarray(mu, dt).reshape(m)
    rule = _stopping.as_rule(stop)
    _stopping.validate_rule_schedule(rule, sched, mu is not None)

    # Phase 1 — range finding (lines 2-11): the one-shot sketch + shift
    # correction + scheduled power loop, packaged as the fixed-K
    # RangeFinder implementation (DESIGN.md §16).  srsvd_tol swaps in
    # the blocked adaptive finder here; a warm start swaps in the
    # prior-seeded sketch (DESIGN.md §17); everything below is shared.
    if warm_start is not None:
        prior_Vt = getattr(warm_start, "Vt", warm_start)
        finder = _rangefinder.WarmStartRangeFinder(
            K=K, use_qr_update=use_qr_update, shift_mode=shift_mode,
            loop=loop, prior_Vt=jnp.asarray(prior_Vt))
    else:
        finder = _rangefinder.FixedRangeFinder(
            K=K, use_qr_update=use_qr_update, shift_mode=shift_mode,
            loop=loop)
    Q, growth = finder.find(eng, op, mu, sched, rule, key=key, k=k, q=q)

    # Phase 2 — shift-corrected post-process.
    # line 12 / Eq. 10:  Y = Q^T X - (Q^T mu) 1^T  ==  ((Xbar)^T Q)^T.
    with obs.span("repro.project"):
        Y = eng.shifted_rmatmat(op, Q, mu).T                # (K, n)

    with obs.span("repro.small_svd"):
        U1, S, Vt = jnp.linalg.svd(Y, full_matrices=False)  # line 13
        U = contact.basis_matmul(Q, U1)                     # line 14
        res = SVDResult(U[:, :k], S[:k], Vt[:k, :])
    if rule is None:
        return res
    return res, _stopping.build_report(rule, growth.tstate, res.S, m,
                                       growth.qmax, growth.fro2,
                                       k_found=growth.k_found)


def rsvd(X, k: int, K: int | None = None, q: int = 0, *,
         key: jax.Array, shift: ShiftSchedule | None = None,
         stop: StopRule | int | None = None,
         engine: contact.ContactEngine | None = None):
    """Halko et al. (2011) randomized SVD — the paper's baseline.

    ``shift=DynamicShift()`` turns it into dashSVD proper (Feng et al.),
    and ``stop=PVEStop(...)`` adds its PVE early-stopping criterion.
    """
    return srsvd(X, None, k, K, q, key=key, shift=shift, stop=stop,
                 engine=engine)


def srsvd_tol(X, mu=None, *, tol: float, b: int = 8, q: int = 0,
              key: jax.Array, max_K: int | None = None,
              shift: ShiftSchedule | jax.Array | None = None,
              engine: contact.ContactEngine | None = None):
    """Tolerance-first adaptive-rank SVD of ``X - mu 1^T``.

    The dual of :func:`srsvd` for callers who know their error budget,
    not their rank: the :class:`~repro.core.rangefinder
    .BlockedAdaptiveRangeFinder` grows the basis ``b`` columns at a
    time against the residual (the engine's ``project_residual``
    contact — prior blocks are never re-materialized) and stops once
    the certified relative Frobenius residual from PR 5's exact
    identity clears ``tol``; the discovered rank is
    ``report.k_found``.  Each round's certificate contact doubles as
    that block's rows of the final projection, so the post-process
    pays no extra contact of X (DESIGN.md §16).

    Args:
      X: (m, n) array, sparse matrix, or LinOp (including the
        out-of-core blocked operators — growth is just more engine
        contacts, so they work unchanged; the streamed sharded
        operators have their own driver,
        ``dist_srsvd_tol_streamed``).
      mu: (m,) shifting vector, or None for the unshifted algorithm.
      tol: target relative Frobenius error; the run stops at the first
        block whose certificate clears it.
      b: growth-block width.  q: deflated power iterations per block.
      key: PRNG key; block ``t`` draws from ``fold_in(key, t)``, so
        runs at different tolerances share their basis prefix
        (``k_found`` is monotone non-increasing in ``tol``).
      max_K: basis cap (default min(m, n)); when hit, the factors are
        returned as-is and ``posterior_rel_err`` reports honestly.
      shift: constant-target schedules (or a shifting vector) only —
        annealed profiles break the certificate
        (``validate_certified_schedule``) and spectral bodies have no
        deflated form here.
      engine: contact engine (default: the hardware-resolved backend).

    Returns:
      ``(SVDResult, ConvergenceReport)`` — always the pair; the report
      carries ``k_found``, a certified ``posterior_rel_err <= tol``
      (when the cap was not hit), and a (rounds, 1) residual trace in
      ``pve_trace``.  Host-driven (the rank is data-dependent), so not
      jittable — like the streamed drivers' host loops.
    """
    op = as_linop(X)
    eng = engine if engine is not None else contact.get_engine()
    m, _ = op.shape
    dt = op.dtype
    if not jnp.issubdtype(dt, jnp.inexact):
        dt = contact.result_dtype(dt, jnp.float32)
    mu, sched = _schedule.resolve_shift(mu, shift)
    if sched.spectral:
        raise ValueError(
            "adaptive growth runs plain deflated power iterations under "
            f"the target shift; a spectral schedule "
            f"({type(sched).__name__}) has no deflated Gram body — use "
            "shift=None or FixedShift with srsvd_tol")
    if mu is not None:
        mu = jnp.asarray(mu, dt).reshape(m)

    finder = _rangefinder.BlockedAdaptiveRangeFinder(tol=tol, b=b,
                                                     max_K=max_K)
    Q, growth = finder.find(eng, op, mu, sched, None, key=key, q=q)

    # The certificate contacts already assembled Y = Q^T Xbar — the
    # final projection is free.
    U1, S, Vt = jnp.linalg.svd(growth.Y, full_matrices=False)
    U = contact.basis_matmul(Q, U1)
    kf = growth.k_found
    res = SVDResult(U[:, :kf], S[:kf], Vt[:kf, :])
    return res, _rangefinder.build_adaptive_report(growth, S[:kf], m)


def expected_error_bound(m: int, k: int, q: int, sigma_k1: float) -> float:
    """Paper Eq. 12: E||Xbar - U S V^T|| <= [1 + 4 sqrt(2m/(k-1))]^(1/(2q+1))
    * sigma_{k+1}."""
    if k <= 1:
        raise ValueError(
            "expected_error_bound needs k >= 2 (the bound divides by "
            f"k - 1), got k={k}")
    return (1.0 + 4.0 * (2.0 * m / (k - 1)) ** 0.5) ** (1.0 / (2 * q + 1)) \
        * sigma_k1


def srsvd_batched(Xs, mus, k: int, K: int | None = None, q: int = 0, *,
                  keys: jax.Array, shift: ShiftSchedule | None = None,
                  stop: StopRule | None = None):
    """vmapped ``srsvd`` over a stack of same-shape dense operators.

    Args:
      Xs: (B, m, n) stacked dense matrices — one factorization job per
        leading-axis slice.
      mus: (B, m) stacked shifting vectors, or None for the unshifted
        algorithm on every slice (``mus`` cannot mix shifted and
        unshifted jobs — the serving layer groups on that).
      keys: (B,) stacked PRNG keys (``jax.vmap``-able key array); slice
        ``b`` draws exactly the omega that ``srsvd(Xs[b], ...,
        key=keys[b])`` would, so batched and single-job results agree.
      k, K, q, shift, stop: as in :func:`srsvd`; ``shift`` must be a
        schedule (not a vector — per-job vectors ride ``mus``), and
        ``stop`` a hashable :class:`~repro.core.stopping.StopRule` or
        None.  All static: one trace serves every batch of the same
        (shape, dtype, B, k, K, q, shift, stop) signature.

    Returns ``SVDResult`` with (B, m, k) / (B, k) / (B, k, n) leaves —
    plus a batched :class:`~repro.core.stopping.ConvergenceReport` when
    ``stop`` is set, exactly mirroring ``srsvd``'s pair contract.  This
    is the device-batching primitive behind the factorization server
    (``launch/factor_serve.py``): B small jobs cost one batched QR/SVD
    pipeline instead of B dispatch rounds (DESIGN.md §15).
    """
    if shift is not None and not isinstance(shift, ShiftSchedule):
        raise TypeError("srsvd_batched takes per-job shifting vectors "
                        "as mus and a ShiftSchedule as shift")
    if stop is not None and not isinstance(stop, StopRule):
        raise TypeError("srsvd_batched takes stop as a StopRule "
                        "(hashable static argument) or None")
    if Xs.ndim != 3:
        raise ValueError(f"Xs must be (B, m, n) stacked, got {Xs.shape}")
    shifted = mus is not None
    if mus is None:
        mus = jnp.zeros((Xs.shape[0], Xs.shape[1]), Xs.dtype)
    K = 2 * k if K is None else K
    return _jit_svd_batched(Xs, mus, k, K, q, shifted, shift, stop,
                            keys)


#: The ``obs`` event counted each time _jit_svd_batched traces (one per
#: distinct static signature + stacked shape) — the server's coalescing
#: tests and its observability counters read the delta around each
#: batched call to prove that same-shape requests share one compilation.
_BATCHED_TRACED = "batched_traced"


def batched_trace_count() -> int:
    """Cumulative trace count of the batched solver (monotone)."""
    return obs.total(_BATCHED_TRACED)


@functools.partial(jax.jit,
                   static_argnames=("k", "K", "q", "shifted", "shift",
                                    "stop"))
def _jit_svd_batched(Xs, mus, k, K, q, shifted, shift, stop, keys):
    obs.count(_BATCHED_TRACED)        # trace-time side effect, by design

    def one(X, mu, key):
        return srsvd(X, mu if shifted else None, k, K, q, key=key,
                     shift=shift, stop=stop, loop="fori")

    return jax.vmap(one)(Xs, mus, keys)


@functools.partial(jax.jit,
                   static_argnames=("k", "K", "q", "shifted", "shift",
                                    "stop"))
def _jit_svd_dense(X, mu, k, K, q, shifted, shift, stop, key):
    # the power loop is a lax.fori_loop with (Q, schedule state, stop
    # state) carry, so q never unrolls into the HLO and dynamic
    # schedules trace once; a stop rule that can fire early swaps the
    # fori_loop for a lax.while_loop — true early exit under jit.
    return srsvd(X, mu if shifted else None, k, K, q, key=key,
                 shift=shift, stop=stop, loop="fori")


def svd_jit(X, mu, k, K=None, q=0, *, key,
            shift: ShiftSchedule | None = None,
            stop: StopRule | None = None):
    """jit'd convenience entry point for dense arrays.

    ``shift`` takes a schedule and ``stop`` a stop rule (both
    frozen/hashable — they ride the jit cache key as static arguments);
    their per-iteration state is carried through the power loop, which
    is a ``lax.fori_loop`` — or a ``lax.while_loop`` when the rule can
    fire early, so XLA executes only the iterations the rule allows.
    With ``stop`` the return value is ``(SVDResult,
    ConvergenceReport)``, like ``srsvd``'s.
    """
    K = 2 * k if K is None else K
    m = X.shape[0]
    if shift is not None and not isinstance(shift, ShiftSchedule):
        raise TypeError("svd_jit takes the shifting vector as mu and a "
                        "ShiftSchedule as shift")
    if stop is not None and not isinstance(stop, StopRule):
        raise TypeError("svd_jit takes stop as a StopRule (hashable "
                        "static argument); ints/vectors are not "
                        "accepted here")
    mu_arr = jnp.zeros((m,), X.dtype) if mu is None else mu
    return _jit_svd_dense(X, mu_arr, k, K, q, mu is not None, shift,
                          stop, key)
