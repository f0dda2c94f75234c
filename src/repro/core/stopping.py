"""Convergence control for the S-RSVD power iteration (DESIGN.md §12).

The shifted iteration exists to *accelerate convergence*, yet a fixed
``q`` runs blind: easy (fast-decay) spectra waste iterations — and in
the out-of-core paths every wasted iteration is a full disk pass —
while the caller learns nothing about how good the returned rank-k
factors actually are.  This module is the single home of both halves of
that problem:

  ``StopRule``            decides, after every power iteration, whether
                          the basis has converged — from quantities the
                          iteration already computed (the R factor of
                          its QR), never a new contact with X.
  ``ConvergenceReport``   returned alongside the factors: iterations
                          actually run, the per-component PVE trace,
                          and a posterior error certificate.

Three rules ship:

  ``FixedIters``    today's behaviour, bit for bit: run exactly ``q``
                    iterations, never stop early (it still records the
                    PVE trace, which costs one O(K^3) ``svdvals`` per
                    iteration and touches no factor math).
  ``PVEStop``       dashSVD's per-vector-error criterion (Feng et al.,
                    arXiv:2404.09276 §4): stop when every monitored
                    singular-value estimate moved by at most ``tol``
                    relative to the head estimate since the previous
                    iteration.  Estimates come from the iteration's own
                    R factor — zero extra contacts of X.
  ``ResidualStop``  shifted Frobenius residual: stop when the captured
                    energy ``sum_i s_i^2`` of the K-dimensional basis
                    certifies ``||Xbar - Q Q^T Xbar||_F / ||Xbar||_F <=
                    tol``.  Needs ``||Xbar||_F^2`` once, via the
                    engine's existing ``fro_norm2`` probe (one extra
                    contact at setup, none per iteration).

Singular-value estimates and the shift back-correction
------------------------------------------------------

Both stopping criteria read the R factor of the iteration's final QR.
For the two-QR body (``Z = Xbar Q'``, ``Q R = qr(Z)``) the singular
values of R are Rayleigh–Ritz estimates of ``sigma_i(Xbar)`` directly.
For the spectral (dashSVD Gram) body the iterate is
``W = (Xbar Xbar^T - alpha I) Q``, so ``svdvals(R)`` estimate
``sigma_i^2 - alpha`` — the schedule's own damping deflates the
estimates, and comparing them across iterations while ``alpha`` grows
would look like divergence.  ``sigma_estimates`` therefore applies the
back-correction ``sigma_i = sqrt(max(svdvals(R) + alpha, 0))`` before
any PVE ratio is formed (DESIGN.md §12 derives this).

Loop-carry contract
-------------------

``StopState`` is a fixed-structure, fixed-shape pytree, so it rides a
``lax.fori_loop`` / ``lax.while_loop`` carry next to the schedule state
(``svd_jit``), a shard_map ``lax.while_loop`` carry (``dist_srsvd`` —
the decision is computed from TSQR's *replicated* R factor, so every
device takes the same branch with zero new collectives), and plain
Python loops (``srsvd(loop="python")``, the streamed distributed
drivers — where a True decision breaks the host loop and saves a full
disk pass per skipped iteration).

Rules are frozen (hashable) dataclasses so they can ride ``jax.jit``
static arguments, exactly like the shift schedules.

Example::

    from repro.core import PVEStop, srsvd

    res, report = srsvd(X, mu, k=10, q=8, key=key, stop=PVEStop(5e-3))
    # report.iters_run <= 8; report.posterior_rel_err certifies the fit
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as onp
from jax import lax

from repro import obs


def sigma_estimates(R: jax.Array, alpha=None) -> jax.Array:
    """Descending singular-value estimates from an iteration's R factor.

    ``alpha`` is the spectral shift the iteration ran under (``None``
    for the two-QR body): the Gram iterate's singular values estimate
    ``sigma^2 - alpha``, so the back-correction adds ``alpha`` and
    takes the square root (clipped at zero — the damped tail may sit
    slightly below ``alpha`` numerically).
    """
    s = jnp.linalg.svd(R, compute_uv=False)
    if alpha is None:
        return s
    return jnp.sqrt(jnp.clip(s + alpha, 0.0, None))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StopState:
    """Loop-carried convergence-monitor state (fixed shapes).

    ``t`` counts completed iterations; ``prev_s`` holds the previous
    iteration's sigma estimates (zeros before the first — which makes
    the first PVE row O(1), so no rule can fire before it has seen two
    estimates of the head component); ``trace`` is the (qmax, K) PVE
    history, NaN where no iteration ran; ``fro2`` is ``||Xbar||_F^2``
    when a rule asked for it (0 otherwise); ``mask`` selects the
    monitored components (the first min(k, K) — tail sampling columns
    beyond the target rank are allowed to keep churning).
    """

    t: jax.Array
    done: jax.Array
    prev_s: jax.Array
    pve: jax.Array
    trace: jax.Array
    fro2: jax.Array
    mask: jax.Array

    def tree_flatten(self):
        return ((self.t, self.done, self.prev_s, self.pve, self.trace,
                 self.fro2, self.mask), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ConvergenceReport:
    """What the power loop actually did, returned alongside the factors.

    Attributes:
      iters_run: power iterations executed (int, or int32 array under
        jit).  ``iters_run < qmax`` means the rule fired early.
      qmax: the iteration ceiling this run was allowed.
      pve_trace: (qmax, K) per-component PVE history — row ``t`` is
        ``|s_i^(t) - s_i^(t-1)| / s_1^(t)``; NaN rows mark iterations
        that never ran (early stop) or were never monitored.
      sigma_estimates: (K,) final singular-value estimates from the last
        iteration's R factor (alpha back-corrected), zeros when no
        iteration ran.
      posterior_rel_err: certified relative Frobenius error of the
        *returned* rank-k factors, ``sqrt(max(0, ||Xbar||_F^2 -
        sum_k S_k^2)) / ||Xbar||_F`` plus an fp slack — exact in exact
        arithmetic (DESIGN.md §12), an upper bound in floating point.
        None when the rule was built with ``certificate=False`` and its
        criterion did not need ``||Xbar||_F^2`` either.
      xbar_fro2: the ``||Xbar||_F^2`` probe behind the certificate
        (None when not computed).
      k_eff: banded per-component convergence count — how many
        monitored components' final PVE sits inside the rule's
        ``k_eff_band`` (int32 array; 0 when no power iteration ran, so
        a q=0 run honestly reports that nothing was *iterated to*
        convergence — the posterior certificate still covers the
        factors).  Adaptive runs count the components resolved above
        the certified residual floor instead (DESIGN.md §16).
      k_found: the basis width this run actually used — the sampling
        width K on the fixed-K paths, the *discovered* rank on the
        adaptive-tolerance paths (``srsvd_tol``).  Host-static (it
        shapes the factors), so it lives in pytree aux_data and
        survives the server's vmapped batching.
    """

    iters_run: jax.Array
    pve_trace: jax.Array
    sigma_estimates: jax.Array
    posterior_rel_err: jax.Array | None
    xbar_fro2: jax.Array | None
    qmax: int = dataclasses.field(default=0)
    k_eff: jax.Array | None = dataclasses.field(default=None)
    k_found: int | None = dataclasses.field(default=None)

    @property
    def stopped_early(self):
        return self.iters_run < self.qmax

    def tree_flatten(self):
        return ((self.iters_run, self.pve_trace, self.sigma_estimates,
                 self.posterior_rel_err, self.xbar_fro2, self.k_eff),
                (self.qmax, self.k_found))

    @classmethod
    def tree_unflatten(cls, aux, children):
        (iters_run, pve_trace, sigma_estimates, posterior_rel_err,
         xbar_fro2, k_eff) = children
        return cls(iters_run=iters_run, pve_trace=pve_trace,
                   sigma_estimates=sigma_estimates,
                   posterior_rel_err=posterior_rel_err,
                   xbar_fro2=xbar_fro2, k_eff=k_eff, qmax=aux[0],
                   k_found=aux[1])


class StopRule:
    """Protocol: decide per iteration whether the power loop is done.

    Subclasses are frozen dataclasses (hashable — they ride jit static
    arguments).  The driver contract, mirrored by every execution path:

      ``qmax = rule.resolve_q(q)``            iteration ceiling
      ``state = rule.init(dtype, K, qmax, k, fro2)``
      per iteration: ``state = rule.update(state, R, alpha)`` with the
        iteration's R factor and the spectral shift it ran under
        (``None`` for non-spectral schedules); then stop when
        ``state.done`` — checked *before* the next iteration, so
        ``state.t`` is always the number of iterations actually run.
    """

    #: False for rules that can never fire (FixedIters): drivers keep
    #: their fixed-trip-count loop (fori_loop) instead of a while_loop.
    #: (deliberately un-annotated, like ShiftSchedule.spectral: dataclass
    #: subclasses must not pick class flags up as constructor fields —
    #: and the base class deliberately declares no ``qmax``/
    #: ``certificate`` annotations for the same reason; subclasses
    #: provide them as their own defaulted fields.)
    can_stop_early = True

    def resolve_q(self, q: int) -> int:
        """Iteration ceiling: the rule's own ``qmax`` wins over the
        call's ``q`` (so one rule instance can carry its budget)."""
        own = getattr(self, "qmax", None)
        return q if own is None else own

    @property
    def needs_fro2(self) -> bool:
        """Whether ``init`` must receive ``||Xbar||_F^2`` — because the
        criterion consumes it, or because the caller asked for the
        posterior certificate in the report."""
        return self.certificate

    def init(self, dtype, K: int, qmax: int, k: int,
             fro2=None) -> StopState:
        real = jnp.zeros((), dtype).real.dtype
        kmon = min(k if getattr(self, "k", None) is None
                   else getattr(self, "k"), K)
        return StopState(
            t=jnp.zeros((), jnp.int32),
            done=jnp.zeros((), bool),
            prev_s=jnp.zeros((K,), real),
            pve=jnp.full((K,), jnp.inf, real),
            # host-side NaN markers ("iteration never ran"): a jnp.full
            # here runs an eager convert_element_type jit whose NaN
            # output trips jax_debug_nans (REPRO_DEBUG=nans) on every
            # monitored solve; device_put of a numpy constant does not
            trace=jnp.asarray(onp.full((max(qmax, 0), K), onp.nan,
                                       onp.dtype(real))),
            fro2=jnp.asarray(0.0 if fro2 is None else fro2, real),
            mask=jnp.arange(K) < kmon)

    def update(self, state: StopState, R: jax.Array,
               alpha=None) -> StopState:
        """Advance the monitor with this iteration's R factor.

        O(K^3) on the (K, K) R — never a contact with X.  ``R`` is
        replicated in the distributed paths (the TSQR contract), so the
        decision is identical on every device for free.
        """
        s = sigma_estimates(R, alpha)
        denom = jnp.maximum(s[0], jnp.finfo(s.dtype).tiny)
        pve = jnp.abs(s - state.prev_s) / denom
        trace = state.trace
        if trace.shape[0]:
            trace = trace.at[state.t].set(pve)
        done = state.done | self.decide(s, pve, state)
        return StopState(t=state.t + 1, done=done, prev_s=s, pve=pve,
                         trace=trace, fro2=state.fro2, mask=state.mask)

    def decide(self, s, pve, state) -> jax.Array:
        """Rule-specific criterion; returns a scalar bool (traceable)."""
        return jnp.zeros((), bool)

    @property
    def k_eff_band(self) -> float:
        """PVE band inside which a component counts as converged for the
        report's ``k_eff``: the rule's own tolerance when it has one
        (PVEStop/ResidualStop), 1e-2 otherwise (FixedIters)."""
        band = getattr(self, "tol", None)
        return 1e-2 if band is None else float(band)


@dataclasses.dataclass(frozen=True)
class FixedIters(StopRule):
    """Run exactly ``q`` iterations — bit-for-bit today's fixed-q path.

    ``q=None`` takes the call site's ``q`` argument.  The factor math
    is untouched (the monitor only *reads* each iteration's R), so
    ``srsvd(..., stop=FixedIters())`` returns the same factors as
    ``srsvd(...)`` bitwise, plus the report.
    """

    q: int | None = None
    certificate: bool = True
    can_stop_early = False

    def resolve_q(self, q: int) -> int:
        return q if self.q is None else self.q


@dataclasses.dataclass(frozen=True)
class PVEStop(StopRule):
    """dashSVD per-vector-error early stopping (Feng et al. §4).

    Stop once every monitored component's singular-value estimate moved
    by at most ``tol`` *relative to the head estimate* since the
    previous iteration:

        max_{i < k} |s_i^(t) - s_i^(t-1)| / s_1^(t)  <=  tol

    Estimates come from the iteration's own R factor (alpha
    back-corrected under spectral schedules), so the criterion costs no
    contact with X.  ``prev_s`` starts at zero, which makes the first
    PVE row contain ``s_1/s_1 = 1`` — a rule can therefore never fire
    before it has seen two estimates.  ``k=None`` monitors the target
    rank; ``qmax=None`` defers the ceiling to the call's ``q``.
    """

    tol: float = 1e-2
    qmax: int | None = None
    k: int | None = None
    certificate: bool = True

    def __post_init__(self):
        if not (self.tol >= 0.0):
            raise ValueError(f"need tol >= 0, got {self.tol=}")

    def decide(self, s, pve, state):
        worst = jnp.max(jnp.where(state.mask, pve, -jnp.inf))
        return worst <= self.tol


@dataclasses.dataclass(frozen=True)
class ResidualStop(StopRule):
    """Shifted Frobenius-residual early stopping.

    Stop once the K-dimensional basis provably captures enough energy:

        sqrt(max(0, ||Xbar||_F^2 - sum_i s_i^2)) / ||Xbar||_F  <=  tol

    with ``s = svdvals(R)`` of the iteration's QR.  For the two-QR body
    ``sum s_i^2 = ||Xbar Q'||_F^2 <= ||Q^T Xbar||_F^2`` makes this a
    rigorous residual bound; under a spectral schedule the alpha
    back-corrected estimates make it an (accurate) estimate instead —
    the certified number is always the end-of-run
    ``posterior_rel_err``, which uses the exactly-computed final S.
    The bound argument requires every iteration to run under the
    target ``mu`` itself: annealed scalar profiles iterate
    ``X - c_t mu 1^T``, whose un-removed ``(1 - c_t)`` mean energy
    inflates ``sum s_i^2`` past ``||Xbar||_F^2`` and would certify
    garbage — drivers reject that pairing up front
    (``validate_rule_schedule``).  Needs ``||Xbar||_F^2`` once at
    setup, via the engine's existing ``fro_norm2`` probe (the
    criterion consumes it, so there is no ``certificate`` opt-out on
    this rule); no per-iteration contact.
    """

    tol: float = 1e-2
    qmax: int | None = None
    certificate: bool = True

    def __post_init__(self):
        if not (self.tol >= 0.0):
            raise ValueError(f"need tol >= 0, got {self.tol=}")
        if not self.certificate:
            raise ValueError(
                "ResidualStop always needs ||Xbar||_F^2 — its criterion "
                "consumes it — so certificate=False would not skip the "
                "probe; omit the flag (use PVEStop(certificate=False) "
                "to stop without any fro_norm2 contact)")

    @property
    def needs_fro2(self) -> bool:
        return True        # the criterion itself consumes it

    def init(self, dtype, K, qmax, k, fro2=None):
        if fro2 is None:
            raise ValueError(
                "ResidualStop needs ||Xbar||_F^2 at init — drivers must "
                "compute it via engine.xbar_fro_norm2 (needs_fro2 is "
                "always True for this rule)")
        return super().init(dtype, K, qmax, k, fro2)

    def decide(self, s, pve, state):
        fro2 = jnp.maximum(state.fro2, jnp.finfo(s.dtype).tiny)
        rel2 = jnp.clip(1.0 - jnp.sum(s * s) / fro2, 0.0, None)
        return rel2 <= self.tol * self.tol


def as_rule(stop) -> StopRule | None:
    """Normalize ``stop``: None passes through (no monitoring), an int
    becomes ``FixedIters(int)``, a rule is itself."""
    if stop is None or isinstance(stop, StopRule):
        return stop
    if isinstance(stop, int) and not isinstance(stop, bool):
        return FixedIters(stop)
    raise TypeError(
        f"stop must be a StopRule, an int, or None; got "
        f"{type(stop).__name__}")


def validate_certified_schedule(sched, shifted: bool, *,
                                what: str) -> None:
    """Reject schedules whose iterates break the captured-energy
    certificate — the shared half of ``validate_rule_schedule`` that the
    adaptive range finder (DESIGN.md §16) validates against too.

    Any tolerance criterion built on PR 5's identity ``||Xbar - Q Q^T
    Xbar||^2 = ||Xbar||^2 - ||Q^T Xbar||^2`` needs every contact to run
    under the target shift itself; an annealed scalar profile
    (``scale_at != 1``) iterates ``X - c_t mu 1^T``, whose un-removed
    ``(1 - c_t)`` mean energy inflates the captured ``sum s^2`` past
    ``||Xbar||_F^2`` and would certify garbage.  Unshifted runs
    (``mu=None``) have no mean component, so any schedule is fine.
    """
    if not shifted or sched.runs_target_shift:
        return
    raise ValueError(
        f"{what}'s residual certificate is only valid when every "
        "iteration runs under the target shift itself; "
        f"{type(sched).__name__} anneals it (scale_at != 1), which "
        "would inflate the captured energy and certify garbage. "
        f"Use PVEStop / FixedIters with this schedule, or a "
        f"constant-scale schedule with {what}")


def validate_rule_schedule(rule: StopRule | None, sched,
                           shifted: bool) -> None:
    """Reject criterion/schedule pairings whose math does not hold.

    ``ResidualStop``'s mid-loop bound reads svdvals of the iterate of
    ``X - c_t mu 1^T``; with an annealed scalar profile (``c_t != 1``)
    the un-removed ``(1 - c_t)`` mean energy inflates the captured
    ``sum s^2`` past ``||Xbar||_F^2``, the clipped residual reads as
    zero, and the rule would stop far from convergence while claiming
    a certification (DESIGN.md §12).  Unshifted runs (``mu=None``)
    have no mean component, so any schedule is fine there.
    """
    if rule is None:
        return
    if isinstance(rule, ResidualStop):
        validate_certified_schedule(sched, shifted, what="ResidualStop")


def resolve_fro2(rule: StopRule | None, eng, op, mu):
    """``||Xbar||_F^2`` when the rule needs it, None otherwise — with an
    actionable error for operators that provide no ``fro_norm2`` probe
    (e.g. a bare ``CallableOp``): the caller can drop the certificate,
    or must implement the probe for ``ResidualStop``."""
    if rule is None or not rule.needs_fro2:
        return None
    try:
        with obs.span("repro.norm"):
            return eng.xbar_fro_norm2(op, mu)
    except NotImplementedError as e:
        raise ValueError(
            f"{type(rule).__name__} needs ||Xbar||_F^2 but "
            f"{type(op).__name__} provides no fro_norm2 probe; pass "
            "certificate=False to skip the posterior certificate "
            "(PVEStop / FixedIters), or implement fro_norm2 on the "
            "operator (ResidualStop cannot run without it)") from e


def concrete_done(state: StopState) -> bool:
    """Host-loop break predicate, with an actionable error under trace."""
    try:
        return bool(state.done)
    except jax.errors.ConcretizationTypeError as e:
        raise ValueError(
            "early stopping with loop='python' needs concrete values; "
            "trace through loop='fori' (svd_jit), whose lax.while_loop "
            "carries the stop state instead") from e


def posterior_rel_err(S, fro2, m: int, K: int | None = None):
    """Certified relative Frobenius error of rank-k factors ``(U_k, S,
    Vt_k)`` built from an orthonormal basis Q.

    The identity (DESIGN.md §12) is exact in exact arithmetic:

        ||Xbar - U_k S_k Vt_k||_F^2 = ||Xbar||_F^2 - sum_{i<=k} S_i^2

    because the error splits orthogonally into the out-of-subspace part
    ``||Xbar||^2 - ||Q^T Xbar||^2`` and the in-subspace truncation
    ``||Q^T Xbar||^2 - sum_k S^2``.  The added slack
    ``8 eps sqrt(m K)`` — with K the *sample width* of the (m, K)
    basis whose orthonormality drift the slack covers, not the k
    values kept in ``S`` — plus the float accumulation of the fro2
    probe, makes the returned value an upper bound in floating point
    as well.
    """
    S = jnp.asarray(S)
    if K is None:
        K = S.shape[0]
    eps = jnp.finfo(S.dtype).eps
    fro2 = jnp.maximum(jnp.asarray(fro2, S.dtype),
                       jnp.finfo(S.dtype).tiny)
    rel2 = jnp.clip(1.0 - jnp.sum(S * S) / fro2, 0.0, None)
    slack = 8.0 * eps * jnp.sqrt(jnp.asarray(float(m * K), S.dtype))
    return jnp.sqrt(rel2) + slack


def build_report(rule: StopRule, state: StopState, S, m: int,
                 qmax: int, fro2=None, *,
                 k_found: int | None = None) -> ConvergenceReport:
    """Assemble the report from the final stop state and the returned
    top-k singular values (``S``).  ``k_found`` is the basis width the
    driver used (its K on the fixed paths); ``k_eff`` counts the
    monitored components whose final PVE sits inside the rule's
    ``k_eff_band`` — 0 when no power iteration ran (the init PVE is
    inf), since nothing was iterated to convergence."""
    with obs.span("repro.report"):
        post = None if fro2 is None else posterior_rel_err(
            S, fro2, m, K=state.prev_s.shape[0])
        k_eff = jnp.sum(
            state.mask & (state.pve <= rule.k_eff_band)).astype(jnp.int32)
        return ConvergenceReport(
            iters_run=state.t, pve_trace=state.trace,
            sigma_estimates=state.prev_s, posterior_rel_err=post,
            xbar_fro2=None if fro2 is None else jnp.asarray(fro2),
            qmax=qmax, k_eff=k_eff, k_found=k_found)


def run_power_loop(sched, rule: StopRule | None, eng, op, Q, mu,
                   qmax: int, sstate, tstate, *, loop: str):
    """Drive the scheduled power loop under an (optional) stop rule —
    the single loop driver behind ``srsvd``'s ``loop="python"`` and
    ``loop="fori"`` spellings, ruled or not, so the (schedule state,
    stop state) init and update order cannot drift between them (the
    distributed paths run their own collective loops against the same
    ``init``/``update``/``done`` contract).

    Returns ``(Q, schedule_state, stop_state)``.  The jit form uses a
    ``lax.while_loop`` when the rule can fire early (true early exit
    under jit — XLA executes only the iterations the rule allows) and
    keeps the fixed-trip ``lax.fori_loop`` otherwise, so ``rule=None``
    and ``FixedIters`` trace exactly like the pre-rule path.
    """
    from repro.core import schedule as _schedule

    def step(t, Q, sstate, tstate):
        a = (sched.alpha(sstate) if rule is not None and sched.spectral
             else None)
        Q, sstate, R = _schedule.power_step(sched, eng, op, Q, mu, t,
                                            sstate)
        if rule is not None:
            tstate = rule.update(tstate, R, a)
        return Q, sstate, tstate

    early = rule is not None and rule.can_stop_early
    if loop == "python":
        for t in range(qmax):
            if early and concrete_done(tstate):
                break
            with obs.span("repro.power_step", t=t):
                Q, sstate, tstate = step(t, Q, sstate, tstate)
        return Q, sstate, tstate
    if loop == "fori":
        if early:
            return lax.while_loop(
                lambda c: (c[2].t < qmax) & ~c[2].done,
                lambda c: step(c[2].t, *c),
                (Q, sstate, tstate))
        return lax.fori_loop(
            0, qmax, lambda t, c: step(t, *c), (Q, sstate, tstate))
    raise ValueError(f"loop must be 'python' or 'fori', got {loop!r}")
