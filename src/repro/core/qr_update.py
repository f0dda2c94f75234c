"""Rank-1 / rank-b thin-QR updates (Golub & Van Loan, Matrix
Computations §12.5).

Given a thin factorization ``A = Q R`` (Q: m x K, R: K x K) and vectors
``u`` (m,), ``v`` (K,), compute a thin QR of ``A + u v^T`` in O(mK + K^2)
— this is the paper's line 6, the step that folds the shift ``-mu 1^T``
into the sample-matrix basis without re-touching X.

``qr_block_update`` generalizes to rank-b updates ``A + U_b W_b^T``
(b sequential rank-1 applications, so ``b=1`` is *bit-identical* to
``qr_rank1_update`` by construction — the incremental property suite
pins that), and ``qr_mean_shift_update`` is the paper's shift algebra
applied incrementally: when the column mean moves from ``mu`` to
``mu'``, fold the rank-1 correction ``-(mu' - mu) v^T`` into the cached
factorization instead of recomputing it (DESIGN.md §17).

TPU adaptation note: the classical formulation is a sequence of scalar
Givens rotations.  We keep the rotation *sequence* (it is inherently
sequential along K) but each rotation is applied to whole rows/columns as
vector ops (VPU-friendly), driven by ``lax.fori_loop``.  K is small
(K = 2k <= a few hundred), so the sweeps' arithmetic is cheap; see
DESIGN.md §3.  ``qr_rank1_update`` is a module-level ``jax.jit``: the
loop bodies are closures made fresh on each call, so an eager call
traces and lowers both sweeps again every time, with the device idle
meanwhile.  Jitted, it compiles once per shape and dtype of its
arguments; inside another ``jit`` it is inlined.

Known edge (DESIGN.md §16, pinned by ``tests/test_qr_update.py``): when
R is *exactly* singular — zero pivots from a base factored past its
rank, or a downdate that zeroes a column — the Givens sweeps still
return an orthonormal Q' and a triangular R' with ``Q' R' = Q R +
u v^T`` to roundoff: the ``_givens`` tiny-guard passes identity
rotations through zero pivots, and the extension column gets a second
Gram-Schmidt pass so an in-span ``u`` contributes *orthogonal* noise
rather than oblique junk (the singular-downdate rotation angle is
noise-determined, so obliquity there would corrupt the basis) —
but callers folding a correction into null directions of a singular
sketch (fixed K > rank) should use the re-factorization spelling
(``use_qr_update=False``) instead; the update cannot rotate energy into
directions the factorization never had.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.contact import basis_matmul


def _givens(a, b):
    """Return (c, s) with [[c, s], [-s, c]] @ [a, b] = [r, 0]."""
    r = jnp.hypot(a, b)
    safe = r > jnp.finfo(a.dtype).tiny
    c = jnp.where(safe, a / jnp.where(safe, r, 1.0), 1.0)
    s = jnp.where(safe, b / jnp.where(safe, r, 1.0), 0.0)
    return c, s


def _rot_rows(M, i, c, s):
    """Left-apply a Givens rotation to rows (i, i+1) of M."""
    two = lax.dynamic_slice_in_dim(M, i, 2, axis=0)
    hi = c * two[0] + s * two[1]
    lo = -s * two[0] + c * two[1]
    return lax.dynamic_update_slice_in_dim(M, jnp.stack([hi, lo]), i, axis=0)


def _rot_cols(M, i, c, s):
    """Right-apply the transpose rotation to columns (i, i+1) of M."""
    two = lax.dynamic_slice_in_dim(M, i, 2, axis=1)
    hi = c * two[:, 0] + s * two[:, 1]
    lo = -s * two[:, 0] + c * two[:, 1]
    return lax.dynamic_update_slice_in_dim(
        M, jnp.stack([hi, lo], axis=1), i, axis=1)


@jax.jit
def qr_rank1_update(Q: jax.Array, R: jax.Array, u: jax.Array, v: jax.Array
                    ) -> tuple[jax.Array, jax.Array]:
    """Thin QR of ``Q @ R + u v^T``.

    Returns (Q', R') with Q': m x K orthonormal, R': K x K upper triangular.
    """
    m, K = Q.shape
    dt = Q.dtype
    u = u.astype(dt)
    v = v.astype(dt)

    # Project u into / out of range(Q):  u = Q w + rho * q_ext.  The
    # second Gram-Schmidt pass (CGS2, correction folded into w so the
    # decomposition stays exact) matters at the singular-downdate edge:
    # with u numerically inside range(Q) the one-pass residual is pure
    # cancellation noise, NOT orthogonal to Q — and a downdate that
    # zeroes a pivot makes the final re-triangularization rotation's
    # angle noise-determined O(1), mixing that junk into the returned
    # basis.  Orthogonal junk is harmless; oblique junk destroys Q'.
    w = basis_matmul(Q.T, u)                      # (K,)
    r = u - basis_matmul(Q, w)
    c2 = basis_matmul(Q.T, r)
    r = r - basis_matmul(Q, c2)
    w = w + c2
    rho = jnp.linalg.norm(r)
    tiny = jnp.asarray(jnp.finfo(dt).tiny, dt)
    q_ext = r / jnp.maximum(rho, tiny)

    Qe = jnp.concatenate([Q, q_ext[:, None]], axis=1)        # m x (K+1)
    we = jnp.concatenate([w, rho[None]])                     # (K+1,)
    Re = jnp.concatenate([R, jnp.zeros((1, K), dt)], axis=0) # (K+1) x K

    # Sweep 1 (bottom-up): rotate w to ||w|| e1; R becomes upper Hessenberg.
    def body1(t, carry):
        Qe, Re, we = carry
        i = K - 1 - t
        c, s = _givens(we[i], we[i + 1])
        wi = c * we[i] + s * we[i + 1]
        we = lax.dynamic_update_slice_in_dim(
            we, jnp.stack([wi, jnp.zeros((), dt)]), i, axis=0)
        Re = _rot_rows(Re, i, c, s)
        Qe = _rot_cols(Qe, i, c, s)
        return Qe, Re, we

    Qe, Re, we = lax.fori_loop(0, K, body1, (Qe, Re, we))

    # Rank-1 add now touches only the first row.
    Re = Re.at[0].add(we[0] * v)

    # Sweep 2 (top-down): restore upper-triangular from upper Hessenberg.
    def body2(i, carry):
        Qe, Re = carry
        c, s = _givens(Re[i, i], Re[i + 1, i])
        Re = _rot_rows(Re, i, c, s)
        Qe = _rot_cols(Qe, i, c, s)
        return Qe, Re

    Qe, Re = lax.fori_loop(0, K, body2, (Qe, Re))

    return Qe[:, :K], Re[:K, :]


def qr_block_update(Q: jax.Array, R: jax.Array, U_b: jax.Array,
                    W_b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Thin QR of ``Q @ R + U_b @ W_b^T`` — the rank-b block update.

    ``U_b`` is (m, b) and ``W_b`` is (K, b); 1-D inputs are treated as
    single columns, so the rank-1 case needs no reshaping at call
    sites.  Implemented as ``b`` sequential Givens rank-1 applications
    (each O(mK + K^2), total O(b·mK)): ``b=1`` is bit-identical to
    :func:`qr_rank1_update` by construction, which is the property the
    serving layer's refresh lane leans on when it routes rank-1
    refreshes through this path.  ``b=0`` returns the factors
    untouched.

    Returns (Q', R') with Q': m x K orthonormal, R': K x K upper
    triangular.
    """
    U_b = jnp.asarray(U_b)
    W_b = jnp.asarray(W_b)
    if U_b.ndim == 1:
        U_b = U_b[:, None]
    if W_b.ndim == 1:
        W_b = W_b[:, None]
    if U_b.shape[1] != W_b.shape[1]:
        raise ValueError(
            "qr_block_update needs matching update widths, got "
            f"U_b {U_b.shape} vs W_b {W_b.shape}")
    for j in range(U_b.shape[1]):
        Q, R = qr_rank1_update(Q, R, U_b[:, j], W_b[:, j])
    return Q, R


def qr_mean_shift_update(Q: jax.Array, R: jax.Array, mu_old, mu_new,
                         v: jax.Array | None = None,
                         ) -> tuple[jax.Array, jax.Array]:
    """Fold a *moved column mean* into a cached thin QR: the factors
    held ``Xbar_old = X - mu_old 1^T``; appended rows (or recounted
    events) moved the mean to ``mu_new``, so the new target is

        ``Xbar_new = Xbar_old - (mu_new - mu_old) 1^T``

    — one more rank-1 correction of exactly the paper's line-6 shape,
    applied incrementally instead of recomputing from scratch
    (DESIGN.md §17).  ``v`` is the right-hand vector the all-ones row
    projects to in the factors' column space — ``Omega^T 1`` for a
    sample-matrix QR (the ``shift_mode="exact"`` convention), ``Vt @
    1_n`` for cached SVD factors — defaulting to ``1_K`` (the printed
    Algorithm 1 / ``shift_mode="paper"`` convention).  ``mu_old=None``
    means the base was unshifted.
    """
    d = (jnp.asarray(mu_new, Q.dtype) if mu_old is None
         else jnp.asarray(mu_new, Q.dtype) - jnp.asarray(mu_old, Q.dtype))
    if v is None:
        v = jnp.ones((R.shape[1],), Q.dtype)
    return qr_rank1_update(Q, R, -d, v)
