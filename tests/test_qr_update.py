"""Rank-1 thin-QR update (paper line 6) vs the exact re-factorization."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import qr_rank1_update
from repro.core.ref import qr_rank1_update_ref


@pytest.mark.parametrize("m,K", [(16, 4), (64, 16), (200, 32), (33, 7)])
def test_qr_rank1_update_matches_refactorization(m, K, rng):
    A = rng.standard_normal((m, K)).astype(np.float32)
    Q, R = np.linalg.qr(A)
    u = rng.standard_normal(m).astype(np.float32)
    v = rng.standard_normal(K).astype(np.float32)

    Q2, R2 = qr_rank1_update(jnp.asarray(Q), jnp.asarray(R),
                             jnp.asarray(u), jnp.asarray(v))
    Q2, R2 = np.asarray(Q2), np.asarray(R2)

    target = A + np.outer(u, v)
    np.testing.assert_allclose(Q2 @ R2, target, atol=2e-5)
    # orthonormal columns
    np.testing.assert_allclose(Q2.T @ Q2, np.eye(K), atol=2e-5)
    # R upper triangular
    assert np.abs(np.tril(R2, -1)).max() < 2e-5


def test_qr_update_zero_vectors(rng):
    """u=0 or v=0 must leave the factorization unchanged (same subspace)."""
    m, K = 40, 8
    A = rng.standard_normal((m, K)).astype(np.float32)
    Q, R = np.linalg.qr(A)
    Q2, R2 = qr_rank1_update(jnp.asarray(Q), jnp.asarray(R),
                             jnp.zeros(m), jnp.zeros(K))
    np.testing.assert_allclose(np.asarray(Q2) @ np.asarray(R2), A,
                               atol=2e-5)


def test_qr_update_u_in_range_of_q(rng):
    """u inside range(Q): the extension column is degenerate — still OK."""
    m, K = 30, 6
    A = rng.standard_normal((m, K)).astype(np.float32)
    Q, R = np.linalg.qr(A)
    u = (Q @ rng.standard_normal(K)).astype(np.float32)   # in range(Q)
    v = rng.standard_normal(K).astype(np.float32)
    Q2, R2 = qr_rank1_update(jnp.asarray(Q), jnp.asarray(R),
                             jnp.asarray(u), jnp.asarray(v))
    np.testing.assert_allclose(np.asarray(Q2) @ np.asarray(R2),
                               A + np.outer(u, v), atol=3e-5)
    np.testing.assert_allclose(np.asarray(Q2).T @ np.asarray(Q2),
                               np.eye(K), atol=3e-5)


def test_qr_update_jit_compatible(rng):
    m, K = 32, 8
    A = rng.standard_normal((m, K)).astype(np.float32)
    Q, R = np.linalg.qr(A)
    u = rng.standard_normal(m).astype(np.float32)
    v = rng.standard_normal(K).astype(np.float32)
    jitted = jax.jit(qr_rank1_update)
    Q2, R2 = jitted(jnp.asarray(Q), jnp.asarray(R), jnp.asarray(u),
                    jnp.asarray(v))
    np.testing.assert_allclose(np.asarray(Q2) @ np.asarray(R2),
                               A + np.outer(u, v), atol=2e-5)


def _factors(rng, m, K):
    """(Q, R, u, v) on the device, ready, so that making them is not
    counted with the update's own lowerings."""
    A = rng.standard_normal((m, K)).astype(np.float32)
    Q, R = np.linalg.qr(A)
    u = rng.standard_normal(m).astype(np.float32)
    v = rng.standard_normal(K).astype(np.float32)
    return [jax.block_until_ready(jnp.asarray(x)) for x in (Q, R, u, v)]


def _lowerings(*args):
    """(lowerings counted while the update runs, its result)."""
    before = obs.total("lowered")
    out = jax.block_until_ready(qr_rank1_update(*args))
    return obs.total("lowered") - before, out


def test_qr_update_repeat_call_lowers_nothing(rng):
    """A second call at the same shapes, with new values, reuses the
    compiled update, and still matches the re-factorization oracle."""
    m, K = 64, 16
    first = _factors(rng, m, K)
    _lowerings(*first)
    Q, R, u, v = _factors(rng, m, K)
    lowered, (Q2, R2) = _lowerings(Q, R, u, v)
    assert lowered == 0
    Q, R, u, v = (np.asarray(x) for x in (Q, R, u, v))
    Qr, Rr = qr_rank1_update_ref(Q, R, u, v)
    Q2, R2 = np.asarray(Q2), np.asarray(R2)
    np.testing.assert_allclose(Q2 @ R2, Qr @ Rr, atol=2e-5)
    np.testing.assert_allclose(Q2 @ Q2.T, Qr @ Qr.T, atol=2e-5)
    np.testing.assert_allclose(np.abs(np.diag(R2)), np.abs(np.diag(Rr)),
                               atol=2e-5)
    np.testing.assert_allclose(Q2.T @ Q2, np.eye(K), atol=2e-5)


def test_qr_update_lowers_once_per_new_K(rng):
    """The update compiles once per shape: from a cleared cache the
    first call at K lowers once, a repeat none, a new K once more."""
    qr_rank1_update.clear_cache()
    m = 48
    counts = [_lowerings(*_factors(rng, m, K))[0] for K in (10, 10, 11)]
    assert counts == [1, 0, 1]


# ---------------------------------------------------------------------
# Downdate / singular-R edges (DESIGN.md §16 caveat): the Givens sweeps
# must keep Q' orthonormal and Q' R' == Q R + u v^T to roundoff even
# when R carries exactly-zero pivots — the `_givens` tiny-guard passes
# identity rotations through them.  What the update *cannot* do (rotate
# energy into null directions a singular sketch never had) is a caller
# contract, documented and handled by srsvd's use_qr_update=False
# spelling; these tests pin the guard itself.


def _assert_thin_qr_of(Q2, R2, target, K, tol=5e-5):
    Q2, R2 = np.asarray(Q2), np.asarray(R2)
    scale = max(1.0, np.abs(target).max())
    np.testing.assert_allclose(Q2 @ R2, target, atol=tol * scale)
    np.testing.assert_allclose(Q2.T @ Q2, np.eye(K), atol=tol)
    assert np.abs(np.tril(R2, -1)).max() < tol * scale


@pytest.mark.parametrize("zeros", [1, 3, 6])
def test_qr_update_exactly_singular_diagonal_R(rng, zeros):
    """R = diag(S) with a run of exactly-zero pivots — the shape every
    refresh of a base factored at K > rank hits (base S has zero tail).
    The update must stay an orthonormal thin QR of QR + uv^T."""
    m, K = 40, 8
    Q, _ = np.linalg.qr(rng.standard_normal((m, K)).astype(np.float32))
    s = np.concatenate([np.linspace(9.0, 1.0, K - zeros),
                        np.zeros(zeros)]).astype(np.float32)
    R = np.diag(s)
    u = rng.standard_normal(m).astype(np.float32)
    v = rng.standard_normal(K).astype(np.float32)
    Q2, R2 = qr_rank1_update(jnp.asarray(Q), jnp.asarray(R),
                             jnp.asarray(u), jnp.asarray(v))
    _assert_thin_qr_of(Q2, R2, Q @ R + np.outer(u, v), K)


def test_qr_update_zero_rows_in_R(rng):
    """Zero *rows* of a non-diagonal R (deficient leading block)."""
    m, K = 30, 6
    Q, _ = np.linalg.qr(rng.standard_normal((m, K)).astype(np.float32))
    R = np.triu(rng.standard_normal((K, K))).astype(np.float32)
    R[2] = 0.0
    R[4] = 0.0
    u = rng.standard_normal(m).astype(np.float32)
    v = rng.standard_normal(K).astype(np.float32)
    Q2, R2 = qr_rank1_update(jnp.asarray(Q), jnp.asarray(R),
                             jnp.asarray(u), jnp.asarray(v))
    _assert_thin_qr_of(Q2, R2, Q @ R + np.outer(u, v), K)


def test_qr_downdate_to_singular(rng):
    """A rank-1 *downdate* that makes the result exactly singular:
    subtract the last column's contribution entirely.  The sweeps must
    not divide by the vanishing pivot (tiny-guard) and the returned R'
    must expose the singularity rather than hide it."""
    m, K = 32, 5
    A = rng.standard_normal((m, K)).astype(np.float32)
    Q, R = np.linalg.qr(A)
    # u v^T = -(A e_K) e_K^T: column K of A + uv^T is exactly zero
    u = (-A[:, K - 1]).astype(np.float32)
    v = np.zeros(K, np.float32)
    v[K - 1] = 1.0
    Q2, R2 = qr_rank1_update(jnp.asarray(Q), jnp.asarray(R),
                             jnp.asarray(u), jnp.asarray(v))
    target = A + np.outer(u, v)
    _assert_thin_qr_of(Q2, R2, target, K)
    # the downdated matrix is singular and R' says so
    assert np.abs(np.asarray(R2)[:, K - 1]).max() < 5e-5 * \
        max(1.0, np.abs(A).max())


def test_qr_block_downdate(rng):
    """Rank-b block *downdate* (negative update) through the block
    path, including one width — the refresh lane's retraction case."""
    from repro.core import qr_block_update
    m, K, b = 36, 7, 3
    A = rng.standard_normal((m, K)).astype(np.float32)
    Q, R = np.linalg.qr(A)
    U_b = rng.standard_normal((m, b)).astype(np.float32)
    W_b = rng.standard_normal((K, b)).astype(np.float32)
    Q2, R2 = qr_block_update(jnp.asarray(Q), jnp.asarray(R),
                             jnp.asarray(-U_b), jnp.asarray(W_b))
    _assert_thin_qr_of(Q2, R2, A - U_b @ W_b.T, K)


def test_qr_block_update_width_mismatch_raises(rng):
    from repro.core import qr_block_update
    m, K = 20, 4
    Q, R = np.linalg.qr(rng.standard_normal((m, K)).astype(np.float32))
    with pytest.raises(ValueError, match="matching update widths"):
        qr_block_update(jnp.asarray(Q), jnp.asarray(R),
                        jnp.zeros((m, 2)), jnp.zeros((K, 3)))


def test_qr_mean_shift_update_folds_shift(rng):
    """qr_mean_shift_update == rank-1 update with u = -(mu'-mu): the
    paper's line-6 shift algebra applied incrementally."""
    from repro.core import qr_mean_shift_update
    m, K = 28, 6
    A = rng.standard_normal((m, K)).astype(np.float32)
    Q, R = np.linalg.qr(A)
    mu_old = rng.standard_normal(m).astype(np.float32)
    mu_new = rng.standard_normal(m).astype(np.float32)
    Q2, R2 = qr_mean_shift_update(jnp.asarray(Q), jnp.asarray(R),
                                  mu_old, mu_new)
    d = mu_new - mu_old
    Q3, R3 = qr_rank1_update(jnp.asarray(Q), jnp.asarray(R),
                             jnp.asarray(-d), jnp.ones(K))
    assert bool(jnp.all(Q2 == Q3)) and bool(jnp.all(R2 == R3))
    _assert_thin_qr_of(Q2, R2, A - np.outer(d, np.ones(K)), K)
