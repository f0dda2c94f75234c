"""Plain reference of the shifted randomized SVD, and the control.

This module imports nothing of the program.  It computes Algorithm 1 of
arXiv:1911.11772 on the explicitly centred matrix ``Xbar = X - mu 1^T``
(mu the column mean): the sample ``Xbar Omega`` with ``Omega =
normal(key, (n, K))``, ``q`` power iterations with a QR after each
product, the projection ``Y = Q^T Xbar`` and its SVD, taken through
the QR of ``Y^T``.  The program draws its Gaussian test matrix from the
key the same way (``factorize(key=...)``), so in exact arithmetic the
program and the reference return the same factors; what separates them
is rounding alone.

``dtype=float32`` runs every product at ``Precision.HIGHEST``.
``dtype=bfloat16`` is the control: the same algorithm with every input,
product and factor rounded to bfloat16 (the QR and SVD run in float32
on the rounded values, and their results are rounded again).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
BF16 = jnp.bfloat16


class Factors(NamedTuple):
    U: jax.Array
    S: jax.Array
    Vt: jax.Array


class Report(NamedTuple):
    posterior_rel_err: jax.Array


@jax.jit
def centred(X):
    """``X - mu 1^T`` with mu the column mean (the mean image)."""
    return X - jnp.mean(X, axis=1, keepdims=True)


def certificate(S, fro2, m: int, K: int):
    """The posterior certificate: factors taken from an orthonormal
    basis leave ``||Xbar||^2 - sum S^2`` of residual, so the relative
    residual is sqrt(1 - sum S^2 / ||Xbar||^2), plus the float32 slack
    8 eps sqrt(m K) that the program adds."""
    rel2 = jnp.clip(1.0 - jnp.sum(S * S) / fro2, 0.0, None)
    slack = 8.0 * float(jnp.finfo(F32).eps) * float(np.sqrt(m * K))
    return jnp.sqrt(rel2) + slack


@functools.partial(jax.jit, static_argnames=("k", "K", "q", "dtype"))
def srsvd(Xbar, key, *, k: int, K: int, q: int, dtype=F32):
    """Rank-k factors of Xbar and their certificate, in ``dtype``."""
    low = jnp.dtype(dtype) == jnp.dtype(BF16)
    prec = lax.Precision.DEFAULT if low else lax.Precision.HIGHEST

    def cast(a):
        return a.astype(BF16) if low else a

    def mm(a, b):
        return cast(jnp.matmul(a, b, precision=prec,
                               preferred_element_type=BF16 if low else F32))

    def qr(a):
        Q, R = jnp.linalg.qr(a.astype(F32), mode="reduced")
        return cast(Q), cast(R)

    m, n = Xbar.shape
    A = cast(Xbar)
    omega = cast(jax.random.normal(key, (n, K), F32))
    Q, _ = qr(mm(A, omega))
    for _ in range(q):
        Qp, _ = qr(mm(A.T, Q))
        Q, _ = qr(mm(A, Qp))
    Y = mm(Q.T, A)                                   # (K, n)
    Qy, Ry = qr(Y.T)                                 # Y = Ry^T Qy^T
    U1, S, Wt = jnp.linalg.svd(Ry.T.astype(F32), full_matrices=False)
    U1, S, Wt = cast(U1), cast(S), cast(Wt)
    U = mm(Q, U1)[:, :k]
    Vt = mm(Wt, Qy.T)[:k]
    S = S[:k]
    fro2 = cast(jnp.sum(A.astype(F32) * A.astype(F32)))
    cert = cast(certificate(S.astype(F32), fro2.astype(F32), m, K))
    return (Factors(U.astype(F32), S.astype(F32), Vt.astype(F32)),
            Report(cert.astype(F32)))


@jax.jit
def residual2(Xbar, U, S, Vt):
    """``||Xbar - U diag(S) Vt||_F^2`` and ``||Xbar||_F^2``."""
    R = Xbar - jnp.matmul(U * S, Vt, precision=lax.Precision.HIGHEST)
    return jnp.sum(R * R), jnp.sum(Xbar * Xbar)


def control(x, k, *, K, q, center, key, **_):
    """The control, put in the program's place: ``factorize``'s
    signature, the bfloat16 reference's factors and certificate."""
    Xbar = centred(x) if center else x
    return srsvd(Xbar, key, k=k, K=K, q=q, dtype=BF16)
