"""ContactEngine — the single owner of every product S-RSVD performs.

The paper's whole value proposition is that the algorithm touches the
data matrix only through products, so the shifted matrix ``X - mu 1^T``
never exists.  Before this module, the rank-1 shift algebra behind that
trick was re-derived at three independent call sites (the ``LinOp``
base-class fallbacks, the TPU-vs-XLA branching in ``kernels/ops.py``,
and a hand-rolled copy inside ``distributed.py``'s shard_map body).
Now it lives here, once (DESIGN.md §2-§3):

  (X - mu 1^T)   @ B  ==  X   @ B - u w^T   with  u = mu,   w = 1^T B
  (X - mu 1^T)^T @ B  ==  X^T @ B - u w^T   with  u = 1_n,  w = mu^T B

Both contact points therefore reduce to one primitive — a rank-1-
corrected matmul ``op(A) @ B - u w^T`` — and backends are just
implementations of that primitive:

  pallas_tpu  fused rank-1-epilogue Pallas kernel (TPU; accumulator and
              epilogue stay in VMEM, one HBM write-back)
  xla         plain-XLA composition (CPU/GPU fallback, sparse operands)
  interpret   the Pallas kernel body executed in Python on CPU — used
              by tests to validate the kernel itself off-TPU

``ContactEngine`` binds a backend and exposes the operator-level
contact points (``matmat`` / ``rmatmat`` / ``shifted_*``) that
``srsvd``, ``PCA`` and the blocked/streaming operators call.  The
distributed path cannot route whole products through an engine (its
products are psum-composed inside shard_map), so it uses the shared
shift-vector/correction helpers below — the algebra still has exactly
one home.
"""
from __future__ import annotations

import dataclasses
import functools
from collections.abc import Callable

import jax
import jax.numpy as jnp

from repro import obs

# --------------------------------------------------------------------------
# The rank-1 shift algebra.  THE single implementation: every shift
# correction in the codebase is one of these four functions.
# --------------------------------------------------------------------------


def shift_vectors_matmat(B: jax.Array, mu: jax.Array):
    """(u, w) such that (X - mu 1^T) @ B == X @ B - u w^T."""
    return mu, B.sum(axis=0)


def shift_vectors_rmatmat(B: jax.Array, mu: jax.Array, n: int, dtype):
    """(u, w) such that (X - mu 1^T)^T @ B == X^T @ B - u w^T."""
    return jnp.ones((n,), dtype), mu @ B


def rank1_correct(P: jax.Array, u: jax.Array, w: jax.Array) -> jax.Array:
    """``P - u w^T`` — the only place the shift outer product is spelled.

    Used directly by call sites that already hold the uncorrected
    product (e.g. a psum-composed local product inside shard_map, where
    the K-vector ``w`` rode the same collective as ``P``).

    Operands are cast to the standard-lattice result dtype explicitly
    (an integer operator's ``u = 1_n`` meets a float ``w``), so the
    correction is strict-promotion clean.
    """
    P, u, w = _upcast_correction(P, u, w)
    return P - u[:, None] * w[None, :]


def rank1_restore(P: jax.Array, u: jax.Array, w: jax.Array) -> jax.Array:
    """``P + u w^T`` — the inverse correction (decompression paths)."""
    P, u, w = _upcast_correction(P, u, w)
    return P + u[:, None] * w[None, :]


def basis_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` among the small basis factors (Q, R, U1, ...: O(m K^2)
    work), at full float32 precision.  A TPU multiplies float32 at
    bfloat16 precision by default, which leaves a product basis
    orthonormal only to about 2e-3 and moves the singular values and
    the posterior certificate by as much.  Contacts with X keep the
    default precision."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _upcast_correction(P, u, w):
    P, u, w = jnp.asarray(P), jnp.asarray(u), jnp.asarray(w)
    dt = result_dtype(P.dtype, u.dtype, w.dtype)
    return P.astype(dt), u.astype(dt), w.astype(dt)


# --------------------------------------------------------------------------
# Backend registry.  A backend is one function: the rank-1-corrected
# matmul primitive ``op(A) @ B - u w^T``.
# --------------------------------------------------------------------------

def canonical_dtype(src_dtype) -> jnp.dtype:
    """Working dtype for a host block source: the raw (possibly 64-bit
    numpy/memmap) dtype canonicalized ONCE under the current x64 mode,
    so it never reaches a jnp accumulator directly and the per-call
    truncation UserWarning never fires.  The single home of this rule —
    the blocked/sharded operators and the sharded contact points below
    must agree on it."""
    return jnp.dtype(jax.dtypes.canonicalize_dtype(jnp.dtype(src_dtype)))


def result_dtype(*dtypes) -> jnp.dtype:
    """Standard-lattice promotion of ``dtypes``, valid under strict mode.

    ``jnp.result_type``/``jnp.promote_types`` themselves *raise* under
    ``jax_numpy_dtype_promotion='strict'`` for mixed inputs, so every
    accumulator-dtype decision routes through this helper: the promotion
    is computed on the standard lattice and the operands are then cast
    *explicitly* at the contact point, which is exactly what strict mode
    exists to force.  The single home of this rule (lint DT005)."""
    with jax.numpy_dtype_promotion("standard"):
        return jnp.dtype(jnp.result_type(*dtypes))


# (A, B, u, w, transpose_a) -> op(A) @ B - u w^T
MatmulRank1 = Callable[..., jax.Array]

# (data, indices, indptr, B, u, w, shape) -> A @ B - u w^T, A in CSR form.
# The sparse twin of the dense primitive (DESIGN.md §13): no transpose
# flag — the transposed contact passes the transposed CSR arrays.
SparseMatmulRank1 = Callable[..., jax.Array]

_REGISTRY: dict[str, MatmulRank1] = {}
_SPARSE_REGISTRY: dict[str, SparseMatmulRank1] = {}
_ENGINES: dict[str, "ContactEngine"] = {}


def register_backend(name: str, matmul_rank1: MatmulRank1,
                     *, overwrite: bool = False) -> None:
    """Register a rank-1-corrected matmul implementation under ``name``."""
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {name!r} already registered")
    _REGISTRY[name] = matmul_rank1
    _ENGINES.pop(name, None)


def register_sparse_backend(name: str, csr_matmul_rank1: SparseMatmulRank1,
                            *, overwrite: bool = False) -> None:
    """Register the CSR rank-1-corrected SpMM primitive under ``name``.

    A backend without a sparse entry refuses CSR operators at the
    sparse contact points (``KeyError``) rather than running some other
    backend's primitive in its place.
    """
    if name in _SPARSE_REGISTRY and not overwrite:
        raise ValueError(f"sparse backend {name!r} already registered")
    _SPARSE_REGISTRY[name] = csr_matmul_rank1
    _ENGINES.pop(name, None)


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def available_sparse_backends() -> tuple[str, ...]:
    return tuple(sorted(_SPARSE_REGISTRY))


def default_backend() -> str:
    """Hardware-resolved default: the fused Pallas kernel on TPU, XLA
    elsewhere (this CPU container, GPUs)."""
    return "pallas_tpu" if jax.default_backend() == "tpu" else "xla"


def resolve_backend(backend: str | None = None,
                    interpret: bool | None = None) -> str:
    """Map the legacy ``interpret`` tri-state and an explicit backend
    name onto a registry key.

    ``interpret=True`` forces the Pallas kernel body to run in Python
    (kernel validation on CPU); ``interpret=False`` forces the XLA
    composition; ``None`` defers to ``backend`` or the hardware default.
    Passing both is a conflict and raises; an explicit ``backend`` must
    name a registered key (typos surface here, not as a silent
    fallback).
    """
    if interpret is not None and backend is not None:
        raise ValueError(
            f"pass either backend ({backend!r}) or the legacy interpret "
            f"flag ({interpret!r}), not both")
    if interpret is not None:
        return "interpret" if interpret else "xla"
    if backend is not None:
        if backend not in _REGISTRY:
            raise KeyError(
                f"unknown contact backend {backend!r}; "
                f"registered: {available_backends()}")
        return backend
    return default_backend()


def backend_uses_pallas(name: str) -> bool:
    """Whether a registry key names a Pallas execution path (used by the
    non-matmul fused ops — attention, scan — that share the dispatch)."""
    return name in ("pallas_tpu", "interpret")


def pallas_dispatch(backend: str | None = None,
                    interpret: bool | None = None) -> tuple[bool, bool]:
    """One-stop dispatch decision for the non-matmul fused ops:
    returns ``(use_pallas, interpret)`` for the resolved backend."""
    name = resolve_backend(backend, interpret)
    return backend_uses_pallas(name), name == "interpret"


# --------------------------------------------------------------------------
# Engine
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ContactEngine:
    """All matrix contact points, bound to one backend.

    Operator-level entry points take anything satisfying the ``LinOp``
    protocol; ``mu=None`` uniformly means "unshifted", so algorithm code
    never branches on shifted-ness.  Dense-array entry points
    (``dense_*``) are the thin layer ``kernels.ops`` re-exports.
    """

    backend: str

    @property
    def _matmul_rank1(self) -> MatmulRank1:
        return _REGISTRY[self.backend]

    # -- dense-array contact points ------------------------------------
    def matmul_rank1(self, A, B, u, w, *, transpose_a: bool = False):
        """``op(A) @ B - u w^T`` on this engine's backend."""
        return self._matmul_rank1(A, B, u, w, transpose_a=transpose_a)

    def dense_shifted_matmat(self, X, B, mu):
        """(X - mu 1^T) @ B for a dense on-device X."""
        u, w = shift_vectors_matmat(B, mu)
        return self.matmul_rank1(X, B, u, w)

    def dense_shifted_rmatmat(self, X, B, mu):
        """(X - mu 1^T)^T @ B for a dense on-device X."""
        u, w = shift_vectors_rmatmat(B, mu, X.shape[1], X.dtype)
        return self.matmul_rank1(X, B, u, w, transpose_a=True)

    # -- sparse contact points (DESIGN.md §13) -------------------------
    #    CSR operands route through the sparse backend primitive; the
    #    rank-1 shift correction stays dense K-vectors fused into the
    #    primitive's epilogue, so sparsity is never destroyed.  A
    #    backend without a registered sparse primitive raises KeyError.

    def sparse_matmul_rank1(self, data, indices, indptr, B, u, w, *,
                            shape):
        """``A @ B - u w^T`` for host CSR arrays ``A`` of ``shape``.

        ``u``/``w`` both None means the plain SpMM.  The transposed
        contact is expressed by passing the transposed CSR — there is
        no transpose flag (a CSR transpose is a different CSR, and the
        block sources hold both orientations).
        """
        fn = _SPARSE_REGISTRY.get(self.backend)
        if fn is None:
            raise KeyError(
                f"contact backend {self.backend!r} has no sparse "
                f"primitive; registered: {available_sparse_backends()}")
        return fn(data, indices, indptr, B, u, w, shape=shape)

    def _sparse_block_product(self, csr, B, u, w):
        """Primitive call for one cached CSR block orientation."""
        return self.sparse_matmul_rank1(csr.data, csr.indices, csr.indptr,
                                        B, u, w, shape=csr.shape)

    def sparse_shifted_matmat(self, source, B, mu):
        """(X - mu 1^T) @ B streamed over a CSR column-block source.

        The rank-1 correction decomposes over column blocks —
        ``(X - mu 1^T) B = sum_blk (X_blk B_blk - mu (1^T B_blk))`` —
        so each slab's share is fused into its primitive's epilogue
        with ``u = mu``, ``w = 1^T B_blk``; nothing is corrected after
        the loop.  ``mu=None`` means unshifted, as everywhere.
        """
        m = int(source.shape[0])
        dt = result_dtype(canonical_dtype(source.dtype), B.dtype)
        acc = jnp.zeros((m, B.shape[1]), dt)
        for j0, blk in source.iter_blocks():
            Bs = B[j0:j0 + blk.shape[1]]
            u, w = (None, None) if mu is None else (mu, Bs.sum(axis=0))
            acc = acc + self._sparse_block_product(blk.csr, Bs, u, w)
        return acc

    def sparse_shifted_rmatmat(self, source, B, mu):
        """(X - mu 1^T)^T @ B over a CSR column-block source — each
        block's rows come from its transposed orientation (the free CSC
        slice) with the ``1 (mu^T B)`` correction fused per block; the
        per-range variant of this contact IS ``sharded_shifted_rmatmat``
        (sparse-aware below), which this delegates to."""
        return self.sharded_shifted_rmatmat(source, B, mu)

    def sparse_shifted_gram_matmat(self, source, B, mu):
        """(X - mu 1^T)(X - mu 1^T)^T @ B over a CSR column-block
        source, one pass per slab: both orientations of each block are
        touched while it is resident (csr_t for the ``X^T``-side, csr
        for the ``X``-side), via the single-pass sharded partials."""
        G, s = self.sharded_shifted_gram_matmat(source, B, mu)
        return G if mu is None else rank1_correct(G, mu, s)

    # -- operator-level contact points ---------------------------------
    @obs.spanned("repro.contact", kind="matmat")
    def matmat(self, op, B):
        return op.matmat(B)

    @obs.spanned("repro.contact", kind="rmatmat")
    def rmatmat(self, op, B):
        return op.rmatmat(B)

    @obs.spanned("repro.contact", kind="shifted_matmat")
    def shifted_matmat(self, op, B, mu):
        """(X - mu 1^T) @ B through ``op``; plain ``X @ B`` when mu is None.

        Operators exposing a dense on-device array via ``contact_array``
        (e.g. ``DenseOp``) get the fused backend primitive; everything
        else (sparse, blocked, chained, callable) computes the product
        through the operator and applies the correction — which costs
        O(mK) extra and never materializes the shifted matrix.
        """
        if mu is None:
            return op.matmat(B)
        X = getattr(op, "contact_array", None)
        if X is not None:
            return self.dense_shifted_matmat(X, B, mu)
        source = getattr(op, "source", None)
        if source is not None \
                and getattr(source, "sparse_format", None) == "csr":
            return self.sparse_shifted_matmat(source, B, mu)
        return rank1_correct(op.matmat(B), *shift_vectors_matmat(B, mu))

    @obs.spanned("repro.contact", kind="shifted_rmatmat")
    def shifted_rmatmat(self, op, B, mu):
        """(X - mu 1^T)^T @ B through ``op``; ``X^T @ B`` when mu is None."""
        if mu is None:
            return op.rmatmat(B)
        X = getattr(op, "contact_array", None)
        if X is not None:
            return self.dense_shifted_rmatmat(X, B, mu)
        source = getattr(op, "source", None)
        if source is not None \
                and getattr(source, "sparse_format", None) == "csr":
            return self.sparse_shifted_rmatmat(source, B, mu)
        u, w = shift_vectors_rmatmat(B, mu, op.shape[1], op.dtype)
        return rank1_correct(op.rmatmat(B), u, w)

    @obs.spanned("repro.contact", kind="shifted_gram_matmat")
    def shifted_gram_matmat(self, op, B, mu):
        """(X - mu 1^T)(X - mu 1^T)^T @ B — the power-iteration Gram
        contact, composed from the two existing contact points (so every
        operator type, fused or streamed, gets it for free).  Used by
        the spectral shift schedules (:mod:`repro.core.schedule`), which
        damp this product by ``alpha * B`` *outside* the contact — the
        schedule update never touches X.

        Block-source operators (``BlockedOp``) take the single-pass
        sharded path below instead: each column slab serves both the
        ``X^T B`` and the ``X (...)`` side while it is resident, halving
        disk traffic per power iteration (2 passes -> 1).
        """
        source = getattr(op, "source", None)
        if source is not None and hasattr(source, "iter_blocks"):
            G, s = self.sharded_shifted_gram_matmat(source, B, mu)
            return G if mu is None else rank1_correct(G, mu, s)
        return self.shifted_matmat(op, self.shifted_rmatmat(op, B, mu), mu)

    @obs.spanned("repro.contact", kind="project_residual")
    def project_residual(self, op, Q, B, mu):
        """``(I - Q Q^T)(X - mu 1^T) @ B`` — the adaptive range finder's
        growth contact (DESIGN.md §16): sample the *residual* of the
        accumulated basis Q without ever materializing the deflated
        operator or re-contacting prior blocks.  One ``shifted_matmat``
        through whatever fused/sparse/streamed path the operator takes,
        plus an O(m·K·b) on-device deflation.  ``Q=None`` (or a
        zero-column Q) means no deflation yet — round zero.
        """
        Y = self.shifted_matmat(op, B, mu)
        if Q is None or Q.shape[1] == 0:
            return Y
        Qc = jnp.asarray(Q, Y.dtype)
        return Y - basis_matmul(Qc, basis_matmul(Qc.T, Y))

    # -- sharded (per-column-range) contact points ---------------------
    #    One host's side of a streamed product: the input is a block
    #    source covering that host's column range (range-local j0), the
    #    output is the host's *partial* — the caller sums partials over
    #    hosts (a psum in the distributed path, a plain sum in-process).
    #    Per-block products route through the backend primitive, so the
    #    pallas_tpu / xla / interpret engines need no call-site changes.

    def sharded_matmat(self, source, B_loc):
        """Local partial ``X_loc @ B_loc`` for one column range.

        ``B_loc`` is the (n_loc, K) slice of the right factor this range
        owns.  Global ``X @ B`` = sum of partials over ranges.
        """
        m = int(source.shape[0])
        dt = result_dtype(canonical_dtype(source.dtype), B_loc.dtype)
        acc = jnp.zeros((m, B_loc.shape[1]), dt)
        for j0, blk in source.iter_blocks():
            Bs = B_loc[j0:j0 + blk.shape[1]]
            if getattr(blk, "is_sparse", False):
                acc = acc + self._sparse_block_product(blk.csr, Bs,
                                                       None, None)
            else:
                # explicit casts: strict promotion forbids int @ float
                acc = acc + jnp.asarray(blk, dt) @ Bs.astype(dt)
        return acc

    def sharded_shifted_rmatmat(self, source, B, mu):
        """Local rows ``(X_loc - mu 1^T)^T @ B`` for one column range.

        Unlike the partial-sum contacts this output is *owned* whole by
        the range (rows of the global product); ranges concatenate, they
        do not sum.  ``mu=None`` means unshifted, as everywhere.
        """
        dt = result_dtype(canonical_dtype(source.dtype), B.dtype)
        if mu is not None:
            dt = result_dtype(dt, jnp.asarray(mu).dtype)
        B = B.astype(dt)
        w = None if mu is None else jnp.asarray(mu, dt) @ B
        parts = []
        for _, blk in source.iter_blocks():
            if getattr(blk, "is_sparse", False):
                u = None if mu is None else jnp.ones((blk.shape[1],),
                                                     w.dtype)
                parts.append(self._sparse_block_product(blk.csr_t, B,
                                                        u, w))
                continue
            blk = jnp.asarray(blk, dt)
            if mu is None:
                parts.append(blk.T @ B)
            else:
                u = jnp.ones((blk.shape[1],), w.dtype)
                parts.append(self.matmul_rank1(blk, B, u, w,
                                               transpose_a=True))
        if not parts:
            n_loc = int(source.shape[1])
            return jnp.zeros((n_loc, B.shape[1]), dt)
        return jnp.concatenate(parts, axis=0)

    def sharded_shifted_gram_matmat(self, source, B, mu):
        """One column range's share of the Gram contact, in a single
        pass over its blocks: returns ``(G_loc, s_loc)`` with

            Zt_blk = blk^T B - 1 (mu^T B)        (fused backend primitive)
            G_loc  = sum_blk blk @ Zt_blk        (m, K)
            s_loc  = sum_blk 1^T Zt_blk          (K,)

        so the *global* Gram product is
        ``(Xbar Xbar^T) B = psum(G_loc) - mu psum(s_loc)`` — the K-vector
        ``s_loc`` rides the same collective as ``G_loc``, exactly like
        the resident-shard ``dist_srsvd`` body (DESIGN.md §5, §10).
        Each block is touched once while resident, serving both sides of
        the Gram product.
        """
        m = int(source.shape[0])
        dt = result_dtype(canonical_dtype(source.dtype), B.dtype)
        if mu is not None:
            dt = result_dtype(dt, jnp.asarray(mu).dtype)
        B = B.astype(dt)
        w = None if mu is None else jnp.asarray(mu, dt) @ B
        G = jnp.zeros((m, B.shape[1]), dt)
        s = jnp.zeros((B.shape[1],), dt)
        for _, blk in source.iter_blocks():
            if getattr(blk, "is_sparse", False):
                # both orientations of the slab while it is resident:
                # csr_t (the free CSC slice) for the X^T side, csr (the
                # cached per-block transpose) for the X side — still a
                # single pass over the source.
                u = None if mu is None else jnp.ones((blk.shape[1],),
                                                     w.dtype)
                Zt_blk = self._sparse_block_product(blk.csr_t, B, u, w)
                G = G + self._sparse_block_product(blk.csr, Zt_blk,
                                                   None, None)
            else:
                blk = jnp.asarray(blk, dt)
                if mu is None:
                    Zt_blk = blk.T @ B
                else:
                    u = jnp.ones((blk.shape[1],), w.dtype)
                    Zt_blk = self.matmul_rank1(blk, B, u, w,
                                               transpose_a=True)
                G = G + blk @ Zt_blk.astype(dt)
            s = s + Zt_blk.sum(axis=0).astype(dt)
        return G, s

    def sharded_growth_contact(self, source, B_loc, Qb, mu):
        """One column range's share of an adaptive growth round, in a
        **single pass** over its blocks (DESIGN.md §16): returns

            P_loc = sum_blk blk @ B_slk          (m, b)   partial — psum
            Z_loc = (X_loc - mu 1^T)^T @ Qb      (n_loc, b_prev) — owned

        i.e. the *sample* partial for this round's draw ``B_loc`` (the
        (n_loc, b) slice of omega this range owns; shift correction
        rides the caller's combine, as in ``sharded_matmat``) and the
        previous round's certificate/projection rows, both computed
        from each slab while it is resident — the pipelining that keeps
        a growth round at one disk pass.  ``Qb=None`` (round zero — no
        block to certify yet) returns ``Z_loc=None``.
        """
        if Qb is None:
            return self.sharded_matmat(source, B_loc), None
        m = int(source.shape[0])
        dt = result_dtype(canonical_dtype(source.dtype), B_loc.dtype)
        if mu is not None:
            dt = result_dtype(dt, jnp.asarray(mu).dtype)
        P_acc = jnp.zeros((m, B_loc.shape[1]), dt)
        Qb = Qb.astype(dt)
        w = None if mu is None else jnp.asarray(mu, dt) @ Qb
        Z_parts = []
        for j0, blk in source.iter_blocks():
            Bs = B_loc[j0:j0 + blk.shape[1]]
            if getattr(blk, "is_sparse", False):
                P_acc = P_acc + self._sparse_block_product(blk.csr, Bs,
                                                           None, None)
                u = None if mu is None else jnp.ones((blk.shape[1],),
                                                     w.dtype)
                Z_parts.append(self._sparse_block_product(blk.csr_t, Qb,
                                                          u, w))
                continue
            blk = jnp.asarray(blk, dt)
            P_acc = P_acc + blk @ Bs.astype(dt)
            if mu is None:
                Z_parts.append(blk.T @ Qb)
            else:
                u = jnp.ones((blk.shape[1],), w.dtype)
                Z_parts.append(self.matmul_rank1(blk, Qb, u, w,
                                                 transpose_a=True))
        if not Z_parts:
            Z = jnp.zeros((int(source.shape[1]), Qb.shape[1]), dt)
        else:
            Z = jnp.concatenate(Z_parts, axis=0)
        return P_acc, Z

    # -- row-sharded (per-row-range) contact points --------------------
    #    The m >> n transpose of the contacts above (DESIGN.md §11):
    #    the input is a row-block source covering one host's row range
    #    (range-local i0).  The sharding roles swap — matmat outputs
    #    are rows the range *owns* (hosts concatenate), rmatmat outputs
    #    are partials (hosts sum / psum).

    def row_sharded_shifted_matmat(self, source, B, mu_loc):
        """Owned rows ``(X_loc - mu_loc 1^T) @ B`` for one row range.

        ``B`` is the full (n, K) right factor (replicated in the
        distributed path — n is small in this regime); ``mu_loc`` is
        this range's slice of the shifting vector, or None for the
        unshifted product.  Each per-block product routes through the
        backend primitive with the block's own mu rows as the rank-1
        ``u`` — the fused pallas_tpu / xla / interpret kernels apply
        per block, no call-site changes.
        """
        dt = result_dtype(canonical_dtype(source.dtype), B.dtype)
        if mu_loc is not None:
            dt = result_dtype(dt, jnp.asarray(mu_loc).dtype)
        B = B.astype(dt)
        w = None if mu_loc is None else B.sum(axis=0)
        parts = []
        for i0, blk in source.iter_blocks():
            blk = jnp.asarray(blk, dt)
            if mu_loc is None:
                parts.append(blk @ B)
            else:
                parts.append(self.matmul_rank1(
                    blk, B, mu_loc[i0:i0 + blk.shape[0]], w))
        if not parts:
            return jnp.zeros((int(source.shape[0]), B.shape[1]), dt)
        return jnp.concatenate(parts, axis=0)

    def row_sharded_rmatmat(self, source, B_loc):
        """Local partial ``X_loc^T @ B_loc`` for one row range.

        ``B_loc`` is the (m_loc, K) row slice of the left factor this
        range owns.  Global ``X^T B`` = sum of partials over ranges (a
        psum in the distributed path).  The shift's K-vector
        ``mu_loc^T B_loc`` needs no disk contact, so the caller
        computes it and rides it on the same collective — exactly like
        the resident-shard body (DESIGN.md §5, §11).
        """
        n = int(source.shape[1])
        dt = result_dtype(canonical_dtype(source.dtype), B_loc.dtype)
        acc = jnp.zeros((n, B_loc.shape[1]), dt)
        for i0, blk in source.iter_blocks():
            blk = jnp.asarray(blk, dt)
            acc = acc + blk.T @ B_loc[i0:i0 + blk.shape[0]].astype(dt)
        return acc

    def row_sharded_growth_contact(self, source, B, Qb_loc, mu_loc):
        """One row range's share of an adaptive growth round, single
        pass (the m >> n transpose of ``sharded_growth_contact``):

            Y_loc = (X_loc - mu_loc 1^T) @ B     (m_loc, b)  — owned rows
            Z_loc = X_loc^T @ Qb_loc             (n, b_prev) partial — psum

        with ``B`` the full (n, b) draw (replicated — n is small in
        this regime), ``Qb_loc`` this range's rows of the previous
        round's block, ``mu_loc`` this range's slice of the shift.  The
        shift's K-vector ``mu_loc^T Qb_loc`` needs no disk contact, so
        the caller computes it and rides it on the same collective as
        ``Z_loc``, exactly like ``row_sharded_rmatmat``.  ``Qb_loc=None``
        (round zero) returns ``Z_loc=None``.
        """
        if Qb_loc is None:
            return self.row_sharded_shifted_matmat(source, B, mu_loc), \
                None
        n = int(source.shape[1])
        dt = result_dtype(canonical_dtype(source.dtype), B.dtype,
                          Qb_loc.dtype)
        if mu_loc is not None:
            dt = result_dtype(dt, jnp.asarray(mu_loc).dtype)
        B = B.astype(dt)
        Qb_loc = Qb_loc.astype(dt)
        w = None if mu_loc is None else B.sum(axis=0)
        Y_parts = []
        Z_acc = jnp.zeros((n, Qb_loc.shape[1]), dt)
        for i0, blk in source.iter_blocks():
            blk = jnp.asarray(blk, dt)
            if mu_loc is None:
                Y_parts.append(blk @ B)
            else:
                Y_parts.append(self.matmul_rank1(
                    blk, B, mu_loc[i0:i0 + blk.shape[0]], w))
            Z_acc = Z_acc + blk.T @ Qb_loc[i0:i0 + blk.shape[0]]
        if not Y_parts:
            Y = jnp.zeros((int(source.shape[0]), B.shape[1]), dt)
        else:
            Y = jnp.concatenate(Y_parts, axis=0)
        return Y, Z_acc

    @obs.spanned("repro.contact", kind="col_mean")
    def col_mean(self, op):
        return op.col_mean()

    @obs.spanned("repro.contact", kind="fro_norm2")
    def fro_norm2(self, op):
        return op.fro_norm2()

    def xbar_fro_norm2(self, op, mu):
        """``||X - mu 1^T||_F^2`` without materializing the shift:

            ||Xbar||_F^2 = ||X||_F^2 - 2 (X 1) . mu + n ||mu||^2

        — the existing ``fro_norm2`` probe plus one K=1 ``matmat``
        (both stream- and sparse-safe).  This is the setup probe behind
        ``ResidualStop`` and the posterior error certificate
        (:mod:`repro.core.stopping`), and the ``||Xbar||`` half of
        ``PCA.mse`` — one home for the identity.
        """
        f = self.fro_norm2(op)
        if mu is None:
            return f
        n = op.shape[1]
        row_sum = self.matmat(op, jnp.ones((n, 1), op.dtype))[:, 0]
        f, mu = jnp.asarray(f), jnp.asarray(mu)
        dt = result_dtype(f.dtype, row_sum.dtype, mu.dtype)
        f, row_sum, mu = f.astype(dt), row_sum.astype(dt), mu.astype(dt)
        return f - 2.0 * (row_sum @ mu) + n * (mu @ mu)


def get_engine(backend: str | None = None, *,
               interpret: bool | None = None) -> ContactEngine:
    """Engine for ``backend`` (default: hardware-resolved).  Cached —
    engines are stateless beyond their registry binding."""
    name = resolve_backend(backend, interpret)   # validates the name
    eng = _ENGINES.get(name)
    if eng is None:
        eng = _ENGINES[name] = ContactEngine(name)
    return eng


# --------------------------------------------------------------------------
# Built-in backends
# --------------------------------------------------------------------------


def _xla_matmul_rank1(A, B, u, w, *, transpose_a: bool = False):
    from repro.kernels import ref
    return ref.matmul_rank1_ref(A, B, u, w, transpose_a=transpose_a)


def _pallas_matmul_rank1(A, B, u, w, *, transpose_a: bool = False):
    from repro.kernels.shifted_matmul import matmul_rank1
    return matmul_rank1(A, B, u, w, transpose_a=transpose_a,
                        interpret=False)


def _interpret_matmul_rank1(A, B, u, w, *, transpose_a: bool = False):
    from repro.kernels.shifted_matmul import matmul_rank1
    return matmul_rank1(A, B, u, w, transpose_a=transpose_a,
                        interpret=True)


def _xla_csr_matmul_rank1(data, indices, indptr, B, u, w, *, shape):
    """BCSR SpMM + rank-1 correction — the sparse primitive of the
    ``xla`` and ``pallas_tpu`` backends.  Host half: index arrays are
    cast to int32 host-side so the x64-truncation warning never fires,
    and integer CSR data is cast to the float result type (strict
    promotion forbids the implicit int-data @ float-B inside the BCSR
    dot); :func:`_bcsr_matmul_rank1` is the device half."""
    import numpy as np
    data = np.asarray(data)
    B = jnp.asarray(B)
    out_dtype = result_dtype(canonical_dtype(data.dtype), B.dtype)
    m = int(shape[0])
    B = B.astype(out_dtype)
    if u is not None:
        u, w = jnp.asarray(u, out_dtype), jnp.asarray(w, out_dtype)
    if data.size == 0 or shape[1] == 0:
        P = jnp.zeros((m, B.shape[1]), out_dtype)
        return P if u is None else rank1_correct(P, u, w)
    return _bcsr_matmul_rank1(
        jnp.asarray(data, dtype=out_dtype),
        jnp.asarray(np.asarray(indices, dtype=np.int32)),
        jnp.asarray(np.asarray(indptr, dtype=np.int32)),
        B, u, w, shape=(m, int(shape[1])))


@functools.partial(jax.jit, static_argnames=("shape",))
def _bcsr_matmul_rank1(data, indices, indptr, B, u, w, *, shape):
    """``A @ B - u w^T`` for ``A = BCSR((data, indices, indptr), shape)``
    as one compiled program (``u``/``w`` None: the plain SpMM).  Operands
    arrive already in the result dtype."""
    from jax.experimental import sparse as jsp
    P = (jsp.BCSR((data, indices, indptr), shape=shape) @ B).astype(B.dtype)
    return P if u is None else rank1_correct(P, u, w)


def _interpret_csr_matmul_rank1(data, indices, indptr, B, u, w, *, shape):
    from repro.kernels.sparse_matmul import csr_matmul_rank1
    return csr_matmul_rank1(data, indices, indptr, B, u, w, shape=shape,
                            interpret=True)


register_backend("xla", _xla_matmul_rank1)
register_backend("pallas_tpu", _pallas_matmul_rank1)
register_backend("interpret", _interpret_matmul_rank1)
register_sparse_backend("xla", _xla_csr_matmul_rank1)
# The ELL kernel (kernels/sparse_matmul.py) is refused by the TPU
# compiler (its in-kernel jnp.take gather: "Shape mismatch in input,
# indices and output"), and its whole-B VMEM block cannot hold a
# paper-vocabulary B; on the chip CSR contacts run the BCSR composition.
register_sparse_backend("pallas_tpu", _xla_csr_matmul_rank1)
register_sparse_backend("interpret", _interpret_csr_matmul_rank1)
