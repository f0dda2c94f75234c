"""Pallas TPU kernel: rank-1-corrected matmul  ``C = op(A) @ B - u w^T``.

This is the paper's memory-avoidance trick pushed down to tile granularity
(DESIGN.md §3).  Every contact S-RSVD makes with the data matrix has the
form ``(X - mu 1^T) @ B`` or ``(X - mu 1^T)^T @ B``; algebraically that is
``X @ B - u w^T`` with a cheap precomputed K-vector ``w``.  A naive XLA
lowering writes the (m, K) matmul result to HBM, reads it back, subtracts
the broadcast outer product, and writes again.  Here the f32 accumulator
tile stays in VMEM across the K-contraction and the rank-1 tile is
subtracted in the epilogue before the single HBM write-back.

Tiling: (bm, bn) output tiles, bk contraction steps as the innermost
("arbitrary") grid dimension; all tile dims MXU-aligned multiples of 128
by default.  u enters as an (m, 1) column block, w as a (1, n) row block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, u_ref, w_ref, o_ref, acc_ref, *, nk: int,
            transpose_a: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    if transpose_a:
        a = a.T
    # f32 operands multiply at bf16 precision (Mosaic's default, as with
    # XLA's DEFAULT dots): max relative error 3e-3 against float64 on a
    # TPU v5e; the sum accumulates in f32.
    acc_ref[...] += jnp.dot(a, b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        rank1 = u_ref[...].astype(jnp.float32) * w_ref[...].astype(
            jnp.float32)                       # (bm,1)*(1,bn) outer product
        o_ref[...] = (acc_ref[...] - rank1).astype(o_ref.dtype)


def _pad_to(x, mults):
    pads = [(0, (-s) % t) for s, t in zip(x.shape, mults, strict=True)]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


@functools.partial(
    jax.jit,
    static_argnames=("transpose_a", "bm", "bn", "bk", "interpret"))
def matmul_rank1(A: jax.Array, B: jax.Array, u: jax.Array, w: jax.Array, *,
                 transpose_a: bool = False, bm: int = 256, bn: int = 256,
                 bk: int = 512, interpret: bool = False) -> jax.Array:
    """``op(A) @ B - u w^T`` with the rank-1 term fused into the epilogue.

    A: (m, n) [or (n, m) when transpose_a];  B: (n, K);  u: (m,);  w: (K,).
    Returns (m, K).  Tile sizes clamp to the (padded) problem size and stay
    multiples of the (8, 128) TPU register tile.
    """
    if transpose_a:
        n_, m = A.shape
    else:
        m, n_ = A.shape
    K = B.shape[1]
    from repro.core.contact import result_dtype
    out_dtype = result_dtype(A.dtype, B.dtype)
    # cast mixed operands up front: the kernel's dot must not rely on
    # implicit promotion (strict-mode clean), and the MXU wants matching
    # operand dtypes anyway
    A = A.astype(out_dtype)
    B = B.astype(out_dtype)

    bm = min(bm, _round_up(m, 8))
    bn = min(bn, _round_up(K, 128))
    bk = min(bk, _round_up(n_, 128))

    A_p = _pad_to(A, (bk, bm) if transpose_a else (bm, bk))
    B_p = _pad_to(B, (bk, bn))
    u_p = _pad_to(u.reshape(m, 1), (bm, 1))
    w_p = _pad_to(w.reshape(1, K), (1, bn))
    mp = A_p.shape[1] if transpose_a else A_p.shape[0]
    np_ = A_p.shape[0] if transpose_a else A_p.shape[1]
    Kp = B_p.shape[1]
    nk = np_ // bk

    a_spec = (pl.BlockSpec((bk, bm), lambda i, j, kk: (kk, i))
              if transpose_a else
              pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)))

    grid = (mp // bm, Kp // bn, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, transpose_a=transpose_a),
        grid=grid,
        in_specs=[
            a_spec,
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, Kp), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="matmul_rank1",
    )(A_p, B_p, u_p, w_p)
    return out[:m, :K]


def _round_up(x: int, t: int) -> int:
    return -(-x // t) * t
