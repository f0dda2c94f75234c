"""Planted image matrices, made on the device from a seed.

An (m, n) matrix of n images with m pixels each, one image per column:

    X[:, j] = clip(round(t + B (d * c_j) + noise * e_j), 0, 255)

with ``t`` the configuration's mean image, ``B`` an (m, rank) Gaussian
basis of per-pixel variation, ``d`` a power-law spectrum
(``d_i ~ (i + 1)^-decay``, scaled so the variation has ``pixel_std``
per pixel), ``c_j`` and ``e_j`` Gaussian, and values rounded and
clipped to the 0..255 range of 8-bit pixels.  This is the form of
``synth_faces`` in ``benchmarks/table1_images.py`` (a template, a
low-rank identity part and noise, clipped to 0..255), at a published
shape and with a decaying spectrum in place of 12 flat directions.

Every value is a pure function of the key and of its column tile:
column coefficients and noise are keyed by the tile's first column.
X is written tile by tile into one buffer, so making it needs X plus
one tile of device memory and never sets the run's memory peak.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def spectrum(rank: int, decay: float, pixel_std: float) -> jax.Array:
    """(rank,) power-law scales whose squares sum to pixel_std**2."""
    d = (jnp.arange(rank, dtype=jnp.float32) + 1.0) ** (-decay)
    return d * (pixel_std / jnp.sqrt(jnp.sum(d * d)))


def basis(key, m: int, rank: int) -> jax.Array:
    return jax.random.normal(jax.random.fold_in(key, 0), (m, rank),
                             jnp.float32)


def tile(key, mean, B, d, noise, col0, *, cols: int):
    """Columns [col0, col0 + cols)."""
    kc, kn = jax.random.fold_in(key, 1), jax.random.fold_in(key, 2)
    C = jax.random.normal(jax.random.fold_in(kc, col0),
                          (B.shape[1], cols), jnp.float32)
    # the noise key also names the row block, of which there is one
    E = jax.random.normal(
        jax.random.fold_in(jax.random.fold_in(kn, 0), col0),
        (B.shape[0], cols), jnp.float32)
    V = mean[:, None] + (B * d) @ C + noise * E
    return jnp.clip(jnp.round(V), 0.0, 255.0)


@functools.partial(jax.jit, static_argnames=("m", "n", "width"))
def matrix(key, mean, d, noise, *, m: int, n: int, width: int):
    """The (m, n) matrix, made ``width`` columns a tile."""
    B = basis(key, m, d.shape[0])
    full, rem = divmod(n, width)

    def body(b, X):
        T = tile(key, mean, B, d, noise, b * width, cols=width)
        return lax.dynamic_update_slice_in_dim(X, T, b * width, axis=1)

    X = lax.fori_loop(0, full, body, jnp.zeros((m, n), jnp.float32))
    if rem:
        T = tile(key, mean, B, d, noise, full * width, cols=rem)
        X = lax.dynamic_update_slice_in_dim(X, T, full * width, axis=1)
    return X


def make(cfg: dict, key, mean: jax.Array) -> jax.Array:
    """X for a planted configuration: ``cfg`` gives m, n, rank, decay,
    pixel_std, noise and tile_cols."""
    d = spectrum(cfg["rank"], cfg["decay"], cfg["pixel_std"])
    return matrix(key, mean, d, jnp.float32(cfg["noise"]), m=cfg["m"],
                  n=cfg["n"], width=cfg["tile_cols"])
