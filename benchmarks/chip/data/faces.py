"""LFW-shaped face images: 250 x 250 grayscale pixel columns.

The mean image is ``synth_faces``'s template (``benchmarks/table1_images
.py``) at the configuration's resolution: a bright oval face on a grey
background with two dark eyes.  The rest of each column comes from
:mod:`planted`.
"""
from __future__ import annotations

import jax.numpy as jnp

from benchmarks.chip.data import Dataset, planted


def template(height: int, width: int) -> jnp.ndarray:
    yy, xx = jnp.meshgrid(jnp.arange(height) / height,
                          jnp.arange(width) / width, indexing="ij")
    face = (128.0
            + 80.0 * jnp.exp(-((xx - .5) ** 2 + (yy - .45) ** 2) / .08)
            - 60.0 * jnp.exp(-((xx - .35) ** 2 + (yy - .35) ** 2) / .003)
            - 60.0 * jnp.exp(-((xx - .65) ** 2 + (yy - .35) ** 2) / .003))
    return face.astype(jnp.float32).ravel()


def make(cfg: dict, key, seed: int, devices) -> Dataset:
    h, w = cfg["image"][:2]
    X = planted.make(cfg, key, template(h, w))
    return Dataset(operand=X, kwargs={}, dense=lambda: X)
