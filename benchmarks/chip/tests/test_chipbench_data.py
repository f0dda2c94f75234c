"""The benchmark's generators, pinned by seeded checksums so that the
yardstick does not move when the program's own generators do."""
import jax
import numpy as np

from benchmarks.chip.data import faces, planted

SMALL = dict(m=96, n=70, rank=8, decay=0.75, pixel_std=30.0, noise=5.0,
             tile_cols=16)


def test_planted_faces_checksum():
    X = np.asarray(planted.make(SMALL, jax.random.PRNGKey(7),
                                faces.template(12, 8)), np.float64)
    assert X.shape == (96, 70)
    assert X.sum() == 982478.0
    assert (X[3, 5], X[90, 69]) == (102.0, 162.0)
    assert X.min() == 0.0 and X.max() == 255.0
    assert np.all(X == np.round(X))          # 8-bit pixel values
