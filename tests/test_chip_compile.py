"""Compile the chip's main path for a described TPU v5e, at real widths.

Nothing runs: each test lowers and compiles for a v5e:2x2 topology that
the installed TPU compiler describes without a chip attached, so what
the chip's compiler refuses (a kernel it cannot lower, more fast memory
than a core has, a program larger than HBM) fails here.  The widths are
those of ``chip_smoke.py``.  The topology is built in a fixture, never
at import, so that only the worker that runs this file loads the TPU
library.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import api
from repro.core import contact
from repro.core.srsvd import _jit_svd_batched
from repro.core.stopping import FixedIters
from repro.kernels.shifted_matmul import matmul_rank1

N = 32768                    # the smoke's dense width: 4 GiB of f32 X


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("transpose_a", [False, True])
@pytest.mark.parametrize("K", [16, 128])
def test_dense_kernel_compiles_without_copying_x(one_chip, K, transpose_a):
    compiled = jax.jit(
        lambda A, B, u, w: matmul_rank1(A, B, u, w,
                                        transpose_a=transpose_a)).lower(
        _sds((N, N), one_chip), _sds((N, K), one_chip),
        _sds((N,), one_chip), _sds((K,), one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the benchmark's roofline reader finds the kernel by this name
    assert "%matmul_rank1" in text
    # tile-aligned f32 X goes to the kernel as is: no padded or cast copy
    assert compiled.memory_analysis().temp_size_in_bytes < N * N * 4 // 8


@pytest.mark.parametrize("orientation", ["csr", "csr_t"])
def test_sparse_contact_compiles_at_paper_shape(one_chip, orientation):
    """The CSR contact as the pallas_tpu backend runs it (the BCSR
    composition), at the paper's 1000 x 300000 co-occurrence shape."""
    assert contact._SPARSE_REGISTRY["pallas_tpu"] \
        is contact._xla_csr_matmul_rank1
    m, n, nnz, K = 1000, 300_000, 1_700_000, 200
    if orientation == "csr_t":
        m, n = n, m
    compiled = contact._bcsr_matmul_rank1.lower(
        _sds((nnz,), one_chip), _sds((nnz,), one_chip, jnp.int32),
        _sds((m + 1,), one_chip, jnp.int32), _sds((n, K), one_chip),
        _sds((m,), one_chip), _sds((K,), one_chip), shape=(m, n)).compile()
    assert compiled.out_info.shape == (m, K)


def test_batched_server_solve_compiles(one_chip, monkeypatch):
    """The FactorServer's vmapped solve at the smoke's request shape,
    traced as on the chip (pallas_tpu contacts)."""
    monkeypatch.setattr(contact, "default_backend", lambda: "pallas_tpu")
    B, m, n, k = 8, 2048, 1024, 16
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(B)])
    compiled = _jit_svd_batched.lower(
        _sds((B, m, n), one_chip), _sds((B, m), one_chip), k, 2 * k, 2,
        True, None, FixedIters(),
        _sds(keys.shape, one_chip, keys.dtype)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_dist_factorize_compiles_on_2x2_mesh(topo):
    """factorize(X, mesh=...) — dist_col_mean + dist_srsvd — with X
    sharded P("model", "data") over four chips: each device holds a
    quarter of X, and its scratch stays below one more quarter (no
    device gathers X or copies its own shard)."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    key = jax.random.PRNGKey(0)
    compiled = jax.jit(
        lambda X, key: api.factorize(X, 64, K=128, q=2, center=True,
                                     mesh=mesh, key=key)).lower(
        _sds((N, N), NamedSharding(mesh, P("model", "data"))),
        _sds(key.shape, NamedSharding(mesh, P()), key.dtype)).compile()
    mem = compiled.memory_analysis()
    quarter = N * N * 4 // 4
    assert quarter <= mem.argument_size_in_bytes < quarter * 1.01
    assert mem.temp_size_in_bytes < quarter
