"""Compile the main-path Pallas sweeps for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles each kernel for
a chip that is described, not attached, and refuses what the chip would
refuse (VMEM over-subscription, unsupported layouts or contractions) —
which interpret-mode tests cannot see.  Shapes are the real tile and band
widths (t=128, band_tiles 8 and 16 with two arrow tiles) at a small number
of diagonal tiles; the grid length does not change what Mosaic checks.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.band_cholesky import (band_cholesky_partitioned_sweep_pallas,
                                         band_cholesky_sweep_pallas)
from repro.kernels.band_solve import (band_backward_sweep_pallas,
                                      band_forward_sweep_pallas)
from repro.kernels.selinv import selinv_sweep_pallas

T, NDT, NAT, K = 128, 12, 2, 8
WIDTHS = [8, 16]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("bt", WIDTHS)
def test_band_cholesky_sweep_compiles(shape, bt):
    _compile(lambda a, r, s: band_cholesky_sweep_pallas(
        a, r, nchunks=4, start_tile=s, interpret=False),
        shape(NDT, bt + 1, T, T), shape(NDT, NAT, T, T),
        shape(dtype=jnp.int32))


@pytest.mark.parametrize("bt", WIDTHS)
def test_band_forward_sweep_compiles(shape, bt):
    _compile(lambda d, r, b, s: band_forward_sweep_pallas(
        d, r, b, s, interpret=False),
        shape(NDT, bt + 1, T, T), shape(NDT, NAT, T, T), shape(NDT, T, K),
        shape(dtype=jnp.int32))


@pytest.mark.parametrize("bt", WIDTHS)
def test_band_backward_sweep_compiles(shape, bt):
    _compile(lambda d, r, y, x, s: band_backward_sweep_pallas(
        d, r, y, x, s, interpret=False),
        shape(NDT, bt + 1, T, T), shape(NDT, NAT, T, T), shape(NDT, T, K),
        shape(NAT, T, K), shape(dtype=jnp.int32))


@pytest.mark.parametrize("bt", WIDTHS)
def test_selinv_sweep_compiles(shape, bt):
    _compile(lambda l, r, c, s: selinv_sweep_pallas(
        l, r, c, s, interpret=False),
        shape(NDT, bt + 1, T, T), shape(NDT, NAT, T, T),
        shape(NAT, NAT, T, T), shape(dtype=jnp.int32))


@pytest.mark.parametrize("bt", WIDTHS)
def test_partitioned_sweep_compiles(shape, bt):
    _compile(lambda a, r, s: band_cholesky_partitioned_sweep_pallas(
        a, r, (0, NDT // 2, NDT), start_tile=s, interpret=False),
        shape(NDT, bt + 1, T, T), shape(NDT, NAT, T, T),
        shape(dtype=jnp.int32))


def test_vmapped_band_cholesky_batch_compiles(shape):
    bt = WIDTHS[0]
    sweep = functools.partial(band_cholesky_sweep_pallas, nchunks=4,
                              interpret=False)
    _compile(jax.vmap(sweep), shape(8, NDT, bt + 1, T, T),
             shape(8, NDT, NAT, T, T))


def test_sharded_batch_compiles(topo):
    """A batch of whole factorizations split over a four-chip ``data``
    mesh (``concurrent_factorize(mesh=...)``): Mosaic kernels cannot be
    partitioned by the compiler, so each chip must run its own slice."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.concurrent import _per_device

    mesh = Mesh(np.array(topo.devices), ("data",))
    shard = NamedSharding(mesh, P("data"))
    bt = WIDTHS[0]

    def sweep(a, r, c):
        panels, ro, schur, st = band_cholesky_sweep_pallas(
            a, r, nchunks=4, interpret=False)
        return panels, ro, c - schur.sum(axis=0), st

    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=shard)
            for s in ((8, NDT, bt + 1, T, T), (8, NDT, NAT, T, T),
                      (8, NAT, NAT, T, T))]
    compiled = _per_device(jax.vmap(sweep), mesh, "data", 4).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
