"""Compile the main-path Pallas sweeps for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles each kernel for
a chip that is described, not attached, and refuses what the chip would
refuse (VMEM over-subscription, unsupported layouts or contractions) —
which interpret-mode tests cannot see.  Shapes are the real tile and band
widths (t=128, band_tiles 8 and 16 with two arrow tiles, and Table II
ID 19's 118 band tiles with one arrow tile, where the streamed Cholesky
sweep takes over) at a small number of diagonal tiles; the grid length
does not change what Mosaic checks.  At t=128 every Cholesky sweep
finishes its columns with the blocked diagonal-tile routines
(``potrf.tile_block``), and the band solves solve their diagonal tiles
with the blocked inverse.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.band_cholesky import (band_cholesky_partitioned_sweep_pallas,
                                         band_cholesky_stream_sweep_pallas,
                                         band_cholesky_sweep_pallas)
from repro.kernels.band_solve import (band_backward_sweep_pallas,
                                      band_forward_sweep_pallas)
from repro.kernels.potrf import potrf_pallas, tile_block
from repro.kernels.selinv import selinv_sweep_pallas

T, NDT, NAT, K = 128, 12, 2, 8
WIDTHS = [8, 16]
# Table II ID 19 at t=128: band tiles and arrow tiles
WIDE_BT, WIDE_NAT = 118, 1


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


def test_blocked_potrf_tile_compiles(shape):
    """The single-tile POTRF kernel runs the blocked tile Cholesky at
    t=128, the one the sweeps' column finish runs."""
    assert tile_block(T) < T
    _compile(lambda a: potrf_pallas(a, interpret=False), shape(NDT, T, T))


# the band solves' right-hand sides: a θ probe's one, and a panel
SOLVE_KS = [1, K]


@pytest.mark.parametrize("k", SOLVE_KS)
@pytest.mark.parametrize("bt", WIDTHS)
def test_band_forward_sweep_compiles(shape, bt, k):
    _compile(lambda d, r, b, s: band_forward_sweep_pallas(
        d, r, b, s, interpret=False),
        shape(NDT, bt + 1, T, T), shape(NDT, NAT, T, T), shape(NDT, T, k),
        shape(dtype=jnp.int32))


@pytest.mark.parametrize("k", SOLVE_KS)
@pytest.mark.parametrize("bt", WIDTHS)
def test_band_backward_sweep_compiles(shape, bt, k):
    _compile(lambda d, r, y, x, s: band_backward_sweep_pallas(
        d, r, y, x, s, interpret=False),
        shape(NDT, bt + 1, T, T), shape(NDT, NAT, T, T), shape(NDT, T, k),
        shape(NAT, T, k), shape(dtype=jnp.int32))


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


def test_stream_sweep_compiles(shape):
    _compile(lambda a, r, s: band_cholesky_stream_sweep_pallas(
        a, r, nchunks=4, start_tile=s, interpret=False),
        shape(NDT, WIDE_BT + 1, T, T), shape(NDT, WIDE_NAT, T, T),
        shape(dtype=jnp.int32))


def test_vmapped_stream_sweep_compiles(shape):
    sweep = functools.partial(band_cholesky_stream_sweep_pallas, nchunks=4,
                              interpret=False)
    _compile(jax.vmap(sweep), shape(1, NDT, WIDE_BT + 1, T, T),
             shape(1, NDT, WIDE_NAT, T, T))


def test_wide_band_solve_sweeps_compile(shape):
    """Both band solves at ID 19's width, with the one right-hand side a
    θ probe solves for: their VMEM ask covers the lane-padded panels."""
    args = (shape(NDT, WIDE_BT + 1, T, T), shape(NDT, WIDE_NAT, T, T))
    _compile(lambda d, r, b, s: band_forward_sweep_pallas(
        d, r, b, s, interpret=False), *args, shape(NDT, T, 1),
        shape(dtype=jnp.int32))
    _compile(lambda d, r, y, x, s: band_backward_sweep_pallas(
        d, r, y, x, s, interpret=False), *args, shape(NDT, T, 1),
        shape(WIDE_NAT, T, 1), shape(dtype=jnp.int32))


KERNEL_NAMES = {
    "band_cholesky_sweep_pallas": lambda shape, bt: (
        lambda a, r, s: band_cholesky_sweep_pallas.__wrapped__(
            a, r, 4, s, interpret=False),
        (shape(NDT, bt + 1, T, T), shape(NDT, NAT, T, T),
         shape(dtype=jnp.int32))),
    "band_forward_sweep_pallas": lambda shape, bt: (
        lambda d, r, b, s: band_forward_sweep_pallas.__wrapped__(
            d, r, b, s, interpret=False),
        (shape(NDT, bt + 1, T, T), shape(NDT, NAT, T, T), shape(NDT, T, K),
         shape(dtype=jnp.int32))),
    "band_backward_sweep_pallas": lambda shape, bt: (
        lambda d, r, y, x, s: band_backward_sweep_pallas.__wrapped__(
            d, r, y, x, s, interpret=False),
        (shape(NDT, bt + 1, T, T), shape(NDT, NAT, T, T), shape(NDT, T, K),
         shape(NAT, T, K), shape(dtype=jnp.int32))),
    # the streamed sweep at the width that takes it, whatever ``bt``
    "band_cholesky_stream_sweep_pallas": lambda shape, bt: (
        lambda a, r, s: band_cholesky_stream_sweep_pallas.__wrapped__(
            a, r, 4, s, interpret=False),
        (shape(NDT, WIDE_BT + 1, T, T), shape(NDT, WIDE_NAT, T, T),
         shape(dtype=jnp.int32))),
    "selinv_sweep_pallas": lambda shape, bt: (
        lambda l, r, c, s: selinv_sweep_pallas.__wrapped__(
            l, r, c, s, interpret=False),
        (shape(NDT, bt + 1, T, T), shape(NDT, NAT, T, T),
         shape(NAT, NAT, T, T), shape(dtype=jnp.int32))),
}


@pytest.mark.parametrize("name", sorted(KERNEL_NAMES))
def test_kernel_keeps_its_name_in_the_compiled_hlo(shape, name):
    """The benchmark's trace readers find each main-path kernel by its
    HLO instruction name.  The kernel sets that name itself: its
    undecorated body, jitted anonymously and vmapped as the batched entry
    points call it, still compiles to a custom call of that name (without
    ``name=`` it would be named after the enclosing trace,
    ``vmap_jit__lambda___``)."""
    body, args = KERNEL_NAMES[name](shape, WIDTHS[0])
    batched = [jax.ShapeDtypeStruct((2,) + a.shape, a.dtype,
                                    sharding=a.sharding) for a in args[:-1]]
    compiled = _compile(jax.vmap(jax.jit(body),
                                 in_axes=(0,) * len(batched) + (None,)),
                        *batched, args[-1])
    calls = [line.split(" = ", 1)[0].split()[-1].lstrip("%")
             for line in compiled.as_text().splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert calls and all(c.split(".")[0] == name for c in calls), calls


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
