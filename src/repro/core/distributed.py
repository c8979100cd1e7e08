"""Distributed single-matrix factorization — the paper's future work, built.

Paper App. A: "the current approach could be extended to allow a single
Cholesky factorization to be distributed and computed across multiple nodes
using nested dissection ordering".  The adaptive-ND ordering (§III-A) makes
the diagonal partitions independent given the separator/arrow block, so:

  1. each device group factorizes its partition's band + arrow rows locally
     (`shard_map` over the chosen mesh axis — the sequential panel sweeps of
     all partitions run concurrently);
  2. each group computes its partial corner Schur complement
     Σ_{n∈partition} R_n R_nᵀ;
  3. partials are combined across the axis with the **GEADD binary tree**
     (`tree_allreduce`, Alg. 3 on ICI links);
  4. the (small) corner is factorized redundantly on every device —
     replicated compute beats a broadcast for ≤2 tiles.

Correctness requires true partition independence (no band coupling across
partition boundaries) — guaranteed by adaptive-ND ordering, and natively by
the paper's block-diagonal cases (Table II ids 1, 7, 10, 13, 16);
:func:`partition_banded` validates this on the host before sharding.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding.collectives import tree_allreduce
from .cholesky import (CholeskyFactor, _band_arrow_sweep_ring,
                       _corner_dense_cholesky)
from .ctsf import BandedCTSF
from .structure import ArrowheadStructure, TileGrid

__all__ = ["partition_banded", "distributed_factorize", "PartitionedCTSF"]


@dataclasses.dataclass
class PartitionedCTSF:
    """A BandedCTSF split into p independent diagonal partitions.

    Dr: (p, ndt_p, bt+1, t, t);  R: (p, ndt_p, nat, t, t);  C: (nat, nat, t, t)
    """
    grid: TileGrid          # per-partition grid (ndt_p diag tiles)
    n_parts: int
    Dr: jnp.ndarray
    R: jnp.ndarray
    C: jnp.ndarray


def partition_banded(m: BandedCTSF, n_parts: int, atol: float = 0.0) -> PartitionedCTSF:
    """Split a block-independent BandedCTSF into ``n_parts`` partitions.

    Validates on host that no band tile couples two partitions (the
    adaptive-ND invariant); raises if the split would be incorrect.
    """
    g = m.grid
    ndt, bt = g.n_diag_tiles, g.band_tiles
    if ndt % n_parts:
        raise ValueError(f"n_diag_tiles={ndt} not divisible by {n_parts}")
    per = ndt // n_parts
    Dr = np.asarray(m.Dr)
    for p in range(1, n_parts):
        start = p * per
        # rows [start, start+bt) may reach columns < start via d > row-start
        for r in range(start, min(start + bt, ndt)):
            for d in range(r - start + 1, bt + 1):
                if np.abs(Dr[r, d]).max() > atol:
                    raise ValueError(
                        f"band tile ({r},{r - d}) crosses partition boundary "
                        f"{start}; reorder with adaptive ND first")
    sub_struct = ArrowheadStructure(
        n=per * g.t + g.structure.arrow, bandwidth=g.structure.bandwidth,
        arrow=g.structure.arrow)
    sub_grid = TileGrid(sub_struct, g.t)
    return PartitionedCTSF(
        sub_grid, n_parts,
        m.Dr.reshape((n_parts, per) + m.Dr.shape[1:]),
        m.R.reshape((n_parts, per) + m.R.shape[1:]),
        m.C)


def distributed_factorize(pm: PartitionedCTSF, mesh: Mesh, axis: str = "model",
                          impl: Optional[str] = None,
                          tree_chunks: int = 8) -> PartitionedCTSF:
    """Factorize one matrix across ``mesh[axis]`` devices (see module doc)."""
    grid = pm.grid
    nat = grid.n_arrow_tiles
    axis_size = mesh.shape[axis]
    if pm.n_parts % axis_size:
        raise ValueError(f"n_parts={pm.n_parts} not divisible by mesh axis "
                         f"{axis}={axis_size}")

    def local(dr, r, c):
        # dr: (parts_per_dev, ndt_p, bt+1, t, t) — sweep each local partition;
        # the sweep emits its own corner-Schur chunks (accumulated in-kernel
        # on the Pallas backend), so no re-contraction of r_l from HBM here
        sweep = jax.vmap(lambda d, rr: _band_arrow_sweep_ring(
            d, rr, grid, impl, tree_chunks))
        dr_l, r_l, sch = sweep(dr, r)
        if nat:
            partial = sch.sum(axis=(0, 1))             # parts x chunks
            schur = tree_allreduce(partial, axis)      # GEADD tree on ICI
            c_l = _corner_dense_cholesky(c - schur)
        else:
            c_l = c
        return dr_l, r_l, c_l

    spec_part = P(axis)
    spec_rep = P()
    # check_vma=False: the ppermute GEADD tree yields replicated values, but
    # that can't be statically inferred (only psum can); we assert it in tests.
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(spec_part, spec_part, spec_rep),
                       out_specs=(spec_part, spec_part, spec_rep),
                       check_vma=False)
    dr, r, c = jax.jit(fn)(pm.Dr, pm.R, pm.C)
    return PartitionedCTSF(grid, pm.n_parts, dr, r, c)


def assemble_factor(pm: PartitionedCTSF, full_grid: TileGrid) -> CholeskyFactor:
    """Reassemble a partitioned factor into one BandedCTSF (host-side)."""
    p, per = pm.Dr.shape[0], pm.Dr.shape[1]
    dr = pm.Dr.reshape((p * per,) + pm.Dr.shape[2:])
    r = pm.R.reshape((p * per,) + pm.R.shape[2:])
    return CholeskyFactor(BandedCTSF(full_grid, dr, r, pm.C))
