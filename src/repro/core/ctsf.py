"""Compressed Tile Storage Format (CTSF) — paper §III-B, Fig. 5.

Two concrete layouts:

* :class:`TileMatrix` — the general CTSF: only nonzero tiles (of the *factor*
  pattern, so fill tiles are pre-allocated by symbolic factorization) are
  stored, stacked into one contiguous ``(n_alloc, t, t)`` buffer.  Host-side
  numpy maps translate (row_tile, col_tile) -> slot.  This is a 1:1 port of
  the paper's format: "each element (i,j) ... is mapped to a corresponding
  tile (k,m), which is allocated only when an element is mapped to it".

* :class:`BandedCTSF` — the regular banded-arrowhead specialization used by
  the TPU-native ``window`` backend (DESIGN.md §4): row-band storage
  ``Dr[m, d] = A_tile[m, m-d]`` plus dense arrow rows ``R[k, i] =
  A_tile[ndt+i, k]`` and corner ``C[i, j]``.  Row-band storage makes every
  left-looking window a contiguous slice.

Both layouts store full (t, t) dense tiles in float32 and read their input
from scipy CSC, matching the paper ("sparse elements are read in CSC").
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from .structure import ArrowheadStructure, TileGrid, tile_pattern_from_coo
from .symbolic import SymbolicFactorization, symbolic_factorize

__all__ = ["TileMatrix", "BandedCTSF"]


def _dense_padded(mat: sp.spmatrix, grid: TileGrid) -> np.ndarray:
    """Materialize the (padded) dense lower-symmetric matrix for tile slicing.

    Only used on host during construction of test/benchmark problems; the
    factorization itself never touches a dense matrix.
    """
    coo = sp.coo_matrix(mat)
    n_pad = grid.padded_n
    out = np.zeros((n_pad, n_pad), dtype=np.float64)
    out[grid.padded_index(coo.row), grid.padded_index(coo.col)] = coo.data
    # pad diagonal with identity so padded tiles stay SPD
    for k in range(grid.structure.n_diag, grid.n_diag_tiles * grid.t):
        out[k, k] = 1.0
    for k in range(grid.n_diag_tiles * grid.t + grid.structure.arrow, n_pad):
        out[k, k] = 1.0
    return out


# ---------------------------------------------------------------------------
# General CTSF
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TileMatrix:
    """General CTSF: stacked nonzero tiles + host-side index map."""

    grid: TileGrid
    symbolic: SymbolicFactorization
    slot: Dict[Tuple[int, int], int]     # (row_tile, col_tile) -> buffer slot
    tiles: jnp.ndarray                   # (n_alloc, t, t) float32

    @classmethod
    def from_sparse(cls, mat: sp.spmatrix, grid: TileGrid,
                    symbolic: Optional[SymbolicFactorization] = None) -> "TileMatrix":
        a_tiles = tile_pattern_from_coo(mat, grid)
        symb = symbolic or symbolic_factorize(a_tiles)
        slots: Dict[Tuple[int, int], int] = {}
        coords = np.argwhere(symb.l_pattern)
        for idx, (i, j) in enumerate(coords):
            slots[(int(i), int(j))] = idx
        dense = _dense_padded(mat, grid)
        t = grid.t
        buf = np.zeros((len(coords), t, t), dtype=np.float32)
        for (i, j), idx in slots.items():
            if a_tiles[i, j]:
                buf[idx] = dense[i * t:(i + 1) * t, j * t:(j + 1) * t]
        return cls(grid, symb, slots, jnp.asarray(buf))

    def to_dense(self, tiles: Optional[jnp.ndarray] = None,
                 lower_only: bool = True) -> np.ndarray:
        t = self.grid.t
        n_pad = self.grid.padded_n
        out = np.zeros((n_pad, n_pad), dtype=np.float32)
        buf = np.asarray(tiles if tiles is not None else self.tiles)
        for (i, j), idx in self.slot.items():
            out[i * t:(i + 1) * t, j * t:(j + 1) * t] = buf[idx]
        if not lower_only:
            out = np.tril(out) + np.tril(out, -1).T
        return out

    @property
    def n_alloc(self) -> int:
        return self.tiles.shape[0]

    def nbytes(self) -> int:
        return int(self.tiles.size * 4)


# ---------------------------------------------------------------------------
# Banded-arrowhead CTSF (regular layout for the `window` backend)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BandedCTSF:
    """Regular banded-arrowhead tile layout.

    Dr: (ndt, bt+1, t, t)  band rows   — Dr[m, d] = A_tile[m, m-d] (d<=min(m,bt))
    R:  (ndt, nat, t, t)   arrow rows  — R[k, i]  = A_tile[ndt+i, k]
    C:  (nat, nat, t, t)   corner      — C[i, j]  = A_tile[ndt+i, ndt+j] (lower)
    """

    grid: TileGrid
    Dr: jnp.ndarray
    R: jnp.ndarray
    C: jnp.ndarray

    @classmethod
    def from_sparse(cls, mat: sp.spmatrix, grid: TileGrid) -> "BandedCTSF":
        """Scatter the COO entries straight into ``Dr``/``R``/``C`` (the
        layout :meth:`from_dense_padded` reads out of the padded dense
        matrix, without materializing it): entries outside the stored tiles
        — upper-triangle tiles, band tiles past ``band_tiles`` — are
        dropped, and padding diagonals are set to 1 so padded tiles stay
        SPD.  Host memory is the CTSF arrays plus O(nnz) indices."""
        t, ndt, nat, bt = grid.t, grid.n_diag_tiles, grid.n_arrow_tiles, grid.band_tiles
        s = grid.structure
        coo = sp.coo_matrix(mat)
        rt, ri = np.divmod(grid.padded_index(coo.row), t)
        ct, ci = np.divmod(grid.padded_index(coo.col), t)
        v = coo.data
        Dr = np.zeros((ndt, bt + 1, t, t), dtype=np.float32)
        R = np.zeros((ndt, nat, t, t), dtype=np.float32)
        C = np.zeros((nat, nat, t, t), dtype=np.float32)
        band = (rt < ndt) & (ct <= rt) & (rt - ct <= bt)
        Dr[rt[band], (rt - ct)[band], ri[band], ci[band]] = v[band]
        arrow = (rt >= ndt) & (ct < ndt)
        R[ct[arrow], rt[arrow] - ndt, ri[arrow], ci[arrow]] = v[arrow]
        corner = (rt >= ndt) & (ct >= ndt) & (ct <= rt)
        C[rt[corner] - ndt, ct[corner] - ndt, ri[corner], ci[corner]] = \
            v[corner]
        pad = np.arange(s.n_diag, ndt * t)
        Dr[pad // t, 0, pad % t, pad % t] = 1.0
        pad = np.arange(s.arrow, nat * t)
        C[pad // t, pad // t, pad % t, pad % t] = 1.0
        return cls(grid, jnp.asarray(Dr), jnp.asarray(R), jnp.asarray(C))

    @classmethod
    def eye(cls, grid: TileGrid) -> "BandedCTSF":
        """Identity matrix in the banded-arrowhead layout: identity diagonal
        tiles, zero band/arrow/corner slack.  This is the neutral element of
        the canonical-grid embedding (``gridpolicy.embed_ctsf``): its
        Cholesky factor, selected inverse and log-determinant contribution
        are all trivial, so padding a problem with identity blocks changes
        nothing about the original entries."""
        t, ndt, nat, bt = grid.t, grid.n_diag_tiles, grid.n_arrow_tiles, grid.band_tiles
        ident = np.eye(t, dtype=np.float32)
        Dr = np.zeros((ndt, bt + 1, t, t), dtype=np.float32)
        if ndt:
            Dr[:, 0] = ident
        C = np.zeros((max(nat, 0), max(nat, 0), t, t), dtype=np.float32)
        for i in range(nat):
            C[i, i] = ident
        R = np.zeros((ndt, max(nat, 0), t, t), dtype=np.float32)
        return cls(grid, jnp.asarray(Dr), jnp.asarray(R), jnp.asarray(C))

    @classmethod
    def from_dense_padded(cls, dense: np.ndarray, grid: TileGrid) -> "BandedCTSF":
        t, ndt, nat, bt = grid.t, grid.n_diag_tiles, grid.n_arrow_tiles, grid.band_tiles
        Dr = np.zeros((ndt, bt + 1, t, t), dtype=np.float32)
        for m in range(ndt):
            for d in range(min(m, bt) + 1):
                j = m - d
                Dr[m, d] = dense[m * t:(m + 1) * t, j * t:(j + 1) * t]
        R = np.zeros((ndt, max(nat, 0), t, t), dtype=np.float32)
        C = np.zeros((max(nat, 0), max(nat, 0), t, t), dtype=np.float32)
        off = ndt * t
        for k in range(ndt):
            for i in range(nat):
                R[k, i] = dense[off + i * t: off + (i + 1) * t, k * t:(k + 1) * t]
        for i in range(nat):
            for j in range(i + 1):
                C[i, j] = dense[off + i * t: off + (i + 1) * t,
                                off + j * t: off + (j + 1) * t]
        return cls(grid, jnp.asarray(Dr), jnp.asarray(R), jnp.asarray(C))

    def to_dense(self, lower_only: bool = True) -> np.ndarray:
        g = self.grid
        t, ndt, nat, bt = g.t, g.n_diag_tiles, g.n_arrow_tiles, g.band_tiles
        n_pad = g.padded_n
        out = np.zeros((n_pad, n_pad), dtype=np.float32)
        Dr, R, C = np.asarray(self.Dr), np.asarray(self.R), np.asarray(self.C)
        for m in range(ndt):
            for d in range(min(m, bt) + 1):
                j = m - d
                out[m * t:(m + 1) * t, j * t:(j + 1) * t] = Dr[m, d]
        off = ndt * t
        for k in range(ndt):
            for i in range(nat):
                out[off + i * t: off + (i + 1) * t, k * t:(k + 1) * t] = R[k, i]
        for i in range(nat):
            for j in range(i + 1):
                out[off + i * t: off + (i + 1) * t, off + j * t: off + (j + 1) * t] = C[i, j]
        if not lower_only:
            out = np.tril(out) + np.tril(out, -1).T
        return out

    def arrays(self) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        return self.Dr, self.R, self.C

    def nbytes(self) -> int:
        return int((self.Dr.size + self.R.size + self.C.size) * 4)
