"""Selected inversion of banded-arrowhead factors — blocked Takahashi recurrence.

INLA (the paper's driving application) follows every factorization with
posterior marginal variances, i.e. selected entries of Σ = A^{-1}.  The
unit-vector panel sweep (``solve.marginal_variances(method="panels")``)
costs one forward solve per selected index and only yields the diagonal;
this module computes *every* Σ entry on the factor's sparsity pattern —
the whole band plus the arrow block — in one backward tile sweep whose cost
is independent of how many entries are selected.

Derivation (blocked Takahashi equations)
----------------------------------------
Let ``A = L L^T`` with block lower-triangular ``L`` and ``Σ = A^{-1}``.
From ``Σ L = L^{-T}`` (upper triangular), taking block entry (i, j) with
``i >= j`` and splitting the sum over ``k >= j``:

    Σ_ij L_jj + Σ_{k>j} Σ_ik L_kj = (L^{-T})_ij

With the *normalized* factor column ``G_kj = L_kj L_jj^{-1}``:

    i > j:   Σ_ij = - Σ_{k>j} Σ_ik G_kj                         (off-diag)
    i = j:   Σ_jj = L_jj^{-T} L_jj^{-1} - Σ_{k>j} Σ_jk G_kj
                  = (L_jj L_jj^T)^{-1} - Σ_{k>j} Σ_kj^T G_kj    (diag)

so column j of Σ needs only Σ entries from trailing columns ``k > j`` — a
*backward* sweep — and, by symmetry ``Σ_jk = Σ_kj^T``, the diagonal needs
only the off-diagonals of column j computed the same step.

For the banded-arrowhead layout, ``L_kj != 0`` only for band rows
``k = j+1 .. j+b`` and arrow rows, so the sum touches Σ tiles with tile
offset ``<= b`` plus arrow/corner tiles: the recurrence *closes* on the
factor's own sparsity pattern and the computed entries are exact entries of
the dense A^{-1}.  The whole backward recurrence is one sweep-level
primitive (``kernels.ops.selinv_sweep``), the mirror image of the
factorization sweep: columns ``j = ndt-1 .. 0`` walk with a
``(b, b+1, t, t)`` ring of the last b computed Σ columns (plus the arrow
ring).  On the Pallas backend the *entire* recurrence is a single fused
kernel launch with the Σ-column ring resident in VMEM across columns
(``kernels/selinv.py``); on the jnp backend it is a ``lax.scan`` of
``kernels.ops.selinv_step`` block-row x block-column contractions.  The
trailing corner seeds the recurrence: the last block columns see no later
columns, hence ``Σ_corner = L_c^{-T} L_c^{-1}`` — one small dense
triangular solve.

Cost: O(ndt · (b + nat)²) tile matmuls — same order as the factorization
itself and independent of the number of selected entries, versus
O(k · ndt · b) for k unit-vector panels.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.ring import band_col_to_row, band_row_to_col
from repro.runtime import telemetry
from .batching import LRUCache, bucketed_batched_call
from .cholesky import CholeskyFactor
from .ctsf import BandedCTSF
from .options import SolverOptions, check_options
from .structure import TileGrid

__all__ = ["SelectedInverse", "selected_inverse", "selinv_batched"]

_HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Result container (mirrors BandedCTSF's layout)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SelectedInverse:
    """Band + arrow block of Σ = A^{-1} in banded-arrowhead tile layout.

    Dr: (ndt, bt+1, t, t)  band rows   — Dr[m, d] = Σ_tile[m, m-d]
    R:  (ndt, nat, t, t)   arrow rows  — R[k, i]  = Σ_tile[ndt+i, k]
    C:  (nat, nat, t, t)   corner      — C[i, j]  = Σ_tile[ndt+i, ndt+j] (lower)

    Leading batch axes (from :func:`selinv_batched`) are carried transparently
    by :meth:`diagonal`; the elementwise accessors assume an unbatched layout
    but broadcast over leading axes as well.
    """

    grid: TileGrid
    Dr: jnp.ndarray
    R: jnp.ndarray
    C: jnp.ndarray

    def diagonal(self, padded: bool = False) -> jnp.ndarray:
        """diag(Σ) — INLA's posterior marginal variances, every latent at
        once.  Returns the unpadded (n,) diagonal unless ``padded``."""
        g = self.grid
        with telemetry.span("selinv.diagonal"):
            d0 = jnp.take(self.Dr, 0, axis=-3)             # (..., ndt, t, t)
            db = jnp.diagonal(d0, axis1=-2, axis2=-1)      # (..., ndt, t)
            db = db.reshape(db.shape[:-2] + (-1,))
            if g.n_arrow_tiles:
                ct = jnp.diagonal(self.C, axis1=-4, axis2=-3)  # (..,t,t,nat)
                dc = jnp.diagonal(ct, axis1=-3, axis2=-2)      # (..., nat, t)
                dc = dc.reshape(dc.shape[:-2] + (-1,))
                full = jnp.concatenate([db, dc], axis=-1)
            else:
                full = db
            if padded:
                return full
            idx = g.padded_index(np.arange(g.structure.n))
            return jnp.take(full, jnp.asarray(idx), axis=-1)

    def covariance(self, i: int, j: int) -> jnp.ndarray:
        """Σ_ij for element indices of the *original* matrix.  Defined
        whenever the entry lies on the stored pattern: |i-j| within the tile
        band, or at least one index in the arrow block."""
        g = self.grid
        s = g.structure
        for v in (i, j):
            if not 0 <= int(v) < s.n:
                raise ValueError(f"index {v} out of range [0, {s.n})")
        pi, pj = g.padded_index(int(i)), g.padded_index(int(j))
        if pi < pj:
            pi, pj = pj, pi                              # Σ is symmetric
        bi, ri = divmod(pi, g.t)
        bj, rj = divmod(pj, g.t)
        ndt = g.n_diag_tiles
        if bi < ndt:                                     # band x band
            d = bi - bj
            if d > g.band_tiles:
                raise ValueError(
                    f"covariance({i}, {j}) lies outside the stored band "
                    f"(tile offset {d} > {g.band_tiles})")
            return self.Dr[..., bi, d, ri, rj]
        if bj < ndt:                                     # arrow row x band col
            return self.R[..., bj, bi - ndt, ri, rj]
        ia, ja = bi - ndt, bj - ndt                      # corner (lower stored)
        return self.C[..., ia, ja, ri, rj]

    def to_dense_band(self, lower_only: bool = False) -> np.ndarray:
        """Materialize the stored band + arrow entries as a dense
        (padded_n, padded_n) array (zeros off-pattern); symmetrized unless
        ``lower_only``."""
        return BandedCTSF(self.grid, self.Dr, self.R,
                          self.C).to_dense(lower_only=lower_only)

    def arrays(self) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        return self.Dr, self.R, self.C

    def nbytes(self) -> int:
        return int((self.Dr.size + self.R.size + self.C.size) * 4)


# ---------------------------------------------------------------------------
# The backward tile recurrence
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("grid", "impl"))
def _selinv_impl(Dr, R, C, grid, impl=None, start_tile=0):
    """Blocked Takahashi sweep over one factor.  Returns (Sd, Sr, Sc) in the
    row-band / arrow-row / lower-corner layout of :class:`SelectedInverse`.

    ``start_tile`` declares the first columns an identity-embedding prefix
    (``core/gridpolicy.py``): the sweep emits identity Σ panels there
    (``Σ = blockdiag(I, Σ_src)``), skipping their compute on the fused
    backend.  Callers omit it on the plain path (static 0) and pass a
    traced scalar on the canonical-grid path."""
    t, ndt, nat, bt = grid.t, grid.n_diag_tiles, grid.n_arrow_tiles, grid.band_tiles
    b1 = bt + 1

    # --- corner seed: Σ_cc = L_c^{-T} L_c^{-1} (dense, small) --------------
    if nat:
        nc = nat * t
        cd = C.transpose(0, 2, 1, 3).reshape(nc, nc)
        winv_c = jax.scipy.linalg.solve_triangular(
            cd, jnp.eye(nc, dtype=C.dtype), lower=True)
        sc_dense = jnp.dot(winv_c.T, winv_c, precision=_HI)
        sc_full = sc_dense.reshape(nat, t, nat, t).transpose(0, 2, 1, 3)
    else:
        sc_full = jnp.zeros((0, 0, t, t), Dr.dtype)

    if ndt == 0:
        sd = jnp.zeros((0, b1, t, t), Dr.dtype)
        sr = jnp.zeros((0, nat, t, t), Dr.dtype)
        return sd, sr, _tril_tiles(sc_full, nat)

    # whole backward recurrence as one sweep primitive: the fused Pallas
    # kernel (impl="pallas") or the per-column selinv_step scan ("ref")
    lcol = band_row_to_col(Dr)       # lcol[j, d] = L_tile[j+d, j]
    panels, sr = ops.selinv_sweep(lcol, R, sc_full, start_tile, impl=impl)
    # panels[j, e] = Σ_{j+e, j}; sr[j, i] = Σ_{ndt+i, j}
    sd = band_col_to_row(panels)     # Sd[m, d] = Σ_{m, m-d}
    return sd, sr, _tril_tiles(sc_full, nat)


def _tril_tiles(sc_full: jnp.ndarray, nat: int) -> jnp.ndarray:
    """Keep the lower tile triangle of the (nat, nat, t, t) corner block
    (the storage convention shared with BandedCTSF)."""
    if not nat:
        return sc_full
    ii = jnp.arange(nat)
    return jnp.where((ii[:, None] >= ii[None, :])[:, :, None, None],
                     sc_full, 0.0)


def selected_inverse(factor: CholeskyFactor,
                     options: Optional[SolverOptions] = None
                     ) -> SelectedInverse:
    """Band + arrow block of Σ = A^{-1} from a banded-arrowhead Cholesky
    factor, via the blocked Takahashi recurrence (one backward tile sweep,
    cost independent of how many entries are selected).

    Canonical-grid embedded factors (``factor.source_grid`` set, or
    ``options.policy`` given) run the recurrence on the canonical grid — one
    compile per canonical rung across all source grids, prefix columns
    skipped via the sweep's traced ``start_tile`` — and the result is
    restricted back to the source grid, so every returned entry is an
    exact entry of the source problem's inverse."""
    from .solve import _resolve_embedding
    opts = check_options(options)
    impl = opts.impl
    with telemetry.span("selinv.selected_inverse") as sp:
        ctsf, src, pad = _resolve_embedding(factor, opts.policy)
        sp.tag(grid=telemetry.rung_tag(ctsf.grid))
        # the plain path keeps its static-zero start_tile trace
        start = () if src is None else (jnp.asarray(pad, jnp.int32),)
        with telemetry.span("selinv.enqueue"):
            sd, sr, sc = _selinv_impl(ctsf.Dr, ctsf.R, ctsf.C, ctsf.grid,
                                      impl, *start)
        out = SelectedInverse(ctsf.grid, sd, sr, sc)
        if src is not None:
            from .gridpolicy import restrict_selinv
            out = restrict_selinv(out, src)
        return out


# ---------------------------------------------------------------------------
# Batched serving path (INLA θ-sweep posterior marginals)
# ---------------------------------------------------------------------------

# bounded traced-callable cache (core/batching.py), mirroring
# cholesky._BATCHED_WINDOW_CACHE
_BATCHED_SELINV_CACHE = LRUCache(maxsize=64, name="batched_selinv")


def _batched_selinv_fn(grid, opts, use_start=False):
    """One vmapped+jitted recurrence per (grid, options compile key) —
    cached on the Python side so repeated same-structure sweeps reuse the
    traced function object (and XLA's compile cache), mirroring
    ``cholesky._batched_window_fn``.  ``use_start=True`` adds the traced
    ``start_tile`` argument of the canonical-grid path (one cache entry per
    canonical rung, shared by every pad depth)."""
    key = (grid, opts.compile_key(), use_start)
    impl = opts.impl

    def batched_selinv(dr, r, c, *start):
        with jax.named_scope("selinv.batched"):
            return _selinv_impl(dr, r, c, grid, impl, *start)

    def build():
        if use_start:
            return jax.jit(jax.vmap(batched_selinv, in_axes=(0, 0, 0, None)))
        return jax.jit(jax.vmap(batched_selinv))

    return _BATCHED_SELINV_CACHE.get_or_create(key, build)


def selinv_batched(factor: CholeskyFactor, bucket: bool = True,
                   options: Optional[SolverOptions] = None
                   ) -> SelectedInverse:
    """Selected inversion of a batch of same-grid factors (leading batch
    axis on the CTSF arrays, as returned by ``factorize_window_batched``) in
    one vmapped dispatch.

    Args:
      factor: batched factor — ``ctsf.Dr`` must be 5-D
        ``(batch, ndt, bt+1, t, t)`` (with matching ``R``/``C``).
      bucket: pad the batch (by repeating the last factor) to the next
        power of two before dispatch and drop the padding results — the
        same pow2 bucketing compile cache as the batched factorization,
        bounding XLA compiles per grid at log2(max batch).  With
        ``bucket=False`` every distinct batch size compiles once.
      options: the solver knobs; ``options.impl`` picks the recurrence's
        kernel backend.

    Returns: a :class:`SelectedInverse` whose arrays carry the leading
    batch axis; ``diagonal()`` / ``covariance(i, j)`` broadcast over it.

    Canonical-grid embedded factors (``factor.source_grid`` set, or
    ``options.policy`` given) run on the canonical grid — the cache keys on
    the canonical grid, so mixed-size traffic compiles one recurrence per
    rung — and the result is restricted back to the source grid.
    """
    from .solve import _resolve_embedding
    opts = check_options(options)
    with telemetry.span("selinv.batched") as sp:
        ctsf, src, pad = _resolve_embedding(factor, opts.policy)
        if ctsf.Dr.ndim != 5:
            raise ValueError(f"selinv_batched needs a leading batch axis, "
                             f"got Dr.ndim={ctsf.Dr.ndim}")
        sp.tag(b=ctsf.Dr.shape[0], grid=telemetry.rung_tag(ctsf.grid))
        if src is not None:
            from .gridpolicy import restrict_selinv
            fn = _batched_selinv_fn(ctsf.grid, opts, use_start=True)
            start = jnp.asarray(pad, jnp.int32)
            call = lambda dr, r, c: fn(dr, r, c, start)
            sd, sr, sc = bucketed_batched_call(
                call, (ctsf.Dr, ctsf.R, ctsf.C), bucket)
            return restrict_selinv(SelectedInverse(ctsf.grid, sd, sr, sc),
                                   src)
        sd, sr, sc = bucketed_batched_call(
            _batched_selinv_fn(ctsf.grid, opts), (ctsf.Dr, ctsf.R, ctsf.C),
            bucket)
        return SelectedInverse(ctsf.grid, sd, sr, sc)
