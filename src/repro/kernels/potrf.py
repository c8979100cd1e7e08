"""Pallas TPU kernel: single-tile Cholesky factorization (POTRF).

One (t, t) SPD tile is loaded into VMEM once, factorized in-register, and
written back once.  On the MXU the surrounding SYRK/GEMM traffic dominates
(O(ndt·b²) matmuls vs O(ndt) POTRFs, same as cuSOLVER's role in the paper)
so this kernel optimizes for a single HBM round-trip rather than peak FLOPs.

The tile Cholesky (:func:`factorize_tile`) is chosen from t alone
(:func:`tile_block`).  Where t >= 2·nb and nb divides t (nb = 32, so every
t = 128 tile) it is blocked: the upper form A = UᵀU walks t/nb row slabs
of nb rows, each factored by nb right-looking steps over the (nb, t) slab
only — a quarter of the tile's vregs at t = 128 — after which the slab
holds its rows of U, diagonal block and panel alike, and the trailing
block takes the slab's update SᵀS as one MXU product.  Smaller tiles run
the unblocked right-looking column loop over the whole tile.  Both use
only masked vector ops and static, sublane-aligned slices (no dynamic
scatters), which map onto the VPU's (8, 128) lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ring import tile_dot, unrolled_fori

__all__ = ["potrf_pallas", "factorize_tile", "tile_block"]

# rows of a slab of the blocked tile routines (kernels/trsm.py's inverse too)
_TILE_BLOCK = 32


def tile_block(t: int) -> int:
    """The row block of the diagonal-tile routines at tile size ``t``: nb
    (32) where ``t >= 2·nb`` and nb divides ``t``, so the tile is factored
    and inverted by blocks; else ``t``, the unblocked loops over the whole
    tile."""
    nb = _TILE_BLOCK
    return nb if t >= 2 * nb and t % nb == 0 else t


def factorize_tile(a: jnp.ndarray, return_status: bool = False):
    """In-kernel dense Cholesky of one (t, t) SPD tile, of which only the
    lower triangle is read, blocked by :func:`tile_block` (only masked
    vector ops and static slices — no dynamic scatters — so it lowers
    inside a Pallas kernel body).  Shared by :func:`potrf_pallas` and the
    fused band-Cholesky sweeps in ``kernels/band_cholesky.py``.  Operates
    in and returns float32.

    ``return_status=True`` additionally returns the minimum *raw* pivot
    encountered by the pivot loop — the true (possibly negative) value of
    ``a[j, j]`` after trailing updates, before ``rsqrt`` destroys its sign.
    A breakdown therefore reports *how* indefinite the tile was, which is
    what sizes the jitter ladder in ``core/robustness.py`` (the sweep-level
    status word derives its pivots from the emitted factor instead, so
    both kernel backends agree bit-for-bit — see ``ref.sweep_status``)."""
    nb = tile_block(a.shape[-1])
    if nb == a.shape[-1]:
        return _factorize_unblocked(a, return_status)
    return _factorize_blocked(a, nb, return_status)


def _factorize_unblocked(a, return_status):
    """The right-looking column loop over the whole tile: each of the t
    steps masks, reduces and rank-1-updates all of it."""
    t = a.shape[-1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    rvec = jax.lax.broadcasted_iota(jnp.int32, (t,), 0)

    def step(j, carry):
        a, min_piv = carry
        # pivot = a[j, j]
        pivot = jnp.sum(jnp.where((rows == j) & (cols == j), a, 0.0))
        min_piv = jnp.minimum(min_piv, pivot)
        dinv = jax.lax.rsqrt(pivot)
        # column j, scaled: L[i, j] = a[i, j] / sqrt(pivot), rows >= j
        col = jnp.sum(jnp.where(cols == j, a, 0.0), axis=1) * dinv
        col = jnp.where(rvec >= j, col, 0.0)
        # trailing update: a[i, m] -= col[i] * col[m] for i > j, m > j
        trailing = (rows > j) & (cols > j)
        a = a - jnp.where(trailing, col[:, None] * col[None, :], 0.0)
        # write the finished column j
        a = jnp.where(cols == j, col[:, None], a)
        return a, min_piv

    a, min_piv = jax.lax.fori_loop(0, t, step, (a, jnp.float32(jnp.inf)))
    a = jnp.where(rows >= cols, a, 0.0)
    if return_status:
        return a, min_piv
    return a


def _factorize_blocked(a, nb, return_status):
    """A = UᵀU (U = Lᵀ, so row p of U is column p of L) by t/nb row slabs.
    The lower triangle is mirrored first, so a slab's rows carry their
    columns.  Slab b (rows r0..r0+nb) runs nb right-looking steps on its
    (nb, t) rows alone; pivot p = r0+i finishes row i as
    U[p, :] = S[i, p:] / sqrt(S[i, p]) and takes its rank-1 update off the
    slab's later rows (U[p, p] is rounded as sqrt(S[i, p]) itself).  The
    finished slab is U's rows r0..r0+nb, diagonal block and panel to its
    right, and the rows below take its update SᵀS as one MXU product; rows
    above the trailing block and columns left of it are never read again,
    so the product is subtracted unmasked."""
    t = a.shape[-1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    a = jnp.where(rows >= cols, a, a.T)
    srows = jax.lax.broadcasted_iota(jnp.int32, (nb, t), 0)
    scols = jax.lax.broadcasted_iota(jnp.int32, (nb, t), 1)
    lanes = scols[:1]
    min_piv = jnp.full((1, 1), jnp.inf, jnp.float32)
    slabs = []
    for r0 in range(0, t, nb):
        def step(i, carry, r0=r0):
            s, min_piv = carry
            p = r0 + i
            raw = jnp.sum(jnp.where(srows == i, s, 0.0), axis=0,
                          keepdims=True)                           # S[i, :]
            # its pivot, and its entries over the slab's rows down the
            # sublanes (U[p, r0+i'] unscaled): two lane reductions side by
            # side, both ahead of the rsqrt
            pivot = jnp.sum(jnp.where(lanes == p, raw, 0.0), axis=1,
                            keepdims=True)                         # (1, 1)
            ucol = jnp.sum(jnp.where(scols == r0 + srows, raw, 0.0), axis=1,
                           keepdims=True)
            if return_status:
                min_piv = jnp.minimum(min_piv, pivot)
            dinv = jax.lax.rsqrt(pivot)
            # the diagonal entry as sqrt, correctly rounded: pivot·rsqrt
            # rounds twice, and the diagonal carries most of L's norm
            row = jnp.where(lanes == p, jnp.sqrt(pivot), raw * dinv)
            row = jnp.where(lanes >= p, row, 0.0)
            s = s - jnp.where((srows > i) & (scols > p), (ucol * dinv) * row,
                              0.0)
            return jnp.where(srows == i, row, s), min_piv

        s, min_piv = unrolled_fori(nb, step, (a[r0:r0 + nb], min_piv))
        slabs.append(s)
        if r0 + nb < t:
            a = a - tile_dot(s, s, trans_a=True)
    l = jnp.concatenate(slabs, axis=0).T
    if return_status:
        return l, jnp.min(min_piv)
    return l


def _potrf_kernel(a_ref, o_ref):
    o_ref[0] = factorize_tile(a_ref[0].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def potrf_pallas(a: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    """Cholesky of one (t, t) tile (or a batch (..., t, t) via grid)."""
    batch_shape = a.shape[:-2]
    t = a.shape[-1]
    a3 = a.reshape((-1, t, t))
    nb = a3.shape[0]
    out = pl.pallas_call(
        _potrf_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, t, t), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, t, t), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, t, t), a.dtype),
        interpret=interpret,
    )(a3)
    return out.reshape(batch_shape + (t, t))
