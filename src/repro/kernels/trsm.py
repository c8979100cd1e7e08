"""Pallas TPU kernel: off-diagonal tile triangular solve (TRSM).

Computes ``X = A @ L^{-T}`` for one (t, t) tile against the freshly
factorized diagonal tile L (lower).  Forward substitution over columns with
masked vector ops; the whole tile lives in VMEM for the duration.

Also the in-kernel triangular routines the fused sweeps share:
:func:`substitute_panel` (``L X = B`` for a (t, k) panel, a t-step loop)
and :func:`invert_lower_tile` (L⁻¹ for the Cholesky sweeps' column
finish and the band solves' diagonal tiles), which inverts by blocks where
``potrf.tile_block`` blocks the tile Cholesky: an nb-step substitution
over the t/nb diagonal blocks at once, then the strictly block-lower part
on the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .potrf import tile_block
from .ring import eye_tile, tile_dot, unrolled_fori

__all__ = ["trsm_pallas", "solve_panel_pallas", "substitute_panel",
           "substitute_right", "invert_lower_tile"]


def substitute_panel(l: jnp.ndarray, b: jnp.ndarray,
                     trans: bool = False) -> jnp.ndarray:
    """In-kernel multi-RHS substitution: solve ``L X = B`` (``trans`` ->
    ``L^T X = B``) for one (t, t) lower-triangular tile against a (t, k)
    panel, using only masked 2-D vector ops (no gather/scatter, no 1-D
    vectors, no matrix-vector ``dot``) so it lowers inside a Mosaic kernel
    body.  Shared by :func:`solve_panel_pallas`, the fused band sweeps in
    ``kernels/band_solve.py`` at tiles ``potrf.tile_block`` leaves whole,
    and, with ``B`` the identity, the selinv sweep, which forms
    ``L_jj^{-1}`` with it, and :func:`invert_lower_tile` at those tiles.
    Operates in and returns float32."""
    t, k = l.shape[-1], b.shape[-1]
    lrows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    lcols = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    prows = jax.lax.broadcasted_iota(jnp.int32, (t, k), 0)
    # the coefficients of X's row j must run down the sublanes next to X's
    # rows: column j of L (trans) or of L^T (row j of L)
    lsrc = l if trans else l.T

    def step(s, x):
        j = (t - 1 - s) if trans else s
        keep = (lrows > j) if trans else (lrows < j)
        lj = jnp.sum(jnp.where((lcols == j) & keep, lsrc, 0.0), axis=1,
                     keepdims=True)                                # (t, 1)
        ljj = jnp.sum(jnp.where((lrows == j) & (lcols == j), l, 0.0))
        bj = jnp.sum(jnp.where(prows == j, b, 0.0), axis=0,
                     keepdims=True)                                # B[j, :]
        xrow = (bj - jnp.sum(lj * x, axis=0, keepdims=True)) / ljj
        return jnp.where(prows == j, xrow, x)

    return jax.lax.fori_loop(0, t, step, jnp.zeros((t, k), jnp.float32))


def invert_lower_tile(l: jnp.ndarray) -> jnp.ndarray:
    """In-kernel L⁻¹ of one (t, t) lower-triangular tile, for the
    Cholesky sweeps' column finish and the band solves' diagonal tiles
    (``kernels/band_solve.py``, which apply it with one product).  With nb = ``potrf.tile_block(t)`` < t,
    L = D + N (D its nb-wide diagonal blocks, N the strictly block-lower
    rest) = D(I + M) with M = D⁻¹N, so L⁻¹ = (I + M)⁻¹D⁻¹.  D⁻¹ takes one
    nb-step substitution, every block at once: step s finishes row s of
    each block and takes it off the block's later rows.  M is strictly
    block-lower, so M^(t/nb) = 0 and (I + M)⁻¹ = (I − M)(I + M²)(I + M⁴)…
    exactly, a few MXU products (four at t/nb = 4).  Where t < 2·nb this
    is :func:`substitute_panel` against the identity, as before.  Operates
    in and returns float32."""
    t = l.shape[-1]
    nb = tile_block(t)
    eye = eye_tile(t)
    if nb == t:
        return substitute_panel(l, eye)
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    same = rows // nb == cols // nb
    rdiag = 1.0 / jnp.sum(jnp.where(rows == cols, l, 0.0), axis=1,
                          keepdims=True)
    # D = diag(d)(I + E), E strictly lower in each block: D⁻¹ = (I + E)⁻¹
    # diag(1/d), so the substitution runs from diag(1/d) and divides by
    # nothing
    e = jnp.where(same & (rows > cols), l * rdiag, 0.0)
    sub = rows % nb
    lane = cols[:1] % nb

    def step(s, x):
        # the blocks' finished rows s, side by side (their columns differ)
        xs = jnp.sum(jnp.where(sub == s, x, 0.0), axis=0, keepdims=True)
        ecol = jnp.sum(jnp.where(lane == s, e, 0.0), axis=1, keepdims=True)
        return x - jnp.where(same, ecol * xs, 0.0)

    dinv = unrolled_fori(nb, step, eye * rdiag)
    m = tile_dot(dinv, jnp.where(same, 0.0, l))
    inv, power = eye - m, m
    for _ in range((t // nb - 1).bit_length() - 1):
        power = tile_dot(power, power)
        inv = inv + tile_dot(inv, power)
    return tile_dot(inv, dinv)


def substitute_right(l: jnp.ndarray, a: jnp.ndarray) -> jnp.ndarray:
    """In-kernel right triangular substitution: solve ``X L^T = A`` (i.e.
    ``X = A L^{-T}``, the TRSM of the tile Cholesky) for a ``(..., t, t)``
    batch of tiles A against one (t, t) lower tile L, using only masked
    2-D vector ops.  The kernel of :func:`trsm_pallas`; the fused Cholesky
    sweeps invert their diagonal tile once (:func:`invert_lower_tile`) and
    multiply instead.  Operates in and returns float32."""
    t = l.shape[-1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)

    def step(j, x):
        # X[..., j] = (A[..., j] - X[..., :j] @ L[j, :j]^T) / L[j, j]
        lrow = jnp.sum(jnp.where((rows == j) & (cols < j), l, 0.0), axis=0,
                       keepdims=True)                              # (1, t)
        ljj = jnp.sum(jnp.where((rows == j) & (cols == j), l, 0.0))
        acol = jnp.sum(jnp.where(cols == j, a, 0.0), axis=-1, keepdims=True)
        xcol = (acol - jnp.sum(x * lrow, axis=-1, keepdims=True)) / ljj
        return jnp.where(cols == j, xcol, x)

    return jax.lax.fori_loop(0, t, step, jnp.zeros(a.shape, jnp.float32))


def _trsm_kernel(l_ref, a_ref, o_ref):
    x = substitute_right(l_ref[0].astype(jnp.float32),
                         a_ref[0].astype(jnp.float32))
    o_ref[0] = x.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def trsm_pallas(l_kk: jnp.ndarray, a_mk: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    """Batched tile TRSM: broadcasting L over a batch of A tiles."""
    t = a_mk.shape[-1]
    batch_shape = a_mk.shape[:-2]
    a3 = a_mk.reshape((-1, t, t))
    nb = a3.shape[0]
    l3 = jnp.broadcast_to(l_kk, (nb, t, t)) if l_kk.ndim == 2 else l_kk.reshape((-1, t, t))
    out = pl.pallas_call(
        _trsm_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, t, t), lambda b: (b, 0, 0)),
                  pl.BlockSpec((1, t, t), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, t, t), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, t, t), a_mk.dtype),
        interpret=interpret,
    )(l3, a3)
    return out.reshape(batch_shape + (t, t))


def _solve_panel_kernel(l_ref, b_ref, o_ref, *, trans):
    """Multi-RHS substitution: solve L X = B (or L^T X = B) for one (t, k)
    panel.  Each step updates a whole row of X — a (t,) x (t, k) contraction
    — so the k right-hand sides ride one sweep instead of k."""
    x = substitute_panel(l_ref[0].astype(jnp.float32),
                         b_ref[0].astype(jnp.float32), trans=trans)
    o_ref[0] = x.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("trans", "interpret"))
def solve_panel_pallas(l_kk: jnp.ndarray, b_panel: jnp.ndarray,
                       trans: bool = False,
                       *, interpret: bool) -> jnp.ndarray:
    """Batched multi-RHS panel solve, broadcasting L over leading dims of B."""
    t, k = b_panel.shape[-2], b_panel.shape[-1]
    batch_shape = b_panel.shape[:-2]
    b3 = b_panel.reshape((-1, t, k))
    nb = b3.shape[0]
    l3 = jnp.broadcast_to(l_kk, (nb, t, t)) if l_kk.ndim == 2 \
        else l_kk.reshape((-1, t, t))
    out = pl.pallas_call(
        functools.partial(_solve_panel_kernel, trans=trans),
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, t, t), lambda b: (b, 0, 0)),
                  pl.BlockSpec((1, t, k), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, t, k), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, t, k), b_panel.dtype),
        interpret=interpret,
    )(l3, b3)
    return out.reshape(batch_shape + (t, k))
