"""Pallas TPU kernel: single-tile Cholesky factorization (POTRF).

One (t, t) SPD tile is loaded into VMEM once, factorized in-register with a
masked right-looking column loop, and written back once.  On the MXU the
surrounding SYRK/GEMM traffic dominates (O(ndt·b²) matmuls vs O(ndt) POTRFs,
same as cuSOLVER's role in the paper) so this kernel optimizes for a single
HBM round-trip rather than peak FLOPs.

The column loop uses only masked vector ops (no dynamic scatters), which maps
cleanly onto the VPU's (8, 128) lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["potrf_pallas", "factorize_tile"]


def factorize_tile(a: jnp.ndarray, return_status: bool = False):
    """In-kernel dense Cholesky of one (t, t) SPD tile via a masked
    right-looking column loop (only masked vector ops — no dynamic
    scatters — so it lowers inside a Pallas kernel body).  Shared by
    :func:`potrf_pallas` and the fused band-Cholesky sweep in
    ``kernels/band_cholesky.py``.  Operates in and returns float32.

    ``return_status=True`` additionally returns the minimum *raw* pivot
    encountered by the column loop — the true (possibly negative) value of
    ``a[j, j]`` after trailing updates, before ``rsqrt`` destroys its sign.
    A breakdown therefore reports *how* indefinite the tile was, which is
    what sizes the jitter ladder in ``core/robustness.py`` (the sweep-level
    status word derives its pivots from the emitted factor instead, so
    both kernel backends agree bit-for-bit — see ``ref.sweep_status``)."""
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
