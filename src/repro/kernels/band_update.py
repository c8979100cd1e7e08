"""Pallas TPU kernel: fused left-looking band-panel update (hot spot).

This is the TPU rethink of the paper's left-looking accumulation insight
("the GEMM operations behave as an accumulator", §II): instead of one task
per (SYRK|GEMM) with an HBM round-trip each, the *entire* update feeding
panel k is computed by one kernel whose accumulator never leaves VMEM:

    u[e] = sum_{j=1..b-e}  w[e, e+j] @ w[0, j]^T      e = 0..b

where ``w`` is the (b+1, b+1, t, t) row-band window (w[e, d] =
L_tile[k+e, k+e-d]).  e == 0 is the diagonal SYRK chain; e > 0 are the GEMM
chains.  Grid = (b+1 target tiles, j-blocks); each target revisits its VMEM
accumulator across j-blocks (grid iterates the last axis fastest), emitting
one HBM write per output tile.

VMEM budget per step: (2·jb + 1)·t²·4B  (A-row block, B-row block, acc)
— e.g. jb=8, t=128: ~1.1 MB, far under the ~16 MB/core of v5e, leaving
room for the pipelined next block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["band_update_pallas"]


def _band_update_kernel(a_ref, b_ref, o_ref, acc_ref, *, b1: int, jb: int, njb: int):
    e = pl.program_id(0)
    jblk = pl.program_id(1)

    @pl.when(jblk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a_ref: (1, jb, t, t) slice of the shifted row e; entries are
    # w[e, e + jblk*jb + jj].  b_ref: (1, jb, t, t) slice w[0, jblk*jb + jj].
    def jstep(jj, acc):
        j = jblk * jb + jj  # global j index (0-based; j==0 masked: term j>=1)
        a = a_ref[0, jj].astype(jnp.float32)
        b = b_ref[0, jj].astype(jnp.float32)
        term = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        valid = (j >= 1) & (e + j <= b1 - 1)
        return acc + jnp.where(valid, term, 0.0)

    acc_ref[...] = jax.lax.fori_loop(0, jb, jstep, acc_ref[...])

    @pl.when(jblk == njb - 1)
    def _emit():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("jblock", "interpret"))
def band_update_pallas(w: jnp.ndarray, jblock: int = 8,
                       *, interpret: bool) -> jnp.ndarray:
    """Fused band-panel update.  w: (b+1, b+1, t, t) -> u: (b+1, t, t).

    Matches ``ref.band_update_ref`` bit-for-bit in float32.
    """
    b1, _, t, _ = w.shape
    b = b1 - 1
    jb = min(jblock, b1)
    njb = pl.cdiv(b1, jb)
    jpad = njb * jb

    # Pre-shift on the host side of the kernel: wsh[e, j] = w[e, e+j]
    # (clamped gather; masked inside the kernel).  The gather is a cheap
    # O(b²t²) copy; the contraction is O(b²t³).
    e_idx = jnp.arange(b1)[:, None]
    j_idx = jnp.arange(jpad)[None, :]
    gather = jnp.clip(e_idx + j_idx, 0, b)
    wsh = jnp.take_along_axis(
        jnp.pad(w, ((0, 0), (0, max(0, jpad - b1)), (0, 0), (0, 0))),
        gather[:, :, None, None], axis=1)
    w0 = jnp.pad(w[0:1], ((0, 0), (0, max(0, jpad - b1)), (0, 0), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_band_update_kernel, b1=b1, jb=jb, njb=njb),
        grid=(b1, njb),
        in_specs=[
            pl.BlockSpec((1, jb, t, t), lambda e, j: (e, j, 0, 0)),
            pl.BlockSpec((1, jb, t, t), lambda e, j: (0, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, t, t), lambda e, j: (e, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b1, t, t), w.dtype),
        scratch_shapes=[pltpu.VMEM((t, t), jnp.float32)],
        interpret=interpret,
    )(wsh, w0)
    return out
