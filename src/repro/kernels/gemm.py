"""Pallas TPU kernels: tile GEMM / SYRK / GEADD accumulation updates.

GEMM: ``C - A @ B^T`` — the dominant FLOP sink of the factorization (the
paper's cublasDgemm calls).  SYRK is GEMM with A==B.  GEADD is the
tree-reduction combine.  Tiles up to 256×256 fit VMEM whole; larger tiles
block over the contraction dim with a float32 VMEM accumulator (revisiting
the output block across the k-grid axis, writing on the last step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gemm_pallas", "syrk_pallas", "geadd_pallas"]


def _gemm_kernel(c_ref, a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    """Grid (batch, k_blocks): accumulate -A@B^T over k in VMEM, emit once."""
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = c_ref[0].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)
    b = b_ref[0].astype(jnp.float32)
    acc_ref[...] -= jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _emit():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kblock", "interpret"))
def gemm_pallas(c_mk: jnp.ndarray, a_mn: jnp.ndarray, b_kn: jnp.ndarray,
                kblock: int = 256, *, interpret: bool) -> jnp.ndarray:
    """Tile update C - A @ B^T, batched over leading dims."""
    t = c_mk.shape[-1]
    batch_shape = c_mk.shape[:-2]
    c3 = c_mk.reshape((-1, t, t))
    a3 = jnp.broadcast_to(a_mn, batch_shape + (t, t)).reshape((-1, t, t))
    b3 = jnp.broadcast_to(b_kn, batch_shape + (t, t)).reshape((-1, t, t))
    nb = c3.shape[0]
    kb = min(kblock, t)
    nk = pl.cdiv(t, kb)
    out = pl.pallas_call(
        functools.partial(_gemm_kernel, nk=nk),
        grid=(nb, nk),
        in_specs=[
            pl.BlockSpec((1, t, t), lambda bidx, k: (bidx, 0, 0)),
            pl.BlockSpec((1, t, kb), lambda bidx, k: (bidx, 0, k)),
            pl.BlockSpec((1, t, kb), lambda bidx, k: (bidx, 0, k)),
        ],
        out_specs=pl.BlockSpec((1, t, t), lambda bidx, k: (bidx, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, t, t), c_mk.dtype),
        scratch_shapes=[pltpu.VMEM((t, t), jnp.float32)],
        interpret=interpret,
    )(c3, a3, b3)
    return out.reshape(batch_shape + (t, t))


@functools.partial(jax.jit, static_argnames=("kblock", "interpret"))
def syrk_pallas(c_kk: jnp.ndarray, a_kn: jnp.ndarray,
                kblock: int = 256, *, interpret: bool) -> jnp.ndarray:
    """Symmetric rank-t tile update C - A @ A^T."""
    return gemm_pallas(c_kk, a_kn, a_kn, kblock=kblock, interpret=interpret)


def _geadd_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = a_ref[...] + b_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def geadd_pallas(a: jnp.ndarray, b: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    """Generalized tile addition (tree-reduction combine, paper Alg. 3)."""
    t = a.shape[-1]
    batch_shape = a.shape[:-2]
    a3 = a.reshape((-1, t, t))
    b3 = b.reshape((-1, t, t))
    nb = a3.shape[0]
    out = pl.pallas_call(
        _geadd_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, t, t), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, t, t), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, t, t), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, t, t), a.dtype),
        interpret=interpret,
    )(a3, b3)
    return out.reshape(batch_shape + (t, t))
