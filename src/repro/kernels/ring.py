"""Shared VMEM-ring machinery for the sequential-sweep Pallas kernels.

Every fused sweep in this repo — the band-solve forward/backward sweeps
(``band_solve.py``), the whole-factorization band-Cholesky sweep
(``band_cholesky.py``) and the fused selinv Takahashi sweep
(``selinv.py``) — follows the same discipline: a sequential ``(ndt,)``
grid walks tile rows/columns in dependence order while a *ring* of the
last ``band_tiles`` finalized panels stays resident in VMEM scratch, so
the bounded-history recurrence

    out[row] = f(inputs[row], out[row - 1], ..., out[row - depth])

never round-trips recent panels through HBM.  This module is the single
home of that ring index math (plus the row-band <-> column-band layout
converters every sweep wrapper needs), so the kernels share one
implementation instead of copy-pasting modular arithmetic.

In-kernel helpers (operate on VMEM scratch refs):
  :func:`ring_read` / :func:`ring_write` — modular slot addressing.
  :func:`ring_accumulate` — the j = 1..depth accumulation loop over ring
  entries that forms each sweep's bounded-history contraction.
  :func:`tile_dot` — the one 2-D float32 MXU contraction every sweep uses.
  :func:`unrolled_fori` — a ``fori_loop`` taking a few steps a trip.

Compile-time helpers: :func:`vmem_ask` — each sweep's VMEM need, computed
from its own scratch, block and live-value sizes — and
:func:`sweep_compiler_params`, that need capped at ``VMEM_CAP_BYTES``.

Host-side helpers (plain jnp, used by the kernel wrappers and the ref
oracles):
  :func:`band_row_to_col` / :func:`band_col_to_row` — the shifted-gather
  between row-band storage (``Dr[m, d] = T[m, m-d]``, what ``BandedCTSF``
  stores) and column-band panels (``P[k, e] = T[k+e, k]``, what the
  column-walking sweeps consume/emit).
  :func:`chunk_layout` — the (chunk size, chunk count) split used by the
  band-Cholesky sweep's on-the-fly corner-Schur partial sums.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["ring_read", "ring_write", "ring_accumulate",
           "band_row_to_col", "band_col_to_row", "chunk_layout",
           "eye_tile", "identity_prefix_panel", "tile_dot",
           "unrolled_fori", "vmem_ask", "sweep_compiler_params"]

# v5e has 128 MiB of VMEM per core; leave the rest to Mosaic's own scratch.
VMEM_CAP_BYTES = 100 * 2 ** 20
# the default scoped VMEM limit on v5e: never ask for less
_VMEM_FLOOR_BYTES = 16 * 2 ** 20
_VMEM_HEADROOM_BYTES = 4 * 2 ** 20


def tile_dot(a, b, trans_a: bool = False, trans_b: bool = False):
    """``a @ b`` (``a.T`` / ``b.T`` with ``trans_a`` / ``trans_b``) of two
    2-D tiles in float32 at full precision — ``ref.py`` contracts at
    ``Precision.HIGHEST``, and Mosaic's default for float32 operands is not
    guaranteed to match it.
    Batched or multi-axis contractions are written as loops over this one
    form, which is what Mosaic lowers to the MXU."""
    if trans_a:
        a = a.T
    dims = (((1,), (1 if trans_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def unrolled_fori(n: int, step, init, unroll: int = 4):
    """``fori_loop(0, n, step, init)`` with ``unroll`` steps a trip
    (``unroll`` divides ``n``), so the scheduler can overlap a step's
    independent work with its neighbours': Mosaic lowers a ``fori_loop``
    unrolled fully or not at all."""
    def trip(j, carry):
        for u in range(unroll):
            carry = step(j * unroll + u, carry)
        return carry

    return jax.lax.fori_loop(0, n // unroll, trip, init)


def vmem_ask(*, scratch: int, blocks: int, temps: int) -> int:
    """The VMEM one sequential sweep needs, uncapped: the kernel's scratch,
    its double-buffered in/out blocks and its live values (all in bytes)
    plus headroom, floored at the default scoped limit."""
    return max(scratch + 2 * blocks + temps + _VMEM_HEADROOM_BYTES,
               _VMEM_FLOOR_BYTES)


def sweep_compiler_params(*, scratch: int, blocks: int, temps: int,
                          semantics=("arbitrary",)):
    """Mosaic compiler params for one sequential sweep: the VMEM limit is
    its :func:`vmem_ask`, capped below the chip's VMEM."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=tuple(semantics),
        vmem_limit_bytes=int(min(vmem_ask(scratch=scratch, blocks=blocks,
                                          temps=temps), VMEM_CAP_BYTES)))


def eye_tile(t: int, dtype=jnp.float32) -> jnp.ndarray:
    """A (t, t) identity tile built from 2-D iotas — safe inside Pallas
    TPU kernels (where 1-D iota does not lower) and identical to
    ``jnp.eye`` everywhere else."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    return jnp.where(rows == cols, 1.0, 0.0).astype(dtype)


def identity_prefix_panel(bt: int, t: int, dtype=jnp.float32) -> jnp.ndarray:
    """The (bt+1, t, t) column panel an identity-embedding prefix column
    contributes to every sweep (``core/gridpolicy.py``): the identity at
    offset 0, zeros below.  Single definition shared by the fused kernels'
    ``start_tile`` skip branches and the ref oracles' masked scans, so the
    prefix contract cannot drift between backends."""
    eye = eye_tile(t, dtype)
    if not bt:
        return eye[None]
    return jnp.concatenate([eye[None], jnp.zeros((bt, t, t), dtype)], axis=0)


# ---------------------------------------------------------------------------
# In-kernel ring-scratch helpers
# ---------------------------------------------------------------------------

def ring_read(ring_ref, row, depth: int, *idx):
    """Read the panel for absolute row index ``row`` from a depth-``depth``
    VMEM ring (``idx`` selects one tile of it).  Valid for ``row >= -depth``
    (the modular shift keeps the slot index nonnegative); slots for rows the
    sweep has not visited hold the zero panels written by the ``step == 0``
    initialization."""
    return ring_ref[(jax.lax.rem(row + depth, depth),) + idx]


def ring_write(ring_ref, row, depth: int, panel, *idx):
    """Store ``panel`` as absolute row ``row`` in the ring (``idx`` selects
    one tile of it), overwriting the entry ``depth`` rows back (which no
    later step can need)."""
    ring_ref[(jax.lax.rem(row + depth, depth),) + idx] = panel


def ring_accumulate(ring_ref, row, depth: int, init, term, step: int = -1):
    """The bounded-history accumulation every sweep kernel performs:

        init + sum_{j=1..depth} term(j, ring[row + step*j])

    ``term(j, panel)`` maps the ring entry ``step*j`` rows away (``step=-1``
    for forward sweeps, ``+1`` for backward sweeps) to its contribution —
    typically one MXU ``dot_general`` against a factor tile.  ``depth == 0``
    returns ``init`` unchanged (single-tile band); unvisited rows contribute
    the ring's zero-initialized panels, so callers need no masking beyond
    structural zeros in their inputs."""
    if not depth:
        return init

    def jstep(j, acc):
        return acc + term(j, ring_read(ring_ref, row + step * j, depth))

    return jax.lax.fori_loop(1, depth + 1, jstep, init)


# ---------------------------------------------------------------------------
# Host-side band-layout converters (shared by sweep wrappers and ref oracles)
# ---------------------------------------------------------------------------

def band_row_to_col(Dr: jnp.ndarray) -> jnp.ndarray:
    """Row-band storage -> column-band panels.

    Input ``Dr (ndt, bt+1, t, t)`` with ``Dr[m, d] = T[m, m-d]`` (zero for
    ``d > m``); output ``P (ndt, bt+1, t, t)`` with ``P[k, e] = T[k+e, k]``
    (zero for ``k+e >= ndt``, from the pad slack).  The gather is a cheap
    O(ndt·bt·t²) copy next to the O(ndt·bt·t³) sweeps that consume it."""
    ndt, b1 = Dr.shape[:2]
    bt = b1 - 1
    drp = jnp.pad(Dr, ((0, bt), (0, 0), (0, 0), (0, 0)))
    kk, ee = jnp.meshgrid(jnp.arange(ndt), jnp.arange(b1), indexing="ij")
    return drp[kk + ee, ee]


def band_col_to_row(panels: jnp.ndarray) -> jnp.ndarray:
    """Column-band panels -> row-band storage (inverse of
    :func:`band_row_to_col`): ``Dr[m, d] = P[m-d, d]``, zero where
    ``m - d < 0`` (above the diagonal)."""
    ndt, b1 = panels.shape[:2]
    mm, dd = jnp.meshgrid(jnp.arange(ndt), jnp.arange(b1), indexing="ij")
    return jnp.where(((mm - dd) >= 0)[:, :, None, None],
                     panels[jnp.clip(mm - dd, 0, max(ndt - 1, 0)), dd], 0.0)


def chunk_layout(n: int, nchunks: int) -> Tuple[int, int]:
    """Split ``n`` sweep steps into ``<= nchunks`` contiguous chunks:
    returns ``(chunk_size, actual_chunks)``.  Both the fused kernel's
    per-chunk Schur emission and the ref oracle's chunked einsum use this,
    so their output shapes agree by construction."""
    if n <= 0:
        return 1, 1
    csz = math.ceil(n / max(nchunks, 1))
    return csz, math.ceil(n / csz)
