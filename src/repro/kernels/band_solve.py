"""Pallas TPU kernels: fused multi-RHS band-solve sweeps (forward/backward).

The post-factorization triangular sweeps are the serving hot path (every
INLA evaluation runs one forward + one backward sweep per factorization).
Driven tile-at-a-time — one ``kernels.ops.solve_panel`` launch per band tile
through a ``lax.fori_loop`` — they are latency-bound: each step round-trips
its (t, k) panel through HBM before the next step may start (cf. Ruipeng
Li's analysis of GPU sparse triangular solves).  These kernels instead
execute an *entire* band sweep in one launch, the solve-phase analogue of
the fused band-Cholesky sweep (``kernels/band_cholesky.py``):

* grid = (ndt,) — one sequential grid step per band tile row; TPU grid
  iteration order makes the recurrence dependence explicit and legal;
* a ring of the last ``bt`` solved (t, k) panels lives in VMEM scratch
  (``kernels/ring.py`` — the ring discipline shared with the fused
  band-Cholesky and selinv sweeps), so the ``L[m, m-j] @ Y_{m-j}``
  (t, t) @ (t, k) MXU accumulations never touch HBM;
* the diagonal tile's solve is chosen from t alone, as the Cholesky
  sweeps' column finish chooses (``potrf.tile_block``): where the tile is
  blocked (every t = 128 tile) it is the blocked inverse
  :func:`kernels.trsm.invert_lower_tile` (an nb-step VPU loop and a few
  MXU products, none of which reads the panel) and one (t, t) @ (t, k)
  product; smaller tiles keep the t-step
  :func:`kernels.trsm.substitute_panel` of the ``solve_panel`` kernel;
* forward only: the arrow-row contributions ``sum_m R[m, i] @ Y_m`` are
  accumulated into a VMEM scratch as the sweep passes each row and emitted
  once at the end — the arrow RHS correction comes for free.

Each sweep asks Mosaic for the VMEM of its step (``_compiler_params``):
the panel ring and arrow accumulator, the double-buffered factor blocks
and panels, and the live values, the blocked inverse's five (t, t)
temporaries among them.

``start_tile`` (forward) supports the RHS-sparsity path of
``marginal_variances(method="panels")``: it is a *traced* scalar (SMEM
input), steps with ``m < start_tile`` write zero panels, so varying
selections never recompile the sweep and the grid stays static.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ring import (band_row_to_col, ring_accumulate, ring_write,
                   sweep_compiler_params, tile_dot)
from .potrf import tile_block
from .trsm import invert_lower_tile, substitute_panel

__all__ = ["band_forward_sweep_pallas", "band_backward_sweep_pallas"]


def _compiler_params(bt, nat_p, t, k):
    """VMEM budget of one band-solve step: the (t, k) panel ring and arrow
    accumulator (scratch), the factor column/row block, arrow rows and
    (t, k) panels in and out, and the step's live panels and tiles: the
    blocked inverse keeps five (t, t) values live (E, D⁻¹, M, its power
    and the inverse).  A panel takes VMEM for whole 128-lane rows, however
    few its columns."""
    panel = t * -(-k // 128) * 128 * 4
    tiles = 2 + (5 if tile_block(t) < t else 0)
    return sweep_compiler_params(
        scratch=(max(bt, 1) + nat_p) * panel,
        blocks=(bt + 1 + nat_p) * t * t * 4 + (2 + 2 * nat_p) * panel,
        temps=8 * panel + tiles * t * t * 4)


def _solve_diagonal(l, rhs, trans=False):
    """``L X = B`` (``trans``: ``L^T X = B``) for the diagonal tile L and
    the step's (t, k) panel: L⁻¹ by blocks and one MXU product where
    ``potrf.tile_block`` blocks the tile, else the t-step substitution."""
    t = l.shape[-1]
    if tile_block(t) == t:
        return substitute_panel(l, rhs, trans=trans)
    return tile_dot(invert_lower_tile(l), rhs, trans_a=trans)


# ---------------------------------------------------------------------------
# Forward sweep: L Y = B over the band, + on-the-fly arrow accumulation
# ---------------------------------------------------------------------------

def _band_forward_kernel(start_ref, dr_ref, r_ref, b_ref, y_ref, acca_ref,
                         ring_ref, arr_ref, *, ndt: int, bt: int, nat_p: int):
    m = pl.program_id(0)
    start = start_ref[0]
    t = dr_ref.shape[-1]
    k = b_ref.shape[-1]

    @pl.when(m == 0)
    def _init():
        ring_ref[...] = jnp.zeros_like(ring_ref)
        arr_ref[...] = jnp.zeros_like(arr_ref)

    # RHS-sparsity fast start: rows above start_tile are identically zero
    # (matching the fori_loop reference, which never visits them), so the
    # whole step body is skipped — masked steps form a contiguous prefix,
    # hence their ring slots still hold the step-0 zeros and contribute
    # nothing to later rows.
    @pl.when(m < start)
    def _skip():
        y_ref[0] = jnp.zeros_like(y_ref[0])

    @pl.when(m >= start)
    def _work():
        # acc = sum_{j=1..bt} L[m, m-j] @ Y_{m-j}; Dr[m, j] = L[m, m-j] is
        # structurally zero for j > m and ring slots for unvisited rows hold
        # zeros, so no masking is needed beyond the zero-init.
        acc = ring_accumulate(
            ring_ref, m, bt, jnp.zeros((t, k), jnp.float32),
            lambda j, yprev: tile_dot(dr_ref[0, j].astype(jnp.float32),
                                      yprev),
            step=-1)

        rhs = b_ref[0].astype(jnp.float32) - acc
        ym = _solve_diagonal(dr_ref[0, 0].astype(jnp.float32), rhs)
        y_ref[0] = ym.astype(y_ref.dtype)
        if bt:
            ring_write(ring_ref, m, bt, ym)

        # arrow rows ride the sweep: arr[i] += R[m, i] @ Y_m
        for i in range(nat_p):
            arr_ref[i] += tile_dot(r_ref[0, i].astype(jnp.float32), ym)

    @pl.when(m == ndt - 1)
    def _emit():
        acca_ref[...] = arr_ref[...].astype(acca_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def band_forward_sweep_pallas(Dr, R, bd, start_tile=0, *, interpret: bool):
    """Fused forward band sweep.  Dr: (ndt, bt+1, t, t) row-band factor
    tiles, R: (ndt, nat, t, t) arrow rows, bd: (ndt, t, k) RHS panel ->
    (yd (ndt, t, k), acc_a (nat, t, k)) with ``L Y = B`` on the band and
    ``acc_a[i] = sum_m R[m, i] @ Y_m`` (the arrow-RHS correction).

    Matches ``ref.band_forward_sweep_ref`` to fp32 tolerance.
    """
    ndt, b1, t, _ = Dr.shape
    bt = b1 - 1
    nat = R.shape[1]
    k = bd.shape[-1]
    if ndt == 0 or k == 0:
        return (jnp.zeros((ndt, t, k), bd.dtype),
                jnp.zeros((nat, t, k), bd.dtype))
    # zero-width arrow blocks break BlockSpecs: pad to one all-zero arrow
    # tile row (its contribution vanishes) and slice the output back.
    nat_p = max(nat, 1)
    rp = R if nat else jnp.zeros((ndt, 1, t, t), Dr.dtype)
    start = jnp.reshape(jnp.asarray(start_tile, jnp.int32), (1,))
    yd, acca = pl.pallas_call(
        functools.partial(_band_forward_kernel, ndt=ndt, bt=bt, nat_p=nat_p),
        grid=(ndt,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, b1, t, t), lambda m: (m, 0, 0, 0)),
            pl.BlockSpec((1, nat_p, t, t), lambda m: (m, 0, 0, 0)),
            pl.BlockSpec((1, t, k), lambda m: (m, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, t, k), lambda m: (m, 0, 0)),
            pl.BlockSpec((nat_p, t, k), lambda m: (0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((ndt, t, k), bd.dtype),
            jax.ShapeDtypeStruct((nat_p, t, k), bd.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((max(bt, 1), t, k), jnp.float32),
                        pltpu.VMEM((nat_p, t, k), jnp.float32)],
        compiler_params=_compiler_params(bt, nat_p, t, k),
        interpret=interpret,
        name="band_forward_sweep_pallas",
    )(start, Dr, rp, bd)
    return yd, acca[:nat]


# ---------------------------------------------------------------------------
# Backward sweep: L^T X = Y over the band, arrow term folded in per step
# ---------------------------------------------------------------------------

def _band_backward_kernel(start_ref, lcol_ref, r_ref, y_ref, xa_ref, x_ref,
                          ring_ref, *, ndt: int, bt: int, nat_p: int):
    s = pl.program_id(0)
    m = ndt - 1 - s
    start = start_ref[0]
    t = lcol_ref.shape[-1]
    k = y_ref.shape[-1]

    @pl.when(s == 0)
    def _init():
        ring_ref[...] = jnp.zeros_like(ring_ref)

    # Canonical-grid fast finish (the mirror of the forward sweep's fast
    # start): rows below start_tile are the identity-embedding prefix with
    # zero RHS, decoupled from the rest — they solve to zero, and since
    # they form a contiguous suffix of this reverse walk nothing reads
    # them afterwards, so the whole step body is skipped.
    @pl.when(m < start)
    def _skip():
        x_ref[0] = jnp.zeros_like(x_ref[0])

    @pl.when(m >= start)
    def _work():
        # acc = sum_{j=1..bt} L[m+j, m]^T @ X_{m+j}; lcol[m, j] = L[m+j, m]
        # is zero-padded past ndt and unvisited ring slots hold zeros.
        acc = ring_accumulate(
            ring_ref, m, bt, jnp.zeros((t, k), jnp.float32),
            lambda j, xnext: tile_dot(lcol_ref[0, j].astype(jnp.float32),
                                      xnext, trans_a=True),
            step=1)

        # arrow term: sum_i R[m, i]^T @ Xa_i
        acc2 = acc
        for i in range(nat_p):
            acc2 = acc2 + tile_dot(r_ref[0, i].astype(jnp.float32),
                                   xa_ref[i].astype(jnp.float32),
                                   trans_a=True)

        rhs = y_ref[0].astype(jnp.float32) - acc2
        xm = _solve_diagonal(lcol_ref[0, 0].astype(jnp.float32), rhs,
                             trans=True)
        x_ref[0] = xm.astype(x_ref.dtype)
        if bt:
            ring_write(ring_ref, m, bt, xm)


@functools.partial(jax.jit, static_argnames=("interpret",))
def band_backward_sweep_pallas(Dr, R, yd, xa, start_tile=0,
                               *, interpret: bool):
    """Fused backward band sweep.  Dr: (ndt, bt+1, t, t), R: (ndt, nat, t, t),
    yd: (ndt, t, k) forward-solved panel, xa: (nat, t, k) already-solved
    arrow panel -> xd (ndt, t, k) with ``L^T X = Y - R^T Xa`` on the band.

    ``start_tile`` (traced SMEM scalar, like the forward sweep's) skips
    rows ``m < start_tile`` — the identity prefix of a canonical-grid
    embedding — leaving X identically zero there.

    Matches ``ref.band_backward_sweep_ref`` to fp32 tolerance.
    """
    ndt, b1, t, _ = Dr.shape
    bt = b1 - 1
    nat = R.shape[1]
    k = yd.shape[-1]
    if ndt == 0 or k == 0:
        return jnp.zeros((ndt, t, k), yd.dtype)
    # column view of the factor: lcol[m, j] = Dr[m+j, j] = L[m+j, m]
    # (cheap O(ndt·bt·t²) gather; the contraction is O(ndt·bt·t²·k))
    lcol = band_row_to_col(Dr)
    nat_p = max(nat, 1)
    rp = R if nat else jnp.zeros((ndt, 1, t, t), Dr.dtype)
    xap = xa if nat else jnp.zeros((1, t, k), yd.dtype)
    start = jnp.reshape(jnp.asarray(start_tile, jnp.int32), (1,))
    return pl.pallas_call(
        functools.partial(_band_backward_kernel, ndt=ndt, bt=bt,
                          nat_p=nat_p),
        grid=(ndt,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, b1, t, t), lambda s: (ndt - 1 - s, 0, 0, 0)),
            pl.BlockSpec((1, nat_p, t, t), lambda s: (ndt - 1 - s, 0, 0, 0)),
            pl.BlockSpec((1, t, k), lambda s: (ndt - 1 - s, 0, 0)),
            pl.BlockSpec((nat_p, t, k), lambda s: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, t, k), lambda s: (ndt - 1 - s, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((ndt, t, k), yd.dtype),
        scratch_shapes=[pltpu.VMEM((max(bt, 1), t, k), jnp.float32)],
        compiler_params=_compiler_params(bt, nat_p, t, k),
        interpret=interpret,
        name="band_backward_sweep_pallas",
    )(start, lcol, rp, yd, xap)
