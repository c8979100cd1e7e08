"""Pallas TPU kernels: Takahashi selected inversion (tile step + fused sweep).

``selinv_step_pallas`` — one backward-recurrence step of the blocked
Takahashi equations (core/selinv.py) computes a whole column of the
selected inverse as

    u[e] = sum_j  S[e, j] @ G[j]        e = 0..e_n-1

where ``S`` is the block row of already-computed Σ tiles visible from column
j (band window + arrow rows + corner) and ``G`` is the normalized factor
column ``G[k] = L[k, j] L[j, j]^{-1}``.  Like ``band_update``, the entire
accumulation chain feeding one output tile runs inside a single kernel whose
accumulator never leaves VMEM: grid = (e_n target tiles, j-blocks); each
target revisits its VMEM accumulator across j-blocks (the grid iterates the
last axis fastest) and emits one HBM write per output tile.

VMEM budget per step: (2·jb + 1)·t²·4B (S-row block, G block, accumulator)
— e.g. jb=8, t=128: ~1.1 MB, far under the ~16 MB/core of v5e.

``selinv_sweep_pallas`` — the *whole* backward Takahashi recurrence as one
launch (the ROADMAP's selinv-fusion item): driven column-at-a-time the
recurrence round-trips its Σ-column ring through HBM between ``lax.scan``
steps; here grid = (ndt,) walks columns j = ndt-1..0 with the ring of the
last ``bt`` computed Σ columns (plus the arrow ring) resident in VMEM
scratch (``kernels/ring.py``, the machinery shared with the band-solve and
band-Cholesky sweeps), the L_jj^{-1} seed solved in-kernel
(:func:`trsm.substitute_panel` against the identity) and the full corner
Σ_cc broadcast to every step.  VMEM budget per step: the Σ ring
bt·(bt+1)·t², the arrow ring bt·nat·t², the corner nat²·t² and the
(bt+1+nat)·t² blocks plus the normalized-column scratch bt·t²; the
wrapper sets the kernel's VMEM limit from these sizes.

Both match their ``kernels/ref.py`` oracles to fp32 tolerance;
``kernels.ops.selinv_step`` / ``kernels.ops.selinv_sweep`` dispatch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ring import (eye_tile, identity_prefix_panel, ring_read, ring_write,
                   sweep_compiler_params, tile_dot)
from .trsm import substitute_panel

__all__ = ["selinv_step_pallas", "selinv_sweep_pallas"]


def _selinv_step_kernel(s_ref, g_ref, o_ref, acc_ref, *, jb: int, njb: int):
    jblk = pl.program_id(1)

    @pl.when(jblk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # s_ref: (1, jb, t, t) slice of Σ row e; g_ref: (jb, t, t) slice of G.
    # The wrapper zero-pads both inputs up to njb*jb, so padded-j terms
    # vanish on their own — no in-kernel masking needed.
    def jstep(jj, acc):
        s = s_ref[0, jj].astype(jnp.float32)
        g = g_ref[jj].astype(jnp.float32)
        return acc + jax.lax.dot_general(s, g, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    acc_ref[...] = jax.lax.fori_loop(0, jb, jstep, acc_ref[...])

    @pl.when(jblk == njb - 1)
    def _emit():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("jblock", "interpret"))
def selinv_step_pallas(s_row: jnp.ndarray, g_col: jnp.ndarray,
                       jblock: int = 8, *, interpret: bool) -> jnp.ndarray:
    """Fused Takahashi tile step.  s_row: (e_n, j_n, t, t), g_col:
    (j_n, t, t) -> u: (e_n, t, t).

    Matches ``ref.selinv_step_ref`` bit-for-bit in float32.
    """
    e_n, j_n, t, _ = s_row.shape
    if e_n == 0 or j_n == 0:
        return jnp.zeros((e_n, t, t), s_row.dtype)
    jb = min(jblock, j_n)
    njb = pl.cdiv(j_n, jb)
    jpad = njb * jb
    sp = jnp.pad(s_row, ((0, 0), (0, jpad - j_n), (0, 0), (0, 0)))
    gp = jnp.pad(g_col, ((0, jpad - j_n), (0, 0), (0, 0)))
    return pl.pallas_call(
        functools.partial(_selinv_step_kernel, jb=jb, njb=njb),
        grid=(e_n, njb),
        in_specs=[
            pl.BlockSpec((1, jb, t, t), lambda e, j: (e, j, 0, 0)),
            pl.BlockSpec((jb, t, t), lambda e, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, t, t), lambda e, j: (e, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((e_n, t, t), s_row.dtype),
        scratch_shapes=[pltpu.VMEM((t, t), jnp.float32)],
        interpret=interpret,
    )(sp, gp)


# ---------------------------------------------------------------------------
# Fused backward sweep: the whole Takahashi recurrence in one launch
# ---------------------------------------------------------------------------

def _selinv_sweep_kernel(start_ref, lcol_ref, r_ref, sc_ref, p_ref, a_ref,
                         ring_ref, ringa_ref, g_ref, *, ndt: int, bt: int,
                         nat_p: int):
    s = pl.program_id(0)
    j = ndt - 1 - s
    start = start_ref[0]
    t = lcol_ref.shape[-1]

    @pl.when(s == 0)
    def _init():
        ring_ref[...] = jnp.zeros_like(ring_ref)
        ringa_ref[...] = jnp.zeros_like(ringa_ref)

    # Canonical-grid fast finish (core/gridpolicy.py): columns j < start
    # are the identity-embedding prefix — decoupled, so their Σ panel is
    # exactly the identity (Σ_embedded = blockdiag(I, Σ)).  The backward
    # walk reaches them last, nothing reads their ring slots afterwards,
    # and the whole seed/normalize/contract body is skipped.
    @pl.when(j < start)
    def _skip():
        p_ref[0] = identity_prefix_panel(bt, t).astype(p_ref.dtype)
        a_ref[0] = jnp.zeros_like(a_ref[0])

    @pl.when(j >= start)
    def _work():
        _selinv_sweep_body(lcol_ref, r_ref, sc_ref, p_ref, a_ref,
                           ring_ref, ringa_ref, g_ref, j, bt=bt, nat_p=nat_p)


def _selinv_sweep_body(lcol_ref, r_ref, sc_ref, p_ref, a_ref,
                       ring_ref, ringa_ref, g_ref, j, *, bt: int, nat_p: int):
    t = lcol_ref.shape[-1]
    zero = jnp.zeros((t, t), jnp.float32)

    def col(d, e):
        # Σ_{j+d+e, j+d}: offset e of the Σ column j+d (from the VMEM ring;
        # zeros past ndt-1 / from the step-0 init)
        return ring_read(ring_ref, j + d, bt, e)

    # seed: winv = L_jj^{-1} (in-kernel substitution against the identity),
    # s0 = (L_jj L_jj^T)^{-1} = winv^T winv
    winv = substitute_panel(lcol_ref[0, 0].astype(jnp.float32), eye_tile(t))
    s0 = tile_dot(winv, winv, trans_a=True)

    # normalized column: G_d = L_{j+d, j} L_jj^{-1} (kept in VMEM scratch
    # so the loops below can index it), arrow Ga_i = R[j,i] winv
    for d in range(1, bt + 1):
        g_ref[d - 1] = tile_dot(lcol_ref[0, d].astype(jnp.float32), winv)
    ga = [tile_dot(r_ref[0, i].astype(jnp.float32), winv)
          for i in range(nat_p)]

    def loop(lo, hi, term, init):
        return jax.lax.fori_loop(lo, hi, lambda d, acc: acc + term(d), init)

    # off-diagonal band targets:  Σ_{j+e, j} = -sum_{k>j} Σ_{j+e, k} G_{k, j}
    off = []
    for e in range(1, bt + 1):
        # d <= e: Σ_{j+e, j+d} lives in column j+d at offset e-d
        acc = loop(1, e + 1, lambda d: tile_dot(col(d, e - d),
                                                g_ref[d - 1]), zero)
        # d > e: Σ_{j+e, j+d} = Σ_{j+d, j+e}^T, from column j+e
        acc = loop(e + 1, bt + 1,
                   lambda d: tile_dot(col(e, d - e), g_ref[d - 1],
                                      trans_a=True), acc)
        # arrow sources: sum_i Σ_{j+e, ndt+i} @ Ga_i = sum_i arow_e[i]^T Ga_i
        for i in range(nat_p):
            acc = acc + tile_dot(ring_read(ringa_ref, j + e, bt, i), ga[i],
                                 trans_a=True)
        off.append(-acc)

    # arrow targets:  Σ_{ndt+i, j} = -(sum_d Σ_{ndt+i, j+d} G_d
    #                                  + sum_i' Σ_cc[i, i'] Ga_i')
    acol = []
    for i in range(nat_p):
        ua = sum((tile_dot(sc_ref[i, i2].astype(jnp.float32), ga[i2])
                  for i2 in range(nat_p)), zero)
        ua = loop(1, bt + 1,
                  lambda d: tile_dot(ring_read(ringa_ref, j + d, bt, i),
                                     g_ref[d - 1]), ua)
        acol.append(-ua)

    # diagonal: Σ_jj = s0 - sum_{k>j} Σ_kj^T G_kj (the fresh off-diagonals)
    corr = sum((tile_dot(acol[i], ga[i], trans_a=True)
                for i in range(nat_p)), zero)
    for e in range(1, bt + 1):
        corr = corr + tile_dot(off[e - 1], g_ref[e - 1], trans_a=True)
    sjj = s0 - corr
    sjj = 0.5 * (sjj + sjj.T)

    panel = jnp.stack([sjj] + off)
    acols = jnp.stack(acol)
    if bt:
        ring_write(ring_ref, j, bt, panel)
        ring_write(ringa_ref, j, bt, acols)
    p_ref[0] = panel.astype(p_ref.dtype)
    a_ref[0] = acols.astype(a_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def selinv_sweep_pallas(lcol, R, sc_full, start_tile=0,
                        *, interpret: bool):
    """Fused backward Takahashi sweep.  lcol: (ndt, bt+1, t, t) column view
    of the factor (``lcol[j, d] = L[j+d, j]``, see ``ring.band_row_to_col``),
    R: (ndt, nat, t, t) arrow rows of the factor, sc_full: (nat, nat, t, t)
    full (symmetric) corner Σ seed ->

      panels (ndt, bt+1, t, t)  Σ column panels: panels[j, e] = Σ[j+e, j]
      acols  (ndt, nat, t, t)   arrow entries:   acols[j, i] = Σ[ndt+i, j]

    ``start_tile`` (traced SMEM scalar) declares columns ``j < start_tile``
    an identity-embedding prefix: they emit identity Σ panels without any
    recurrence work (``core/gridpolicy.py``).

    Matches ``ref.selinv_sweep_ref`` (the lax.scan oracle) to fp32 tolerance.
    """
    ndt, b1, t, _ = lcol.shape
    bt = b1 - 1
    nat = R.shape[1]
    if ndt == 0:
        return (jnp.zeros((0, b1, t, t), lcol.dtype),
                jnp.zeros((0, nat, t, t), lcol.dtype))
    nat_p = max(nat, 1)
    rp = R if nat else jnp.zeros((ndt, 1, t, t), lcol.dtype)
    scp = sc_full if nat else jnp.zeros((1, 1, t, t), lcol.dtype)
    start = jnp.reshape(jnp.asarray(start_tile, jnp.int32), (1,))
    tile = t * t * 4
    panels, acols = pl.pallas_call(
        functools.partial(_selinv_sweep_kernel, ndt=ndt, bt=bt, nat_p=nat_p),
        grid=(ndt,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, b1, t, t), lambda s: (ndt - 1 - s, 0, 0, 0)),
            pl.BlockSpec((1, nat_p, t, t), lambda s: (ndt - 1 - s, 0, 0, 0)),
            pl.BlockSpec((nat_p, nat_p, t, t), lambda s: (0, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, b1, t, t), lambda s: (ndt - 1 - s, 0, 0, 0)),
            pl.BlockSpec((1, nat_p, t, t), lambda s: (ndt - 1 - s, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((ndt, b1, t, t), lcol.dtype),
            jax.ShapeDtypeStruct((ndt, nat_p, t, t), lcol.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((max(bt, 1), b1, t, t), jnp.float32),
            pltpu.VMEM((max(bt, 1), nat_p, t, t), jnp.float32),
            pltpu.VMEM((max(bt, 1), t, t), jnp.float32),
        ],
        compiler_params=sweep_compiler_params(
            scratch=max(bt, 1) * (b1 + nat_p + 1) * tile,
            blocks=(2 * (b1 + nat_p) + nat_p * nat_p) * tile,
            temps=(3 * b1 + 2 * nat_p + 4) * tile),
        interpret=interpret,
        name="selinv_sweep_pallas",
    )(start, lcol, rp, scp)
    return panels, acols[:, :nat]

