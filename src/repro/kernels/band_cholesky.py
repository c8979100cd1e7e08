"""Pallas TPU kernel: the entire banded-arrowhead Cholesky in one launch.

After the solve sweeps were fused (``band_solve.py``), the factorization
itself was the last per-panel dispatcher: the ring sweep in
``core/cholesky.py`` ran one ``potrf`` + ``trsm`` + ``band_update`` launch
per band panel through a ``lax.scan``, round-tripping the (bt+1, t, t)
panel ring and the arrow ring through HBM on every step.  This kernel is
the factorization analogue of the fused solves — the whole band + arrow
factorization as one sequential-grid launch, in the spirit of tiled
Cholesky's "keep the active window resident" insight (Buttari et al.) and
the paper's left-looking accumulator reading of GEMM chains (§II):

* grid = (ndt,) — one sequential step per band *column* panel k; the TPU
  grid iteration order carries the factorization's critical path;
* a VMEM ring of the last ``bt`` finalized column panels plus an
  arrow-row ring (``kernels/ring.py``, shared with the solve and selinv
  sweeps) feeds the left-looking update

      U[e] = sum_{j=1..bt} L[k+e, k-j] @ L[k, k-j]^T

  entirely from VMEM — the ``band_update`` contraction with no HBM reads;
* the diagonal tile factorizes in-kernel (:func:`potrf.factorize_tile`,
  shared with the single-tile POTRF kernel) and is inverted once
  (:func:`trsm.invert_lower_tile`); each sub-diagonal and arrow tile of
  the column is then one MXU product ``X = A L_kk^{-T}`` (``tile_dot``),
  so the column's VPU loops run over one tile whatever bt + nat, and each
  tile is stored as it is formed.  Both tile routines are blocked by
  nb = 32 rows where t >= 64 and 32 divides t (``potrf.tile_block``, from
  t alone; every t = 128 column): the factorization runs its t pivots on
  (nb, t) row slabs with one MXU trailing update a slab, the inverse one
  nb-step substitution over the diagonal blocks and a few MXU products
  for the rest.  Smaller tiles run the unblocked t-step loops;
* the corner Schur complement rides the sweep: partial sums
  ``sum_k L_a[k] L_a[k]^T`` accumulate in a VMEM scratch and emit once
  per chunk, so the corner factorization reads a precomputed
  (nchunks, nat, nat, t, t) buffer instead of re-contracting the whole
  arrow block from HBM (and the chunked layout preserves the paper's
  Alg. 3 tree-reduction association).

VMEM budget per step: the panel ring bt·(bt+1)·t², the arrow ring
bt·nat·t², the Schur accumulator nat²·t², the (bt+1+nat)·t² in/out blocks
and the step's live values; the kernel asks Mosaic for that
(``_compiler_params``) — e.g. 35.6 MiB at bt=16, t=128, nat=2, of the
128 MiB of VMEM on a v5e core.

The ring grows as bt², so past bt = 32 (t = 128) it cannot fit, and
:func:`sweep_path` picks the **streamed sweep**
(:func:`band_cholesky_stream_sweep_pallas`) from (t, bt, nat) alone.  It
keeps the factor in HBM (``memory_space=pl.ANY``), written in place
through ``input_output_aliases``, and a sequential (batch, ndt) grid walks
the columns.  Column k DMAs its own bt+1 band and nat arrow tiles in,
streams the update from its sources k-j (j = 1..min(k, bt)) — tiles j..bt
of column k-j in double-buffered blocks of g = 8 tiles, each one MXU
product of the stacked (g·t, t) block by L[k, k-j]^T into a (bt+1+nat)-
tile VMEM accumulator, and the source's arrow tiles with its first
block — then finishes as the ring kernels do (``_finish_column``) and
DMAs the column back.  VMEM per step: the column and its accumulator
2·(bt+1+nat)·t², the window 2·(g+nat)·t², L[k, k-j] and the Schur
accumulator (nat²+1)·t², no ring: 16.25 MiB at bt = 118, t = 128,
nat = 1.  HBM bytes a matrix (:func:`stream_bytes`): each column read and
written once plus Σ_k Σ_{j ≤ min(k, bt)} (g·ceil((bt+1-j)/g) + nat) tiles
of window, 179.3 GB for Table II ID 19 (ndt 391), against 2.75 M tile
products of update.

Both match ``ref.band_cholesky_sweep_ref`` (the lax.scan oracle) to fp32
tolerance; ``kernels.ops.band_cholesky_sweep`` dispatches between them and
it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import custom_batching
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ring
from .potrf import factorize_tile
from .ring import (chunk_layout, identity_prefix_panel, ring_read,
                   ring_write, sweep_compiler_params, tile_dot)
from .trsm import invert_lower_tile

__all__ = ["band_cholesky_sweep_pallas", "band_cholesky_stream_sweep_pallas",
           "band_cholesky_partitioned_sweep_pallas", "sweep_path",
           "stream_bytes"]


# The breakdown status word [min_pivot, nonfinite, first_bad] rides the
# sweep as one (8, 128) VMEM vector — lanes 0, 1 and 2 hold the three
# entries — because Mosaic cannot store scalars to VMEM and a (1, 3) block
# of a (P, 3) array breaks the TPU block-shape rule.  Its output block has
# a constant index map within a sweep, so it stays resident across the
# sequential grid and doubles as the carry.
_ST_SHAPE = (8, 128)


def _status_init(st_ref):
    lane = jax.lax.broadcasted_iota(jnp.int32, _ST_SHAPE, 1)
    st_ref[0] = jnp.where(lane == 0, jnp.inf,
                          jnp.where(lane == 1, 0.0, -1.0)).astype(jnp.float32)


def _status_fold(st_ref, piv, nonfinite, bad, col):
    """Fold one column into the carry: the per-column update
    ``ref.sweep_status`` applies to the emitted factor, so both backends
    report identical words."""
    st = st_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, _ST_SHAPE, 1)
    colv = jnp.full(_ST_SHAPE, col, jnp.int32).astype(jnp.float32)
    st_ref[0] = jnp.where(
        lane == 0, jnp.minimum(st, piv),
        jnp.where(lane == 1, jnp.maximum(st, nonfinite),
                  jnp.where((st < 0.0) & bad, colv, st)))


def _not_finite(x):
    return jnp.max(jnp.max(jnp.where(jnp.isfinite(x), 0.0, 1.0), axis=0))


def _finish_column(a_kk, band_in, arrow_in, put_band, put_arrow, sacc_ref,
                   sch_ref, st_ref, col, *, bt: int, nat_p: int,
                   unroll: bool = True):
    """Everything of one column after its left-looking update, shared by
    every Cholesky sweep: the diagonal tile ``a_kk`` (its update already
    subtracted) factorizes, is inverted once (both blocked by
    ``potrf.tile_block``), and each sub-diagonal tile ``band_in(e)``
    (e = 1..bt) and arrow tile ``arrow_in(i)`` (updates subtracted)
    becomes one MXU product X = A L_kk^{-T}, so the only VPU loops a
    column runs are over a single tile.  ``put_band(e, x)`` /
    ``put_arrow(i, x)`` store each factor tile as it is formed (e = 0 is
    L_kk), so no value of the whole column stays live; then the corner-Schur
    partial sums and the status word fold the column in.  ``unroll=False``
    walks the band tiles with a loop instead of unrolling them (wide
    bands)."""
    t = a_kk.shape[-1]
    lkk = factorize_tile(a_kk)
    winv = invert_lower_tile(lkk)                       # L_kk^{-1}
    put_band(0, lkk)
    nonfinite = _not_finite(lkk)

    def band(e, nonfinite):
        x = tile_dot(band_in(e), winv, trans_b=True)
        put_band(e, x)
        return jnp.maximum(nonfinite, _not_finite(x))

    if unroll:
        for e in range(1, bt + 1):
            nonfinite = band(e, nonfinite)
    else:
        nonfinite = jax.lax.fori_loop(1, bt + 1, band, nonfinite)
    la = []
    for i in range(nat_p):
        x = tile_dot(arrow_in(i), winv, trans_b=True)
        put_arrow(i, x)
        nonfinite = jnp.maximum(nonfinite, _not_finite(x))
        la.append(x)

    # corner-Schur partial sums on the fly: sacc[i,j] += La[i] @ La[j]^T
    for i in range(nat_p):
        for j in range(nat_p):
            sacc_ref[i, j] += tile_dot(la[i], la[j], trans_b=True)
    sch_ref[0] = sacc_ref[...].astype(sch_ref.dtype)

    # in-sweep breakdown detection (masked 2-D reductions only)
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    dmask = rows == cols
    dsq = jnp.where(dmask, lkk * lkk, jnp.float32(jnp.inf))
    nonfin_d = jnp.max(jnp.where(dmask & ~jnp.isfinite(lkk), 1.0, 0.0))
    piv = jnp.where(nonfin_d > 0.0, jnp.float32(jnp.inf), jnp.min(dsq))
    bad = (nonfinite > 0.0) | (piv <= 0.0)
    _status_fold(st_ref, piv, nonfinite, bad, col)


def _cholesky_column(ac_ref, r_ref, p_ref, ro_ref, sch_ref, st_ref,
                     ring_ref, ringa_ref, sacc_ref, k, col,
                     *, bt: int, nat_p: int):
    """One left-looking column step of both ring sweep kernels.  ``k``
    drives the rings (the local column of the partition); ``col`` is the
    global column the status word records."""
    t = ac_ref.shape[-1]
    # rhs_j = L[k, k-j] = panel_{k-j}[j], read from the VMEM ring (zeros for
    # k-j < 0 from the step-0 init).  Ring tiles are loaded one at a time
    # where they are used: loading whole panels would hold bt·(bt+1) tiles
    # of values live next to the ring itself.
    rhs = [ring_read(ring_ref, k - j, bt, j) for j in range(1, bt + 1)]

    # left-looking band update: U[e] = sum_j L[k+e, k-j] @ L[k, k-j]^T
    # (e = 0 is the SYRK chain, e > 0 the GEMM chains; e+j > bt pairs are
    # structurally outside the band)
    def band_term(e):
        def body(j, acc):
            return acc + tile_dot(ring_read(ring_ref, k - j, bt, e + j),
                                  ring_read(ring_ref, k - j, bt, j),
                                  trans_b=True)
        return jax.lax.fori_loop(1, bt + 1 - e, body,
                                 jnp.zeros((t, t), jnp.float32))

    # arrow-row update: V[i] = sum_j L[ndt+i, k-j] @ L[k, k-j]^T
    va = [sum((tile_dot(ring_read(ringa_ref, k - j, bt, i), rhs[j - 1],
                        trans_b=True) for j in range(1, bt + 1)),
              jnp.zeros((t, t), jnp.float32))
          for i in range(nat_p)]

    # ring slot k overwrites column k-bt, which band_term(0) and rhs were
    # the last to read
    def put_band(e, x):
        p_ref[0, e] = x.astype(p_ref.dtype)
        if bt:
            ring_write(ring_ref, k, bt, x, e)

    def put_arrow(i, x):
        ro_ref[0, i] = x.astype(ro_ref.dtype)
        if bt:
            ring_write(ringa_ref, k, bt, x, i)

    _finish_column(
        ac_ref[0, 0].astype(jnp.float32) - band_term(0),
        lambda e: ac_ref[0, e].astype(jnp.float32) - band_term(e),
        lambda i: r_ref[0, i].astype(jnp.float32) - va[i],
        put_band, put_arrow, sacc_ref, sch_ref, st_ref, col,
        bt=bt, nat_p=nat_p)


def _band_cholesky_kernel(start_ref, ac_ref, r_ref, p_ref, ro_ref, sch_ref,
                          st_ref, ring_ref, ringa_ref, sacc_ref,
                          *, bt: int, nat_p: int, csz: int):
    k = pl.program_id(0)
    start = start_ref[0]
    t = ac_ref.shape[-1]

    @pl.when(k == 0)
    def _init():
        ring_ref[...] = jnp.zeros_like(ring_ref)
        ringa_ref[...] = jnp.zeros_like(ringa_ref)
        _status_init(st_ref)

    @pl.when(jax.lax.rem(k, csz) == 0)
    def _chunk_init():
        sacc_ref[...] = jnp.zeros_like(sacc_ref)

    # Canonical-grid fast start (core/gridpolicy.py): columns k < start
    # are the identity-embedding prefix, whose factor is known — an
    # identity panel with zero arrow rows — so the whole update/potrf/trsm
    # body is skipped.  The prefix forms a contiguous head of the walk and
    # its ring slots keep the step-0 zeros; later columns read rhs_j =
    # panel_{k-j}[j], an off-diagonal slot that is zero for identity
    # panels, so skipping the ring writes is exact.
    @pl.when(k < start)
    def _skip():
        p_ref[0] = identity_prefix_panel(bt, t).astype(p_ref.dtype)
        ro_ref[0] = jnp.zeros_like(ro_ref[0])
        sch_ref[0] = sacc_ref[...].astype(sch_ref.dtype)
        # identity panel: pivot 1, finite — same fold ref.sweep_status
        # applies to the emitted identity column
        _status_fold(st_ref, jnp.float32(1.0), jnp.float32(0.0),
                     jnp.bool_(False), k)

    @pl.when(k >= start)
    def _work():
        _cholesky_column(ac_ref, r_ref, p_ref, ro_ref, sch_ref, st_ref,
                         ring_ref, ringa_ref, sacc_ref, k, k,
                         bt=bt, nat_p=nat_p)


def _ring_vmem(b1, nat_p, t):
    """VMEM budget of one ring sweep step: the panel and arrow rings and
    the Schur accumulator (scratch), the in/out column blocks, and the
    step's live values (an allowance sized for the whole column)."""
    tile = t * t * 4
    bt = max(b1 - 1, 1)
    return dict(scratch=(bt * b1 + bt * nat_p + nat_p * nat_p) * tile,
                blocks=(2 * (b1 + nat_p) + nat_p * nat_p) * tile,
                temps=6 * (b1 + nat_p) * tile)


def _compiler_params(b1, nat_p, t, semantics):
    return sweep_compiler_params(**_ring_vmem(b1, nat_p, t),
                                 semantics=semantics)


def sweep_path(t: int, bt: int, nat: int) -> str:
    """The Cholesky sweep the Pallas backend runs for tile size ``t``,
    ``bt`` band tiles and ``nat`` arrow tiles: ``"fused"`` (the VMEM ring,
    :func:`band_cholesky_sweep_pallas`) while its VMEM ask fits under
    ``ring.VMEM_CAP_BYTES``, else ``"stream"``
    (:func:`band_cholesky_stream_sweep_pallas`).  At t = 128 and one or
    two arrow tiles the ring fits up to bt = 32."""
    fits = ring.vmem_ask(**_ring_vmem(bt + 1, max(nat, 1), t)) \
        <= ring.VMEM_CAP_BYTES
    return "fused" if fits else "stream"


@functools.partial(jax.jit, static_argnames=("nchunks", "interpret"))
def band_cholesky_sweep_pallas(Ac, R, nchunks: int = 1, start_tile=0,
                               *, interpret: bool):
    """Fused band+arrow Cholesky sweep.  Ac: (ndt, bt+1, t, t) column-band
    tiles (``Ac[k, e] = A[k+e, k]``, see ``ring.band_row_to_col``), R:
    (ndt, nat, t, t) arrow rows ->

      panels (ndt, bt+1, t, t)      column panels of L: panels[k, e] = L[k+e, k]
      R_out  (ndt, nat, t, t)       factored arrow rows L[ndt+i, k]
      schur  (nch, nat, nat, t, t)  per-chunk partial sums of R_out·R_outᵀ
                                    (``nch = chunk_layout(ndt, nchunks)[1]``)
      status (3,) float32           breakdown word [min_pivot, nonfinite,
                                    first_bad] accumulated *in-kernel* as
                                    the sweep runs (a VMEM-resident carry —
                                    no extra HBM pass, no host sync);
                                    matches ``ref.sweep_status`` exactly

    ``start_tile`` (traced SMEM scalar) declares columns ``k < start_tile``
    an identity-embedding prefix (``core/gridpolicy.py``): they emit
    identity panels / zero arrow rows without any update, potrf or trsm
    work, so canonical-grid diagonal slack costs ~0 compute.

    Matches ``ref.band_cholesky_sweep_ref`` to fp32 tolerance.
    """
    ndt, b1, t, _ = Ac.shape
    bt = b1 - 1
    nat = R.shape[1]
    csz, nch = chunk_layout(ndt, nchunks)
    if ndt == 0:
        from .ref import empty_sweep_status
        return (jnp.zeros((0, b1, t, t), Ac.dtype),
                jnp.zeros((0, nat, t, t), Ac.dtype),
                jnp.zeros((nch, nat, nat, t, t), Ac.dtype),
                empty_sweep_status())
    # zero-width arrow blocks break BlockSpecs: pad to one all-zero arrow
    # tile row (its factor and Schur terms vanish) and slice the output back.
    nat_p = max(nat, 1)
    rp = R if nat else jnp.zeros((ndt, 1, t, t), Ac.dtype)
    start = jnp.reshape(jnp.asarray(start_tile, jnp.int32), (1,))
    panels, ro, schur, st = pl.pallas_call(
        functools.partial(_band_cholesky_kernel, bt=bt, nat_p=nat_p, csz=csz),
        grid=(ndt,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, b1, t, t), lambda k: (k, 0, 0, 0)),
            pl.BlockSpec((1, nat_p, t, t), lambda k: (k, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, b1, t, t), lambda k: (k, 0, 0, 0)),
            pl.BlockSpec((1, nat_p, t, t), lambda k: (k, 0, 0, 0)),
            pl.BlockSpec((1, nat_p, nat_p, t, t),
                         lambda k: (k // csz, 0, 0, 0, 0)),
            pl.BlockSpec((1,) + _ST_SHAPE, lambda k: (0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((ndt, b1, t, t), Ac.dtype),
            jax.ShapeDtypeStruct((ndt, nat_p, t, t), Ac.dtype),
            jax.ShapeDtypeStruct((nch, nat_p, nat_p, t, t), Ac.dtype),
            jax.ShapeDtypeStruct((1,) + _ST_SHAPE, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((max(bt, 1), b1, t, t), jnp.float32),
            pltpu.VMEM((max(bt, 1), nat_p, t, t), jnp.float32),
            pltpu.VMEM((nat_p, nat_p, t, t), jnp.float32),
        ],
        compiler_params=_compiler_params(b1, nat_p, t, ("arbitrary",)),
        interpret=interpret,
        name="band_cholesky_sweep_pallas",
    )(start, Ac, rp)
    return panels, ro[:, :nat], schur[:, :nat, :nat], st[0, 0, :3]


# ---------------------------------------------------------------------------
# The streamed sweep: the factor in HBM, the band update streamed through VMEM
# ---------------------------------------------------------------------------

# band tiles one window DMA moves
_STREAM_TILES = 8


def _stream_block(bt: int) -> int:
    return max(1, min(_STREAM_TILES, bt))


def _stream_window(p_hbm, ro_hbm, acc_ref, rhs_ref, win_ref, wina_ref, sem,
                   b, k, jmax, *, bt: int, nat_p: int, g: int):
    """The left-looking update of column k streamed from HBM:
    ``acc[e] += L[k+e, k-j] L[k, k-j]^T`` (e = 0..bt-j) and
    ``acc[bt+1+i] += L[ndt+i, k-j] L[k, k-j]^T`` for j = 1..jmax.  Source
    column k-j needs its tiles j..bt, which blocks of ``g`` tiles starting
    at j, j+g, ... cover (the last one moved back to end at tile bt, its
    tiles already taken skipped), plus its arrow tiles with the first
    block.  Each block is one DMA, double-buffered across the flat walk
    of (j, block) units, and one MXU product of its g stacked tiles by
    L[k, k-j]^T."""
    b1 = bt + 1
    t = acc_ref.shape[-1]
    f32 = jnp.float32

    def first(j, q):
        return jnp.minimum(j + q * g, b1 - g)

    def band_copy(j, q, slot):
        return pltpu.make_async_copy(p_hbm.at[b, k - j, pl.ds(first(j, q), g)],
                                     win_ref.at[slot], sem.at[2 + slot])

    def arrow_copy(j, slot):
        return pltpu.make_async_copy(ro_hbm.at[b, k - j], wina_ref.at[slot],
                                     sem.at[4 + slot])

    def fetch(j, q, slot):
        band_copy(j, q, slot).start()

        @pl.when(q == 0)
        def _():
            arrow_copy(j, slot).start()

    def source(j, slot):
        nb = (b1 - j + g - 1) // g

        def block(q, slot):
            last = q + 1 == nb
            nj = jnp.where(last, j + 1, j)

            @pl.when(nj <= jmax)
            def _():
                fetch(nj, jnp.where(last, 0, q + 1), 1 - slot)

            band_copy(j, q, slot).wait()
            s = first(j, q)

            @pl.when(q == 0)
            def _():
                arrow_copy(j, slot).wait()
                rhs_ref[...] = win_ref[slot, j - s].astype(f32)
                for i in range(nat_p):
                    acc_ref[b1 + i] += tile_dot(
                        wina_ref[slot, i].astype(f32), rhs_ref[...],
                        trans_b=True)

            prod = tile_dot(win_ref[slot].astype(f32).reshape(g * t, t),
                            rhs_ref[...], trans_b=True)
            lo = jnp.maximum(j + q * g, s)
            for p in range(g):
                @pl.when(s + p >= lo)
                def _():
                    acc_ref[s + p - j] += prod[p * t:(p + 1) * t]
            return 1 - slot

        return jax.lax.fori_loop(0, nb, block, slot)

    @pl.when(jmax > 0)
    def _():
        fetch(1, 0, 0)
        jax.lax.fori_loop(1, jmax + 1, source, 0)


def _band_cholesky_stream_kernel(start_ref, ac_hbm, r_hbm, p_hbm, ro_hbm,
                                 sch_ref, st_ref, col_ref, arow_ref, acc_ref,
                                 rhs_ref, win_ref, wina_ref, sacc_ref, sem,
                                 *, bt: int, nat_p: int, csz: int, g: int):
    # ac_hbm and r_hbm are p_hbm and ro_hbm (aliased): column k is read
    # from and written back to the output, which holds the input's columns
    # >= k and the factor's columns < k
    del ac_hbm, r_hbm
    b = pl.program_id(0)
    k = pl.program_id(1)
    start = start_ref[b]
    t = col_ref.shape[-1]
    f32 = jnp.float32

    @pl.when(k == 0)
    def _init():
        _status_init(st_ref)

    @pl.when(jax.lax.rem(k, csz) == 0)
    def _chunk_init():
        sacc_ref[...] = jnp.zeros_like(sacc_ref)

    def column_copies(to_hbm):
        pairs = ((col_ref, p_hbm.at[b, k], sem.at[0]),
                 (arow_ref, ro_hbm.at[b, k], sem.at[1]))
        return [pltpu.make_async_copy(v, h, s) if to_hbm
                else pltpu.make_async_copy(h, v, s) for v, h, s in pairs]

    def write_column():
        out = column_copies(True)
        for c in out:
            c.start()
        for c in out:
            c.wait()

    # the identity-embedding prefix, as in the fused kernel
    @pl.when(k < start)
    def _skip():
        col_ref[...] = identity_prefix_panel(bt, t).astype(col_ref.dtype)
        arow_ref[...] = jnp.zeros_like(arow_ref)
        write_column()
        sch_ref[0, 0] = sacc_ref[...].astype(sch_ref.dtype)
        _status_fold(st_ref, jnp.float32(1.0), jnp.float32(0.0),
                     jnp.bool_(False), k)

    @pl.when(k >= start)
    def _work():
        read = column_copies(False)
        for c in read:
            c.start()
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # source columns inside the identity prefix have no tile in row k
        _stream_window(p_hbm, ro_hbm, acc_ref, rhs_ref, win_ref, wina_ref,
                       sem, b, k, jnp.minimum(bt, k - start),
                       bt=bt, nat_p=nat_p, g=g)
        for c in read:
            c.wait()

        def put_band(e, x):
            col_ref[e] = x.astype(col_ref.dtype)

        def put_arrow(i, x):
            arow_ref[i] = x.astype(arow_ref.dtype)

        _finish_column(
            col_ref[0].astype(f32) - acc_ref[0],
            lambda e: col_ref[e].astype(f32) - acc_ref[e],
            lambda i: arow_ref[i].astype(f32) - acc_ref[bt + 1 + i],
            put_band, put_arrow, sacc_ref, sch_ref.at[0], st_ref, k,
            bt=bt, nat_p=nat_p, unroll=False)
        write_column()


def _stream_vmem(b1, nat_p, t, g):
    """VMEM budget of one streamed sweep step: the column in and out, its
    update accumulator, the double-buffered window blocks and arrow rows,
    L[k, k-j] and the Schur accumulator (scratch), the Schur and status
    blocks, and the step's live values (a block's product and a column
    tile's)."""
    tile = t * t * 4
    return dict(scratch=(2 * (b1 + nat_p) + 1 + 2 * (g + nat_p)
                         + nat_p * nat_p) * tile,
                blocks=nat_p * nat_p * tile,
                temps=(g + 8) * tile)


def _stream_call(Ac, R, start, nchunks: int, interpret: bool):
    """The streamed sweep's launch over a leading batch axis: Ac (B, ndt,
    bt+1, t, t), R (B, ndt, nat_p, t, t), start (B,)."""
    B, ndt, b1, t, _ = Ac.shape
    bt = b1 - 1
    nat_p = R.shape[2]
    csz, nch = chunk_layout(ndt, nchunks)
    g = _stream_block(bt)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_band_cholesky_stream_kernel, bt=bt, nat_p=nat_p,
                          csz=csz, g=g),
        grid=(B, ndt),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), hbm, hbm],
        out_specs=[
            hbm, hbm,
            pl.BlockSpec((1, 1, nat_p, nat_p, t, t),
                         lambda b, k: (b, k // csz, 0, 0, 0, 0)),
            pl.BlockSpec((1,) + _ST_SHAPE, lambda b, k: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(Ac.shape, Ac.dtype),
            jax.ShapeDtypeStruct(R.shape, R.dtype),
            jax.ShapeDtypeStruct((B, nch, nat_p, nat_p, t, t), Ac.dtype),
            jax.ShapeDtypeStruct((B,) + _ST_SHAPE, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b1, t, t), Ac.dtype),
            pltpu.VMEM((nat_p, t, t), R.dtype),
            pltpu.VMEM((b1 + nat_p, t, t), jnp.float32),
            pltpu.VMEM((t, t), jnp.float32),
            pltpu.VMEM((2, g, t, t), Ac.dtype),
            pltpu.VMEM((2, nat_p, t, t), R.dtype),
            pltpu.VMEM((nat_p, nat_p, t, t), jnp.float32),
            pltpu.SemaphoreType.DMA((6,)),
        ],
        input_output_aliases={1: 0, 2: 1},
        compiler_params=sweep_compiler_params(
            **_stream_vmem(b1, nat_p, t, g),
            semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="band_cholesky_stream_sweep_pallas",
    )(start, Ac, R)


@functools.lru_cache(maxsize=None)
def _stream_batched(nchunks: int, interpret: bool):
    """:func:`_stream_call` as a function whose ``vmap`` folds the mapped
    axis into the launch's own batch axis: Pallas cannot batch a kernel
    whose operands stay in HBM, and one launch over the whole batch is
    what the fused sweep's batched grid amounts to."""
    @custom_batching.custom_vmap
    def call(Ac, R, start):
        return tuple(_stream_call(Ac, R, start, nchunks, interpret))

    @call.def_vmap
    def _vmap(axis_size, in_batched, Ac, R, start):
        args = [x if bat else jnp.broadcast_to(x, (axis_size,) + x.shape)
                for x, bat in zip((Ac, R, start), in_batched)]
        outs = call(*(x.reshape((-1,) + x.shape[2:]) for x in args))
        return (tuple(o.reshape((axis_size, -1) + o.shape[1:])
                      for o in outs), (True,) * len(outs))

    return call


@functools.partial(jax.jit, static_argnames=("nchunks", "interpret"))
def band_cholesky_stream_sweep_pallas(Ac, R, nchunks: int = 1, start_tile=0,
                                      *, interpret: bool):
    """The band+arrow Cholesky sweep for bands too wide for the VMEM ring
    of :func:`band_cholesky_sweep_pallas`, with its contract: (Ac, R) ->
    (panels, R_out, per-chunk Schur, status), ``start_tile`` as there.

    The factor stays in HBM (the input's buffers, written in place
    through ``input_output_aliases``: column k is read, factored and
    written back, so a column's sources k-1..k-bt are factor columns when
    it reads them).  A sequential grid walks the columns; each streams
    its left-looking update (``_stream_window``) into a VMEM accumulator,
    then finishes as the fused kernel does (``_finish_column``).
    :func:`stream_bytes` counts the HBM bytes its DMAs move.

    Matches ``ref.band_cholesky_sweep_ref`` to fp32 tolerance."""
    ndt, b1, t, _ = Ac.shape
    nat = R.shape[1]
    csz, nch = chunk_layout(ndt, nchunks)
    if ndt == 0:
        from .ref import empty_sweep_status
        return (jnp.zeros((0, b1, t, t), Ac.dtype),
                jnp.zeros((0, nat, t, t), Ac.dtype),
                jnp.zeros((nch, nat, nat, t, t), Ac.dtype),
                empty_sweep_status())
    rp = R if nat else jnp.zeros((ndt, 1, t, t), Ac.dtype)
    start = jnp.reshape(jnp.asarray(start_tile, jnp.int32), (1,))
    panels, ro, schur, st = _stream_batched(nchunks, interpret)(
        Ac[None], rp[None], start)
    return panels[0], ro[0, :, :nat], schur[0, :, :nat, :nat], st[0, 0, :3]


def stream_bytes(ndt: int, bt: int, nat: int, t: int,
                 itemsize: int = 4) -> int:
    """HBM bytes the DMAs of :func:`band_cholesky_stream_sweep_pallas`
    move for one matrix: every column read and written once (bt+1 band
    and nat arrow tiles, at least one), and for each source column k-j
    (j = 1..min(k, bt)) of column k the ceil((bt+1-j)/g) blocks of g tiles
    and the arrow tiles its update reads.  Columns of an identity prefix
    (``start_tile``) are counted as worked."""
    g = _stream_block(bt)
    b1, nat_p = bt + 1, max(nat, 1)
    per_source = [-(-(b1 - j) // g) * g + nat_p for j in range(1, bt + 1)]
    upto = [0]
    for n in per_source:
        upto.append(upto[-1] + n)
    tiles = ndt * 2 * (b1 + nat_p) + sum(upto[min(k, bt)] for k in range(ndt))
    return tiles * t * t * itemsize


def _band_cholesky_partitioned_kernel(bounds_ref, start_ref, ac_ref, r_ref,
                                      p_ref, ro_ref, sch_ref, st_ref,
                                      ring_ref, ringa_ref, sacc_ref,
                                      *, bt: int, nat_p: int):
    p = pl.program_id(0)
    k = pl.program_id(1)                       # local step within partition p
    s0 = bounds_ref[p]
    size = bounds_ref[p + 1] - s0
    g = s0 + k                                 # global column index
    start = start_ref[0]
    active = k < size
    t = ac_ref.shape[-1]

    @pl.when(k == 0)
    def _init():
        # fresh partition: its rings, Schur accumulator and per-partition
        # status word all reset — partitions share no state, which is what
        # lets the leading grid axis carry "parallel" semantics
        ring_ref[...] = jnp.zeros_like(ring_ref)
        ringa_ref[...] = jnp.zeros_like(ringa_ref)
        sacc_ref[...] = jnp.zeros_like(sacc_ref)
        _status_init(st_ref)

    # Steps k >= size are padding of the rectangular (P, max_tiles) grid:
    # they touch nothing — the clamped index maps revisit the partition's
    # last blocks, which persist unchanged.
    @pl.when(active & (g < start))
    def _skip():
        # canonical-grid identity prefix (contiguous global head, so within
        # a partition the skips precede all work steps) — same contract as
        # the unpartitioned kernel
        p_ref[0] = identity_prefix_panel(bt, t).astype(p_ref.dtype)
        ro_ref[0] = jnp.zeros_like(ro_ref[0])
        sch_ref[0] = sacc_ref[...].astype(sch_ref.dtype)
        _status_fold(st_ref, jnp.float32(1.0), jnp.float32(0.0),
                     jnp.bool_(False), g)

    @pl.when(active & (g >= start))
    def _work():
        # the *local* index k drives the rings (panel k-j of this
        # partition; k-j < 0 reads the step-0 zeros, exactly the
        # cross-boundary zeros block-separability guarantees), one Schur
        # chunk per partition is the tree-reduction leaf it contributes to
        # the shared corner, and first_bad is recorded in *global* columns
        # so the per-partition words fold with ref.combine_sweep_status
        _cholesky_column(ac_ref, r_ref, p_ref, ro_ref, sch_ref, st_ref,
                         ring_ref, ringa_ref, sacc_ref, k, g,
                         bt=bt, nat_p=nat_p)


@functools.partial(jax.jit, static_argnames=("boundaries", "interpret"))
def band_cholesky_partitioned_sweep_pallas(Ac, R, boundaries, start_tile=0,
                                           *, interpret: bool):
    """Partition-parallel fused band+arrow Cholesky: one launch over all
    ND partitions.

    Same input layout as :func:`band_cholesky_sweep_pallas`, plus the
    static ``boundaries`` tuple ``(0, c_1, ..., ndt)`` of a
    :class:`~repro.core.ordering.PartitionPlan` certifying that no band
    tile crosses a cut (block-separable input — the adaptive-ND ordering's
    independent partitions).  The grid becomes 2D:

      grid = (P, max_tiles) — the leading axis walks partitions with
      ``parallel`` dimension semantics (partitions share no state: rings,
      Schur accumulator and status word all reset at each partition's step
      0), the trailing axis is the per-partition sequential factorization.
      The critical path drops from O(ndt) sequential steps to
      O(max partition tiles).

    Partition sizes are ragged; the rectangular grid is padded and the
    per-column index maps clamp to the partition's last tile, where the
    padding steps are pure no-ops.  ``boundaries`` rides scalar prefetch
    (`pltpu.PrefetchScalarGridSpec`) so the index maps can look the
    partition's tile range up dynamically.

    Output layout matches ``ref.band_cholesky_partitioned_sweep_ref``:
    panels/R_out as usual, ``schur (P, nat, nat, t, t)`` with one
    tree-reduction leaf per partition, and the global (3,) status word.
    """
    from .ref import combine_sweep_status, empty_sweep_status

    ndt, b1, t, _ = Ac.shape
    bt = b1 - 1
    nat = R.shape[1]
    bounds = tuple(int(b) for b in boundaries)
    if len(bounds) < 2 or bounds[0] != 0 or bounds[-1] != ndt or \
            any(b1_ <= b0_ for b0_, b1_ in zip(bounds, bounds[1:])):
        raise ValueError(
            f"boundaries {bounds!r} must be strictly increasing from 0 "
            f"to ndt={ndt}")
    P = len(bounds) - 1
    maxk = max(b1_ - b0_ for b0_, b1_ in zip(bounds, bounds[1:]))
    if ndt == 0:
        return (jnp.zeros((0, b1, t, t), Ac.dtype),
                jnp.zeros((0, nat, t, t), Ac.dtype),
                jnp.zeros((P, nat, nat, t, t), Ac.dtype),
                empty_sweep_status())
    nat_p = max(nat, 1)
    rp = R if nat else jnp.zeros((ndt, 1, t, t), Ac.dtype)
    bounds_arr = jnp.asarray(bounds, jnp.int32)
    start = jnp.reshape(jnp.asarray(start_tile, jnp.int32), (1,))

    def col(p, k, bounds_ref, start_ref):
        # partition p's column s0+k, clamped to its last tile for padding
        return (jnp.minimum(bounds_ref[p] + k, bounds_ref[p + 1] - 1),
                0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(P, maxk),
        in_specs=[
            pl.BlockSpec((1, b1, t, t), col),
            pl.BlockSpec((1, nat_p, t, t), col),
        ],
        out_specs=[
            pl.BlockSpec((1, b1, t, t), col),
            pl.BlockSpec((1, nat_p, t, t), col),
            pl.BlockSpec((1, nat_p, nat_p, t, t),
                         lambda p, k, b, s: (p, 0, 0, 0, 0)),
            pl.BlockSpec((1,) + _ST_SHAPE, lambda p, k, b, s: (p, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((max(bt, 1), b1, t, t), jnp.float32),
            pltpu.VMEM((max(bt, 1), nat_p, t, t), jnp.float32),
            pltpu.VMEM((nat_p, nat_p, t, t), jnp.float32),
        ],
    )
    panels, ro, schur, st = pl.pallas_call(
        functools.partial(_band_cholesky_partitioned_kernel,
                          bt=bt, nat_p=nat_p),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((ndt, b1, t, t), Ac.dtype),
            jax.ShapeDtypeStruct((ndt, nat_p, t, t), Ac.dtype),
            jax.ShapeDtypeStruct((P, nat_p, nat_p, t, t), Ac.dtype),
            jax.ShapeDtypeStruct((P,) + _ST_SHAPE, jnp.float32),
        ],
        compiler_params=_compiler_params(b1, nat_p, t,
                                         ("parallel", "arbitrary")),
        interpret=interpret,
        name="band_cholesky_partitioned_sweep_pallas",
    )(bounds_arr, start, Ac, rp)
    return (panels, ro[:, :nat], schur[:, :nat, :nat],
            combine_sweep_status(st[:, 0, :3]))
