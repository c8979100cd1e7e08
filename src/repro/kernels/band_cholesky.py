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
  (:func:`trsm.substitute_panel` against the identity, as the selinv
  sweep seeds its columns); each sub-diagonal and arrow tile of the
  column is then one MXU product ``X = A L_kk^{-T}`` (``tile_dot``), so
  the column's t-step VPU loops run over one tile whatever bt + nat, and
  each tile is stored as it is formed;
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

Matches ``ref.band_cholesky_sweep_ref`` (the lax.scan oracle) to fp32
tolerance; ``kernels.ops.band_cholesky_sweep`` dispatches between them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .potrf import factorize_tile
from .ring import (chunk_layout, eye_tile, identity_prefix_panel, ring_read,
                   ring_write, sweep_compiler_params, tile_dot)
from .trsm import substitute_panel

__all__ = ["band_cholesky_sweep_pallas", "band_cholesky_partitioned_sweep_pallas"]


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


def _cholesky_column(ac_ref, r_ref, p_ref, ro_ref, sch_ref, st_ref,
                     ring_ref, ringa_ref, sacc_ref, k, col,
                     *, bt: int, nat_p: int):
    """One left-looking column step shared by both sweep kernels.  ``k``
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

    # diagonal tile, then its inverse once: every sub-diagonal and arrow
    # tile of the column is X = A L_kk^{-T}, one MXU product each, so the
    # only t-step VPU loops a column runs are over a single tile
    lkk = factorize_tile(ac_ref[0, 0].astype(jnp.float32) - band_term(0))
    winv = substitute_panel(lkk, eye_tile(t))           # L_kk^{-1}

    def not_finite(x):
        return jnp.max(jnp.max(jnp.where(jnp.isfinite(x), 0.0, 1.0), axis=0))

    # each tile goes to its outputs as it is formed, so no value of the
    # whole column stays live; ring slot k overwrites column k-bt, which
    # band_term(0) and rhs were the last to read
    p_ref[0, 0] = lkk.astype(p_ref.dtype)
    if bt:
        ring_write(ring_ref, k, bt, lkk, 0)
    nonfinite = not_finite(lkk)
    for e in range(1, bt + 1):
        x = tile_dot(ac_ref[0, e].astype(jnp.float32) - band_term(e), winv,
                     trans_b=True)
        p_ref[0, e] = x.astype(p_ref.dtype)
        ring_write(ring_ref, k, bt, x, e)
        nonfinite = jnp.maximum(nonfinite, not_finite(x))
    la = []
    for i in range(nat_p):
        x = tile_dot(r_ref[0, i].astype(jnp.float32) - va[i], winv,
                     trans_b=True)
        ro_ref[0, i] = x.astype(ro_ref.dtype)
        if bt:
            ring_write(ringa_ref, k, bt, x, i)
        nonfinite = jnp.maximum(nonfinite, not_finite(x))
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


def _compiler_params(b1, nat_p, t, semantics):
    """VMEM budget of one Cholesky sweep step: the panel and arrow rings
    and the Schur accumulator (scratch), the in/out column blocks, and the
    step's live values (an allowance sized for the whole column)."""
    tile = t * t * 4
    bt = max(b1 - 1, 1)
    return sweep_compiler_params(
        scratch=(bt * b1 + bt * nat_p + nat_p * nat_p) * tile,
        blocks=(2 * (b1 + nat_p) + nat_p * nat_p) * tile,
        temps=6 * (b1 + nat_p) * tile,
        semantics=semantics)


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
