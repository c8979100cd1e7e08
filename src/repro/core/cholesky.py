"""Sparse tile Cholesky factorization — the paper's core (Algorithms 1–3).

Two numerical backends over the CTSF layouts:

* :func:`factorize_tasklist` — **paper-faithful**: executes the exact static
  task list from symbolic factorization (Algorithm 1 order = Algorithm 2's
  per-thread Task Assignment Tables, with XLA's static scheduler standing in
  for the progress table).  Operates on the general CTSF, touching only
  nonzero(+fill) tiles.  Optional tree reduction (Algorithm 3) groups each
  destination tile's accumulation chain.

* :func:`factorize_window` — **TPU-native** (beyond-paper, DESIGN.md §4):
  for the regular banded-arrowhead layout, the whole band + arrow
  factorization is one sweep-level primitive
  (``kernels.ops.band_cholesky_sweep``): on the Pallas backend a *single
  fused kernel launch* walks the band with the panel ring resident in
  VMEM (``sweep="fused"``); on the jnp backend a ring-buffer ``lax.scan``
  dispatches per-panel tile ops.  Corner Schur partial sums ride the
  sweep as tree-reduction chunks.

Both produce bit-comparable factors (tests assert allclose against
`jnp.linalg.cholesky` of the dense matrix).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ring
from repro.kernels.band_cholesky import stream_bytes, sweep_path
from repro.kernels.potrf import tile_block
from repro.kernels.ref import sweep_status
from repro.kernels.ring import band_col_to_row, band_row_to_col
from repro.runtime import telemetry
from .batching import LRUCache, bucketed_batched_call
from .ctsf import BandedCTSF, TileMatrix
from .robustness import (FactorInfo, RegularizePolicy, fold_corner_status,
                         run_ladder)
from .structure import TileGrid
from .symbolic import Task, TaskType
from .options import SolverOptions, UNSET, resolve_options
from .tree_reduction import chunked_tree_sum, should_use_tree, tree_combine

__all__ = ["factorize_tasklist", "factorize_window",
           "factorize_window_batched", "CholeskyFactor"]

_HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Task-list backend (paper-faithful)
# ---------------------------------------------------------------------------

def _group_tasks_by_column(tasks: List[Task]):
    """Regroup Alg. 1's flat task list into per-column phases:
    (k, syrk_srcs, [(m, gemm_pairs, has_trsm)...]).
    """
    cols: Dict[int, dict] = {}
    for t in tasks:
        c = cols.setdefault(t.k, {"syrk": [], "panel": {}})
        if t.type == TaskType.SYRK:
            c["syrk"].append(t.n)
        elif t.type == TaskType.GEMM:
            c["panel"].setdefault(t.m, {"gemm": [], "trsm": False})
            c["panel"][t.m]["gemm"].append(t.n)
        elif t.type == TaskType.TRSM:
            c["panel"].setdefault(t.m, {"gemm": [], "trsm": False})
            c["panel"][t.m]["trsm"] = True
    return cols


class _StaticSpec:
    """Hashable wrapper for the (slot map, column-grouped task list)."""

    def __init__(self, slot, cols):
        self._key = (slot, cols)
        self.slot = dict(slot)
        self.cols = {k: {"syrk": list(s),
                         "panel": {m: {"gemm": list(g), "trsm": tr}
                                   for (m, g, tr) in panel}}
                     for (k, s, panel) in cols}

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _StaticSpec) and self._key == other._key

    def __iter__(self):  # unpack as (slot, cols)
        return iter((self.slot, self.cols))


@functools.partial(jax.jit, static_argnames=("tm_static", "impl", "tree_workers"))
def _factorize_tasklist_impl(tiles, tm_static, impl, tree_workers):
    slot, cols = tm_static
    for k in sorted(cols):
        col = cols[k]
        kk = slot[(k, k)]
        # --- SYRK accumulation chain on the diagonal tile ------------------
        srcs = [slot[(k, n)] for n in col["syrk"]]
        if srcs:
            if should_use_tree(len(srcs), tree_workers):
                gathered = tiles[jnp.asarray(srcs)]
                terms = jnp.einsum("nab,ncb->nac", gathered, gathered,
                                   precision=_HI)
                total = chunked_tree_sum(terms, tree_workers)
                tiles = tiles.at[kk].add(-total)
            else:
                for s in srcs:
                    tiles = tiles.at[kk].set(ops.syrk(tiles[kk], tiles[s], impl=impl))
        tiles = tiles.at[kk].set(ops.potrf(tiles[kk], impl=impl))
        # --- panel: GEMM chains + TRSM per below-diagonal tile -------------
        for m in sorted(col["panel"]):
            ent = col["panel"][m]
            mk = slot[(m, k)]
            pairs = [(slot[(m, n)], slot[(k, n)]) for n in ent["gemm"]]
            if pairs:
                if should_use_tree(len(pairs), tree_workers):
                    a = tiles[jnp.asarray([p[0] for p in pairs])]
                    b = tiles[jnp.asarray([p[1] for p in pairs])]
                    terms = jnp.einsum("nab,ncb->nac", a, b, precision=_HI)
                    total = chunked_tree_sum(terms, tree_workers)
                    tiles = tiles.at[mk].add(-total)
                else:
                    for sa, sb in pairs:
                        tiles = tiles.at[mk].set(
                            ops.gemm(tiles[mk], tiles[sa], tiles[sb], impl=impl))
            if ent["trsm"]:
                tiles = tiles.at[mk].set(ops.trsm(tiles[kk], tiles[mk], impl=impl))
    return tiles


def factorize_tasklist(tm: TileMatrix, impl: Optional[str] = None,
                       tree_reduction: bool = False,
                       tree_workers: int = 8) -> jnp.ndarray:
    """Run Algorithm 1/2 over the general CTSF.  Returns the L tile buffer
    (same slot map as ``tm``)."""
    cols = _group_tasks_by_column(tm.symbolic.tasks)
    # freeze python structures into hashable static arg
    frozen_cols = tuple(sorted(
        (k, tuple(v["syrk"]),
         tuple(sorted((m, tuple(e["gemm"]), e["trsm"])
                      for m, e in v["panel"].items())))
        for k, v in cols.items()))
    slot = tuple(sorted((k, v) for k, v in tm.slot.items()))
    static = _StaticSpec(slot, frozen_cols)
    workers = tree_workers if tree_reduction else 0
    return _factorize_tasklist_impl(tm.tiles, static, impl, workers)


# ---------------------------------------------------------------------------
# Window backend (TPU-native)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CholeskyFactor:
    """Factor L in banded-arrowhead CTSF layout.

    ``source_grid`` is set when the factor lives on a *canonical* grid
    (``core/gridpolicy.py``) but represents a problem measured on
    ``source_grid``: the CTSF arrays then hold ``blockdiag(I_prefix, L)``
    and the policy-aware solve/selinv entry points embed right-hand sides
    in and restrict results back automatically.  :meth:`restrict` strips
    the embedding when the raw factor is wanted.

    ``info`` is attached when the factorization ran under a
    ``regularize=`` policy: per-element numerical status (OK / RECOVERED
    with diagonal jitter / FAILED), attempts, applied jitter and minimum
    pivot — see :class:`~repro.core.robustness.FactorInfo`.  Serving
    callers should consult ``info`` instead of expecting exceptions; a
    FAILED element's factor is numerically unusable but never poisons its
    batch siblings.
    """
    ctsf: BandedCTSF
    source_grid: Optional[TileGrid] = None
    info: Optional[FactorInfo] = None

    def restrict(self) -> "CholeskyFactor":
        """Slice a canonical-grid factor back onto its source grid (no-op
        for factors that were never embedded)."""
        if self.source_grid is None:
            return self
        from .gridpolicy import restrict_factor
        return restrict_factor(self, self.source_grid)

    def logdet(self) -> jnp.ndarray:
        """log det A = 2 * sum log diag(L); padded diagonal entries are 1
        (including the identity prefix of a canonical-grid embedding, so
        embedded factors report the source problem's log-determinant).
        Leading batch axes (``factorize_window_batched`` /
        ``concurrent_factorize`` factors) broadcast: a batched factor
        returns a ``(batch,)`` vector."""
        g = self.ctsf.grid
        d0 = jnp.take(self.ctsf.Dr, 0, axis=-3)          # (..., ndt, t, t)
        db = jnp.diagonal(d0, axis1=-2, axis2=-1)        # (..., ndt, t)
        total = jnp.sum(jnp.log(jnp.abs(db)), axis=(-2, -1))
        if g.n_arrow_tiles > 0:
            ct = jnp.diagonal(self.ctsf.C, axis1=-4, axis2=-3)  # (..., t, t, nat)
            dc = jnp.diagonal(ct, axis1=-3, axis2=-2)           # (..., t, nat)
            total = total + jnp.sum(jnp.log(jnp.abs(dc)), axis=(-2, -1))
        return 2.0 * total


def _corner_dense_cholesky(c: jnp.ndarray) -> jnp.ndarray:
    """Blocked dense Cholesky of the (nat, nat, t, t) corner.

    Left-looking over columns as a single ``lax.fori_loop``: each step does
    one masked batched SYRK/GEMM contraction over the finalized columns plus
    a batched TRSM of the whole sub-diagonal panel.  Trace/compile size is
    O(nat) instead of the O(nat²) of the previous Python-unrolled tile
    loops — the difference between seconds and minutes of XLA compile for
    thick arrows — while tiny corners lower to the same handful of kernels.
    Its tile ops are always the jnp ones: the corner is small and dense,
    so XLA's own Cholesky and triangular solve serve it, and the whole
    factorization stays one Pallas launch on the fused path.
    """
    nat, t = c.shape[0], c.shape[-1]
    rows = jnp.arange(nat)

    def col_step(k, c):
        done = (rows < k)[:, None, None]                # finalized columns j<k
        row_k = jax.lax.dynamic_slice(c, (k, 0, 0, 0), (1, nat, t, t))[0]
        rk = jnp.where(done, row_k, 0.0)                # L[k, :k], zero-padded
        ckk = jax.lax.dynamic_slice(c, (k, k, 0, 0), (1, 1, t, t))[0, 0]
        syrk_acc = jnp.einsum("jab,jcb->ac", rk, rk, precision=_HI)
        lkk = ops.potrf(ckk - syrk_acc, impl="ref")
        col_k = jax.lax.dynamic_slice(c, (0, k, 0, 0), (nat, 1, t, t))[:, 0]
        # masked rk zeroes the j>=k terms, so unfactorized columns of c
        # contribute nothing to the GEMM accumulation
        gemm_acc = jnp.einsum("mjab,jcb->mac", c, rk, precision=_HI)
        panel = ops.trsm(lkk, col_k - gemm_acc, impl="ref")
        new_col = jnp.where((rows > k)[:, None, None], panel,
                            jnp.where((rows == k)[:, None, None],
                                      lkk[None], col_k))
        return jax.lax.dynamic_update_slice(c, new_col[:, None], (0, k, 0, 0))

    return jax.lax.fori_loop(0, nat, col_step, c)


def _band_arrow_sweep_ring(Dr, R, grid, impl, tree_chunks: int = 1):
    """Band + arrow factorization through the sweep-level primitive
    (``kernels.ops.band_cholesky_sweep``) — the (Dr, R) -> (Dr_L, R_L,
    schur) entry point ``core/distributed.py`` vmaps over shards.  The
    per-chunk corner-Schur partial sums come straight from the sweep (the
    fused kernel accumulates them on the fly), so callers must not
    re-contract R_L.  ``impl="pallas"`` = one fused kernel launch;
    ``"ref"`` = the ring-buffer ``lax.scan``.  The sweep's breakdown
    status word is dropped here — the distributed path does its own
    health checks at the shard level."""
    panels, R_out, schur, _status = ops.band_cholesky_sweep(
        band_row_to_col(Dr), R, nchunks=tree_chunks, impl=impl)
    return band_col_to_row(panels), R_out, schur


def _band_arrow_sweep(Dr, R, grid, impl, start_tile=0):
    """The sequential panel sweep (thin critical path): factor the band and
    arrow rows, leaving the corner untouched.  Returns (Dr_L, R_L).

    ``start_tile`` skips the first rows of the sweep, leaving their input
    values in place — correct exactly when they are the identity-embedding
    prefix of a canonical grid (whose factor equals the input)."""
    t, ndt, nat, bt = grid.t, grid.n_diag_tiles, grid.n_arrow_tiles, grid.band_tiles
    b1 = bt + 1

    # pad: bt trailing zero rows on Dr (window slack), bt leading on R
    Drp = jnp.pad(Dr, ((0, bt), (0, 0), (0, 0), (0, 0)))
    Rp = jnp.pad(R, ((bt, 0), (0, 0), (0, 0), (0, 0))) if nat else R

    erange = jnp.arange(b1)

    def panel_step(k, carry):
        Drp, Rp = carry
        w = jax.lax.dynamic_slice(Drp, (k, 0, 0, 0), (b1, b1, t, t))
        u = ops.band_update(w, impl=impl)                       # (b1, t, t)
        lkk = ops.potrf(w[0, 0] - u[0], impl=impl)
        # sub-diagonal panel tiles A[k+e, k] live on the window diagonal
        amk = w[erange[1:], erange[1:]] - u[1:]
        lmk = ops.trsm(lkk, amk, impl=impl)
        vals = jnp.concatenate([lkk[None], lmk], axis=0)
        Drp = Drp.at[k + erange, erange].set(vals)
        if nat:
            rwin = jax.lax.dynamic_slice(Rp, (k, 0, 0, 0), (bt, nat, t, t)) \
                if bt else jnp.zeros((0, nat, t, t), Rp.dtype)
            # V[i] = sum_{j=1..bt} R[k-j, i] @ L[k, k-j]^T ; rwin[bt-j] = R[k-j]
            w0rev = jnp.flip(w[0, 1:], axis=0) if bt else jnp.zeros((0, t, t), w.dtype)
            v = jnp.einsum("jiab,jcb->iac", rwin, w0rev, precision=_HI) \
                if bt else 0.0
            lak = ops.trsm(lkk, Rp[k + bt] - v, impl=impl)
            Rp = jax.lax.dynamic_update_slice(Rp, lak[None], (k + bt, 0, 0, 0))
        return (Drp, Rp)

    Drp, Rp = jax.lax.fori_loop(start_tile, ndt, panel_step, (Drp, Rp))
    Dr_out = Drp[:ndt]
    R_out = Rp[bt:] if nat else R
    return Dr_out, R_out


def _corner_schur(R_L: jnp.ndarray, tree_chunks: int) -> jnp.ndarray:
    """sum_n R[n] R[n]^T over all band columns — the paper's flagship
    accumulation chain, computed via Alg. 3's chunked tree."""
    ndt = R_L.shape[0]
    terms = jnp.einsum("niab,njcb->nijac", R_L, R_L, precision=_HI)
    chunks = tree_chunks if tree_chunks else 1
    if should_use_tree(ndt, chunks):
        return chunked_tree_sum(terms, chunks)
    return terms.sum(axis=0)


def _resolve_sweep(grid, impl, sweep, plan=None) -> str:
    """The sweep :func:`_factorize_window_impl` runs for ``sweep``: one of
    ``"fused"``, ``"stream"``, ``"ring"``, ``"window"`` or
    ``"partitioned"`` (``"stream"`` only by the ``"auto"`` choice).
    Refuses contradictory or impossible requests."""
    if sweep not in ("auto", "fused", "ring", "window", "partitioned"):
        raise ValueError(f"unknown sweep {sweep!r} (want 'auto', 'fused', "
                         "'ring', 'window' or 'partitioned')")
    # "ring" is the jnp scan and "fused" the Pallas kernel by definition —
    # an explicit impl pointing the other way would silently run a
    # different backend than asked, so refuse the contradiction.
    if (sweep == "ring" and impl == "pallas") or \
            (sweep == "fused" and impl in ("ref", "unrolled")):
        raise ValueError(
            f"sweep={sweep!r} contradicts impl={impl!r}: the ring sweep is "
            "the jnp reference scan and the fused sweep is the Pallas "
            "kernel; use sweep='auto' to dispatch by impl")
    if sweep == "partitioned" and plan is None:
        raise ValueError(
            "sweep='partitioned' needs a partition plan: pass "
            "options=SolverOptions(partition_plan=...) (see "
            "core.ordering.detect_partition_plan)")
    if plan is not None and plan.n_tiles != grid.n_diag_tiles:
        raise ValueError(
            f"partition plan covers {plan.n_tiles} diagonal tiles but the "
            f"grid has {grid.n_diag_tiles}; rebuild the plan for this grid "
            "(PartitionPlan.shifted embeds a plan into a canonical grid)")
    path = sweep_path(grid.t, grid.band_tiles, grid.n_arrow_tiles)
    if sweep == "fused" and path != "fused":
        raise ValueError(
            f"sweep='fused' cannot run at t={grid.t}, band_tiles="
            f"{grid.band_tiles}, arrow tiles {grid.n_arrow_tiles}: its VMEM "
            f"ring asks for more than the {ring.VMEM_CAP_BYTES / 2**20:g} "
            "MiB cap (kernels/ring.py); sweep='auto' streams such a band "
            "from HBM")
    if sweep != "auto":
        return sweep
    if plan is not None and plan.n_partitions > 1:
        return "partitioned"
    if (impl or ops.default_impl()) != "pallas":
        return "ring"
    return path


def _record_sweep(span, grid, opts: SolverOptions, plan):
    """Counts a dispatch by the sweep it takes (``cholesky.sweep
    {path=}``) and tags the entry point's span with it; a Pallas sweep's
    span also carries ``tile_block``, the row block its column finish
    factors and inverts the diagonal tile by (``potrf.tile_block``: t where
    unblocked), and a streamed sweep's ``stream_bytes``, the HBM bytes its
    DMAs move for one matrix."""
    if not telemetry.enabled():
        return
    path = _resolve_sweep(grid, opts.impl, opts.sweep, plan)
    telemetry.inc("cholesky.sweep", path=path)
    span.tag(sweep=path)
    if path in ("fused", "stream", "partitioned"):
        span.tag(tile_block=tile_block(grid.t))
    if path == "stream":
        span.tag(stream_bytes=stream_bytes(grid.n_diag_tiles,
                                           grid.band_tiles,
                                           grid.n_arrow_tiles, grid.t))


@functools.partial(jax.jit,
                   static_argnames=("grid", "impl", "tree_chunks", "sweep",
                                    "plan"))
def _factorize_window_impl(Dr, R, C, grid, impl, tree_chunks, sweep="auto",
                           start_tile=0, plan=None):
    """Window factorization with sweep-mode dispatch
    (:func:`_resolve_sweep`):

    * ``"auto"`` (default) — ``"partitioned"`` when ``plan`` (a
      :class:`~repro.core.ordering.PartitionPlan`) has more than one
      partition; else on the Pallas backend (native TPU or an explicit
      ``impl="pallas"``) the fused sweep while its VMEM ring fits the
      chip and the streamed sweep beyond (a choice made from the grid's
      tile size, band and arrow tiles alone,
      ``kernels.band_cholesky.sweep_path``); else ``"ring"``: every caller
      (:func:`factorize_window`, :func:`factorize_window_batched`,
      ``concurrent_factorize``) rides a fused kernel wherever Pallas is the
      kernel backend.
    * ``"fused"`` — force the single-launch Pallas sweep with the VMEM ring
      (``kernels/band_cholesky.py``); a ``ValueError`` where its ring
      cannot fit.
    * ``"ring"`` — force the ring-buffer ``lax.scan`` reference.
    * ``"window"`` — the legacy dynamic-slice window sweep
      (``kernels.band_update`` per panel), kept for comparison.
    * ``"partitioned"`` — the multi-partition fused sweep
      (``kernels.ops.band_cholesky_partitioned_sweep``): one 2D-grid
      launch over all of ``plan``'s independent band partitions, their
      per-partition corner-Schur leaves tree-combined before the shared
      corner factorization.  Requires a ``plan``; a trivial
      single-partition plan stays on the fused/ring path so its factor is
      bit-identical to a plan-less call.

    The fused, streamed and ring paths read the corner Schur complement
    from the sweep's per-chunk partial sums (accumulated on the fly in the
    kernels) instead of re-contracting R_out from HBM.

    ``start_tile`` declares the first band columns an identity-embedding
    prefix (``core/gridpolicy.py``); callers omit it on the plain path so
    the argument stays a trace-time constant 0 (keeping the static loop
    bounds), and pass a *traced* scalar on the canonical-grid path so
    distinct pad depths share one compilation per canonical grid.

    Returns ``(Dr_L, R_L, C_L, status)`` — ``status`` the (3,) float32
    breakdown word ``[min_pivot, nonfinite, first_bad]`` covering band
    *and* corner (a corner breakdown reports ``first_bad = ndt``).  It is
    carried in-graph with no host sync; the jitter ladder
    (``core/robustness.py``) is the consumer."""
    nat = grid.n_arrow_tiles
    mode = _resolve_sweep(grid, impl, sweep, plan)
    if mode == "partitioned":
        panels, R_out, schur, status = ops.band_cholesky_partitioned_sweep(
            band_row_to_col(Dr), R, plan.boundaries, start_tile=start_tile,
            impl=impl)
        Dr_out = band_col_to_row(panels)
        if nat:
            # one Schur leaf per partition: combine them with the Alg. 3
            # binary tree before the shared separator/corner factorization
            C_out = _corner_dense_cholesky(C - tree_combine(schur))
        else:
            C_out = C
        return Dr_out, R_out, C_out, fold_corner_status(
            status, C_out, grid.n_diag_tiles, nat)
    if mode == "window":
        Dr_out, R_out = _band_arrow_sweep(Dr, R, grid, impl, start_tile)
        # legacy sweep predates the in-sweep status carry: fold the same
        # word from the emitted factor (row layout keeps diag at [:, 0],
        # which is all ref.sweep_status reads)
        status = sweep_status(Dr_out, R_out)
        if nat:
            C_out = _corner_dense_cholesky(
                C - _corner_schur(R_out, tree_chunks))
        else:
            C_out = C
        return Dr_out, R_out, C_out, fold_corner_status(
            status, C_out, grid.n_diag_tiles, nat)

    # the kernel layer runs the streamed sweep where the ring cannot fit
    sweep_impl = "pallas" if mode in ("fused", "stream") else "ref"
    nchunks = max(1, min(tree_chunks or 1, grid.n_diag_tiles or 1))
    panels, R_out, schur, status = ops.band_cholesky_sweep(
        band_row_to_col(Dr), R, nchunks=nchunks, start_tile=start_tile,
        impl=sweep_impl)
    Dr_out = band_col_to_row(panels)
    if nat:
        # the chunks are the tree-reduction leaves; summing them is the
        # root combine of the paper's Alg. 3 chain
        C_out = _corner_dense_cholesky(C - jnp.sum(schur, axis=0))
    else:
        C_out = C
    return Dr_out, R_out, C_out, fold_corner_status(
        status, C_out, grid.n_diag_tiles, nat)


def _embed_matrix(m: BandedCTSF, policy):
    """Canonical-grid embedding of a matrix (or matrix batch) for the
    factorization entry points — the matrix-side mirror of
    ``solve._resolve_embedding``.  Returns ``(embedded, source_grid,
    start_tile)`` with ``start_tile`` the *traced* identity-prefix depth,
    so every pad depth shares the canonical grid's compilation."""
    from .gridpolicy import embed_ctsf
    cgrid = policy.canonicalize(m.grid)
    start = jnp.asarray(cgrid.n_diag_tiles - m.grid.n_diag_tiles, jnp.int32)
    return embed_ctsf(m, cgrid), m.grid, start


def factorize_window(m: BandedCTSF, impl=UNSET,
                     tree_chunks: int = 8,
                     sweep=UNSET, policy=UNSET,
                     regularize=UNSET,
                     options: Optional[SolverOptions] = None) -> CholeskyFactor:
    """Banded-arrowhead factorization (window backend).

    ``options`` (a :class:`~repro.core.options.SolverOptions`) carries the
    solver knobs — backend, sweep mode, bucketing policy, regularization
    and the partition plan; the bare ``impl=``/``sweep=``/``policy=``/
    ``regularize=`` kwargs are deprecated aliases for the matching fields
    (legacy wins when both are given, with a ``DeprecationWarning``).

    With ``options.impl="pallas"`` (or running natively on TPU) the whole
    band + arrow block factorizes in **one fused Pallas launch**
    (``kernels.ops.band_cholesky_sweep``); ``options.sweep`` overrides the
    dispatch (see :func:`_factorize_window_impl`).  An
    ``options.partition_plan`` with more than one partition upgrades the
    launch to the 2D partition-parallel sweep — critical path
    O(max partition tiles) instead of O(ndt).

    With a :class:`~repro.core.gridpolicy.GridBucketPolicy` the matrix is
    first embedded into its canonical grid (identity-diagonal padding) and
    the sweep skips the prefix via its traced ``start_tile`` — mixed-size
    traffic then compiles once per canonical rung instead of once per
    grid.  The returned factor lives on the canonical grid with
    ``source_grid`` set; the solve/selinv entry points consume it
    transparently, or :meth:`CholeskyFactor.restrict` strips the
    embedding.

    ``regularize`` opts into numerical fault tolerance: ``True`` (default
    :class:`~repro.core.robustness.RegularizePolicy`) or a policy runs the
    escalating-jitter retry ladder on breakdown and attaches a
    :class:`~repro.core.robustness.FactorInfo` to the returned factor
    instead of ever raising; an SPD input factorizes on the first attempt
    and its factor is bit-identical to the unregularized call."""
    opts = resolve_options(options, _where="factorize_window", impl=impl,
                           sweep=sweep, policy=policy, regularize=regularize)
    with telemetry.span("factorize.window",
                        grid=telemetry.rung_tag(m.grid)) as sp:
        pol = RegularizePolicy.resolve(opts.regularize)
        plan = opts.partition_plan
        source = None
        if opts.policy is not None:
            src_ndt = m.grid.n_diag_tiles
            m, source, start = _embed_matrix(m, opts.policy)
            sp.tag(rung=telemetry.rung_tag(m.grid))
            if plan is not None:
                # the canonical-grid identity prefix joins partition 0;
                # the pad depth is a Python int, so each (rung, pad) pair
                # is one compilation — same as the plan-less policy path
                plan = plan.shifted(m.grid.n_diag_tiles - src_ndt)
            call = lambda dr, r, c: _factorize_window_impl(
                dr, r, c, m.grid, opts.impl, tree_chunks, opts.sweep, start,
                plan=plan)
        else:
            call = lambda dr, r, c: _factorize_window_impl(
                dr, r, c, m.grid, opts.impl, tree_chunks, opts.sweep,
                plan=plan)
        _record_sweep(sp, m.grid, opts, plan)
        if pol is None:
            Dr, R, C, _status = call(m.Dr, m.R, m.C)
            info = None
        else:
            Dr, R, C, info = run_ladder(m.Dr, m.R, m.C, m.grid, call, pol)
        return CholeskyFactor(BandedCTSF(m.grid, Dr, R, C),
                              source_grid=source, info=info)


# ---------------------------------------------------------------------------
# Batched window factorization (INLA θ-sweep serving path)
# ---------------------------------------------------------------------------

# bounded so long-running serving processes cycling through many distinct
# grids cannot grow the traced-callable map without limit; an evicted key
# pays retrace + recompile on re-entry (core/batching.py)
_BATCHED_WINDOW_CACHE = LRUCache(maxsize=64, name="batched_window")


def _batched_window_fn(grid, opts: SolverOptions, tree_chunks,
                       use_start=False):
    """One vmapped+jitted window factorization per (grid,
    ``opts.compile_key()``, chunks) — cached on the Python side so
    repeated θ-sweeps reuse the same traced function object (and
    therefore XLA's compile cache).  Keying on the options object's
    compile-relevant subset means option-equal calls share an entry no
    matter which construction path (legacy kwargs, facade, replace())
    produced them.

    ``use_start=True`` (the canonical-grid path) adds a *traced*
    ``start_tile`` argument broadcast across the batch, so every source
    grid embedding into ``grid`` — whatever its pad depth — shares this
    one cache entry; the plain path keeps its static-zero trace."""
    key = (grid, opts.compile_key(), tree_chunks, use_start)
    impl, sweep, plan = opts.impl, opts.sweep, opts.partition_plan

    def batched_window(dr, r, c, *start):
        # the entry point's name rides the op metadata of everything
        # traced here, the XLA work around the kernel included
        with jax.named_scope("factorize.window_batched"):
            return _factorize_window_impl(dr, r, c, grid, impl, tree_chunks,
                                          sweep, *start, plan=plan)

    def build():
        if use_start:
            return jax.jit(jax.vmap(batched_window, in_axes=(0, 0, 0, None)))
        return jax.jit(jax.vmap(batched_window))

    return _BATCHED_WINDOW_CACHE.get_or_create(key, build)


def factorize_window_batched(batch, impl=UNSET,
                             tree_chunks: int = 8,
                             bucket: bool = True,
                             sweep=UNSET,
                             policy=UNSET,
                             regularize=UNSET,
                             start_tile=None,
                             options: Optional[SolverOptions] = None
                             ) -> CholeskyFactor:
    """Factorize a batch of same-grid matrices in one vmapped dispatch.

    ``options`` (a :class:`~repro.core.options.SolverOptions`) is the
    preferred way to pass the solver knobs; the bare ``impl=``/``sweep=``/
    ``policy=``/``regularize=`` kwargs are deprecated aliases (legacy
    wins, with a ``DeprecationWarning``).  ``tree_chunks``, ``bucket`` and
    ``start_tile`` are per-call arguments, not options.

    ``batch`` is either a list of :class:`BandedCTSF` or one whose arrays
    carry a leading batch axis (cf. ``concurrent.stack_ctsf``).  This is the
    INLA θ-sweep primitive: every hyperparameter candidate's arrowhead
    matrix rides the same ring sweep + corner Schur, so a sweep of B
    candidates costs one kernel launch sequence instead of B — and on the
    Pallas backend the whole band+arrow factorization of every candidate
    is one fused launch (``sweep`` as in :func:`factorize_window`).

    With ``bucket=True`` the batch is padded (by repeating the last matrix)
    to the next power of two before dispatch and the padding results are
    dropped — bounding XLA compiles per grid at log2(max batch) instead of
    one per distinct sweep size.  The vmapped callable itself is cached per
    (grid, impl, tree_chunks, sweep), so factorizing a new batch of a known
    shape costs zero retracing.

    ``policy`` (a :class:`~repro.core.gridpolicy.GridBucketPolicy`) extends
    the bucketing across *grids*: the batch is embedded into its canonical
    grid, the cache keys on that canonical grid, and the sweep skips the
    identity prefix via a traced ``start_tile`` — so mixed-size serving
    traffic compiles O(#canonical rungs) sweeps instead of one per distinct
    grid.  The returned factor carries ``source_grid`` (see
    :func:`factorize_window`).

    ``regularize`` (bool or :class:`~repro.core.robustness.RegularizePolicy`)
    runs the escalating-jitter ladder *per batch element*: retries
    refactorize the whole (bucketed) batch through the same compiled
    callable with only the failed elements' diagonals jittered, healthy
    elements keep their first-attempt factors bit-for-bit, and the
    returned ``factor.info`` carries ``(B,)`` status/attempts/tau vectors
    — one poisoned θ-candidate degrades to a flagged element instead of
    sinking the sweep.

    ``start_tile`` is for callers that did the canonical-grid embedding
    *themselves* (``gridpolicy.assemble_rung_batch`` — the rung server
    stacks mixed source grids before dispatch): it threads the shared
    identity-prefix depth through the sweep as a traced scalar, reusing
    the same ``use_start`` cache entry the ``policy`` path compiles,
    without re-embedding.  Mutually exclusive with ``policy`` (which
    computes its own start); the returned factor keeps ``source_grid``
    None — restriction stays with the caller who owns the embedding.
    """
    opts = resolve_options(options, _where="factorize_window_batched",
                           impl=impl, sweep=sweep, policy=policy,
                           regularize=regularize)
    if start_tile is not None and opts.policy is not None:
        raise ValueError(
            "start_tile= is for pre-embedded batches and the bucketing "
            "policy embeds itself; pass one or the other")
    if isinstance(batch, (list, tuple)):
        grid = batch[0].grid
        for m in batch:
            if m.grid != grid:
                raise ValueError(
                    "batched factorization needs equal structure; use "
                    "concurrent.stack_ctsf(policy=...) to embed mixed "
                    "grids onto a shared canonical rung first")
        Dr = jnp.stack([m.Dr for m in batch])
        R = jnp.stack([m.R for m in batch])
        C = jnp.stack([m.C for m in batch])
    else:
        grid = batch.grid
        Dr, R, C = batch.Dr, batch.R, batch.C
        if Dr.ndim != 5:
            raise ValueError(
                f"batched CTSF needs a leading batch axis, got Dr.ndim="
                f"{Dr.ndim}")
    with telemetry.span("factorize.window_batched", b=Dr.shape[0],
                        grid=telemetry.rung_tag(grid)) as sp:
        source = None
        if opts.policy is not None:
            src_ndt = grid.n_diag_tiles
            emb, source, start = _embed_matrix(BandedCTSF(grid, Dr, R, C),
                                               opts.policy)
            Dr, R, C, grid = emb.Dr, emb.R, emb.C, emb.grid
            sp.tag(rung=telemetry.rung_tag(grid))
            if opts.partition_plan is not None:
                opts = opts.replace(partition_plan=opts.partition_plan
                                    .shifted(grid.n_diag_tiles - src_ndt))
            fn = _batched_window_fn(grid, opts, tree_chunks, use_start=True)
            call = lambda dr, r, c: fn(dr, r, c, start)
        elif start_tile is not None:
            start = jnp.asarray(start_tile, jnp.int32)
            fn = _batched_window_fn(grid, opts, tree_chunks, use_start=True)
            call = lambda dr, r, c: fn(dr, r, c, start)
        else:
            call = _batched_window_fn(grid, opts, tree_chunks)
        _record_sweep(sp, grid, opts, opts.partition_plan)
        pol = RegularizePolicy.resolve(opts.regularize)
        if pol is None:
            with telemetry.span("factorize.enqueue"):
                dr, r, c, _status = bucketed_batched_call(call, (Dr, R, C),
                                                          bucket)
            info = None
        else:
            # ladder inside the bucketed call: the pow2 padding elements
            # (copies of the last matrix) ride the retries and are stripped
            # with the other outputs; FactorInfo arrays flatten through the
            # stripper
            kept = []

            def ladder_call(dr_, r_, c_):
                d2, r2, c2, inf = run_ladder(dr_, r_, c_, grid, call, pol)
                kept.append(inf.matrix is not None)
                return (d2, r2, c2, inf.status, inf.attempts, inf.tau,
                        inf.min_pivot, inf.first_bad_tile)

            with telemetry.span("factorize.enqueue"):
                dr, r, c, st, at, ta, mp, fb = bucketed_batched_call(
                    ladder_call, (Dr, R, C), bucket)
            # re-attach the *unpadded* original batch for the refinement path
            matrix = BandedCTSF(grid, Dr, R, C) if kept[-1] else None
            info = FactorInfo(status=st, attempts=at, tau=ta, min_pivot=mp,
                              first_bad_tile=fb, matrix=matrix)
        return CholeskyFactor(BandedCTSF(grid, dr, r, c), source_grid=source,
                              info=info)
