"""Triangular solves, log-determinant and GMRF sampling from sTiles factors.

INLA (the paper's driving application) needs, per factorization: solves
``A x = b`` (posterior means), ``log det A`` (Laplace approximations) and
samples ``L^{-T} z`` (GMRF realizations).  All operate directly on the
banded-arrowhead CTSF factor without densification.

Batched serving path
--------------------
Every sweep here is a *multi-RHS panel* sweep: right-hand sides are shaped
``(padded_n, k)`` and the band step applies each ``(t, t)`` factor tile to a
``(t, k)`` panel — one matmul instead of k matvecs (cf. Ruipeng Li's
observation that sparse triangular solves only escape the latency/bandwidth
bound when RHS are blocked into panels).  The single-RHS API
(:func:`solve`, :func:`forward_solve`, ...) is the k=1 specialization of the
same code path; :func:`solve_many` exposes the panel form, and
:func:`marginal_variances` / :func:`sample_gmrf` ride one blocked sweep for
all selected indices / samples.

With ``options.impl="pallas"`` each band sweep is one *fused* kernel launch
(``kernels/band_solve.py``): a ring of the most recent ``band_tiles``
solved panels stays resident in VMEM across tile rows, removing the
per-tile HBM round-trips of the ``fori_loop``-of-``solve_panel`` reference
path (which remains the jnp oracle and the CPU default).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.potrf import tile_block
from repro.runtime import telemetry
from .batching import LRUCache, bucketed_batched_call
from .cholesky import CholeskyFactor
from .ctsf import BandedCTSF
from .options import SolverOptions, check_options

__all__ = ["forward_solve", "backward_solve", "solve", "logdet",
           "forward_solve_many", "backward_solve_many", "solve_many",
           "solve_many_batched", "sample_gmrf", "sample_gmrf_many",
           "marginal_variances"]

_HI = jax.lax.Precision.HIGHEST


def _split_rhs(g, b: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Split an (padded_n, k) RHS panel into band (ndt, t, k) and arrow
    (nat, t, k) tile panels."""
    t, ndt, nat = g.t, g.n_diag_tiles, g.n_arrow_tiles
    # a real validation (bare asserts vanish under `python -O`)
    if b.ndim != 2 or b.shape[0] != g.padded_n:
        raise ValueError(
            f"rhs panel must be (padded_n={g.padded_n}, k), got {b.shape}")
    k = b.shape[1]
    bd = b[: ndt * t].reshape(ndt, t, k)
    ba = b[ndt * t:].reshape(nat, t, k) if nat else jnp.zeros((0, t, k), b.dtype)
    return bd, ba


def _tag_tile_block(span, grid, impl):
    """Tags a solve span, where the Pallas band sweeps run, with
    ``tile_block``: the row block their diagonal tiles are solved by
    (``potrf.tile_block``: 32 where the blocked inverse runs, t where the
    t-step substitution does)."""
    if (impl or ops.default_impl()) == "pallas":
        span.tag(tile_block=tile_block(grid.t))


@functools.partial(jax.jit, static_argnames=("grid", "impl"))
def _forward_impl(Dr, R, C, bd, ba, grid, impl=None, start_tile=0):
    """Solve L Y = B for an RHS panel: bd (ndt, t, k), ba (nat, t, k).

    The band part is one :func:`repro.kernels.ops.band_forward_sweep` —
    with ``impl="pallas"`` the whole sweep (and the arrow-RHS accumulation)
    is a single fused kernel launch; otherwise it is the per-tile
    ``fori_loop`` of ``solve_panel`` reference.

    ``start_tile`` exploits RHS sparsity: when every column of the panel is
    zero above band tile ``start_tile`` (e.g. the unit-vector panels of
    selected marginals), the band sweep may begin there — Y is provably zero
    above the first nonzero tile, which both backends encode by leaving
    those rows zero.  It is a *traced* scalar, so varying selections never
    retrace/recompile the sweep.
    """
    t, ndt, nat, bt = grid.t, grid.n_diag_tiles, grid.n_arrow_tiles, grid.band_tiles
    k = bd.shape[-1]
    if ndt:
        yd, acc_a = ops.band_forward_sweep(Dr, R, bd, start_tile=start_tile,
                                           impl=impl)
    else:
        yd = jnp.zeros((0, t, k), bd.dtype)
        acc_a = jnp.zeros((nat, t, k), bd.dtype)

    if nat:
        # arrow rows: Y_a = Lc^{-1} (B_a - sum_n R[n] Y_n), block forward
        rhs0 = ba - acc_a
        iota = jnp.arange(nat)

        def corner_step(i, ya):
            # rhs_i = rhs0_i - sum_{j<i} C[i,j] Y_j  (masked full-row matmul)
            crow = jax.lax.dynamic_slice(C, (i, 0, 0, 0), (1, nat, t, t))[0]
            crow = jnp.where((iota < i)[:, None, None], crow, 0.0)
            contrib = jnp.einsum("jab,jbk->ak", crow, ya, precision=_HI)
            cii = jax.lax.dynamic_slice(C, (i, i, 0, 0), (1, 1, t, t))[0, 0]
            rhs = jax.lax.dynamic_slice(rhs0, (i, 0, 0), (1, t, k))[0] - contrib
            yi = ops.solve_panel(cii, rhs, impl=impl)
            return jax.lax.dynamic_update_slice(ya, yi[None], (i, 0, 0))

        ya = jax.lax.fori_loop(0, nat, corner_step,
                               jnp.zeros((nat, t, k), bd.dtype))
    else:
        ya = ba
    return yd, ya


@functools.partial(jax.jit, static_argnames=("grid", "impl"))
def _backward_impl(Dr, R, C, yd, ya, grid, impl=None, start_tile=0):
    """Solve L^T X = Y for an RHS panel: yd (ndt, t, k), ya (nat, t, k).

    Corner first (the arrow panel seeds the band rows), then the band part
    runs as one :func:`repro.kernels.ops.band_backward_sweep` — fused into
    a single kernel launch under ``impl="pallas"``.

    ``start_tile`` mirrors the forward sweep's traced fast path: rows
    below it (the identity prefix of a canonical-grid embedding,
    ``core/gridpolicy.py``) are decoupled with zero RHS, so the reverse
    sweep stops before reaching them and X stays zero there."""
    t, ndt, nat, bt = grid.t, grid.n_diag_tiles, grid.n_arrow_tiles, grid.band_tiles
    k = yd.shape[-1]

    if nat:
        iota = jnp.arange(nat)

        def corner_step(s, xa):
            i = nat - 1 - s
            # rhs_i = Y_i - sum_{j>i} C[j,i]^T X_j  (masked full-column matmul)
            ccol = jax.lax.dynamic_slice(C, (0, i, 0, 0), (nat, 1, t, t))[:, 0]
            ccol = jnp.where((iota > i)[:, None, None], ccol, 0.0)
            contrib = jnp.einsum("jba,jbk->ak", ccol, xa, precision=_HI)
            cii = jax.lax.dynamic_slice(C, (i, i, 0, 0), (1, 1, t, t))[0, 0]
            rhs = jax.lax.dynamic_slice(ya, (i, 0, 0), (1, t, k))[0] - contrib
            xi = ops.solve_panel(cii, rhs, trans=True, impl=impl)
            return jax.lax.dynamic_update_slice(xa, xi[None], (i, 0, 0))

        xa = jax.lax.fori_loop(0, nat, corner_step,
                               jnp.zeros((nat, t, k), yd.dtype))
    else:
        xa = ya

    # band rows, reverse sweep:
    # X_m = Lmm^{-T}(Y_m - sum_{j=1..bt} L[m+j,m]^T X_{m+j} - sum_i R[m,i]^T Xa_i)
    if ndt:
        xd = ops.band_backward_sweep(Dr, R, yd, xa, start_tile, impl=impl)
    else:
        xd = jnp.zeros((0, t, k), yd.dtype)
    return xd, xa


def _solve_panels(Dr, R, C, bd, ba, grid, impl=None, start_tile=None):
    """Full ``A X = B`` on split panels: forward then backward sweep.  The
    single source of truth shared by :func:`solve_many` and the vmapped
    ``concurrent_solve`` — layout changes (e.g. a fused Pallas band-solve)
    land here once.  ``start_tile=None`` keeps the static-zero traces;
    a (traced) value threads the canonical-grid prefix skip through both
    sweeps."""
    if start_tile is None:
        yd, ya = _forward_impl(Dr, R, C, bd, ba, grid, impl)
        return _backward_impl(Dr, R, C, yd, ya, grid, impl)
    yd, ya = _forward_impl(Dr, R, C, bd, ba, grid, impl, start_tile)
    return _backward_impl(Dr, R, C, yd, ya, grid, impl, start_tile)


def _resolve_embedding(factor: CholeskyFactor, policy=None):
    """Resolve the canonical-grid embedding of a factor for the solve-side
    entry points.

    Returns ``(ctsf, source_grid, start_tile)``: for a plain factor with no
    policy that is ``(factor.ctsf, None, None)`` (static-zero sweeps); for
    a factor already living on a canonical grid (``source_grid`` set by the
    policy-aware factorizations) the embedding is reused as-is; for a plain
    factor with a ``policy`` the factor itself is embedded on the fly — the
    Cholesky factor of ``blockdiag(I, A)`` is ``blockdiag(I, L)``, so
    identity-padding a *factor* is exact.  Note the on-the-fly path pads
    fresh arrays *per call*: a serving loop reusing one factor should pass
    the policy at factorization time instead, so the factor is embedded
    once and every solve reuses it."""
    ctsf, src = factor.ctsf, factor.source_grid
    if src is None and policy is not None:
        from .gridpolicy import embed_ctsf
        cgrid = policy.canonicalize(ctsf.grid)
        src, ctsf = ctsf.grid, embed_ctsf(ctsf, cgrid)
    if src is None:
        return ctsf, None, None
    return ctsf, src, ctsf.grid.n_diag_tiles - src.n_diag_tiles


def _embedded_panels(factor: CholeskyFactor, policy, B: jnp.ndarray):
    """The shared front half of every policy-aware RHS entry point:
    resolve the factor's canonical-grid embedding, lift the panel into the
    canonical layout, and hand back the restriction mapping results home.

    Returns ``(ctsf, source_grid, grid, panel, start_tile, restrict)``.
    For a plain factor without policy the panel passes through, ``start``
    is None (keeping the static-zero sweep traces) and ``restrict`` is the
    identity; otherwise ``start`` is the traced prefix depth and
    ``restrict`` slices a canonical-layout result panel (any leading batch
    axes) back to the source layout.  Every entry point writes the
    embed/restrict logic exactly once — here."""
    ctsf, src, pad = _resolve_embedding(factor, policy)
    g = ctsf.grid
    if src is None:
        return ctsf, src, g, B, None, lambda X: X
    from .gridpolicy import embed_rhs, restrict_rhs
    return (ctsf, src, g, embed_rhs(B, src, g),
            jnp.asarray(pad, jnp.int32),
            lambda X: restrict_rhs(X, src, g))


def _merge_panels(xd: jnp.ndarray, xa: jnp.ndarray) -> jnp.ndarray:
    """Rejoin band (ndt, t, k) and arrow (nat, t, k) tile panels into one
    (padded_n, k) RHS panel — the inverse of :func:`_split_rhs`.  Shapes are
    spelled out (no -1) so a k=0 panel round-trips."""
    k = xd.shape[-1]
    return jnp.concatenate([xd.reshape(xd.shape[0] * xd.shape[1], k),
                            xa.reshape(xa.shape[0] * xa.shape[1], k)])


def forward_solve_many(factor: CholeskyFactor, B: jnp.ndarray,
                       start_tile: int = 0,
                       options: Optional[SolverOptions] = None) -> jnp.ndarray:
    """Solve ``L Y = B`` for a panel of right-hand sides in one blocked sweep.

    Args:
      factor: banded-arrowhead Cholesky factor (``factorize_window``).
      B: ``(padded_n, k)`` float32 panel in the *padded* layout of
        ``factor.ctsf.grid`` (band rows first, then padding, then arrow
        rows — see ``TileGrid.padded_index``).  Rows in the padding region
        must be zero; they solve against identity diagonal tiles.
      options: a :class:`~repro.core.options.SolverOptions` carrying the
        solver knobs.  ``options.impl="pallas"`` runs the whole band sweep
        as one fused kernel (``kernels.ops.band_forward_sweep``), ``"ref"``
        the per-tile ``fori_loop`` reference; ``None`` picks per backend
        (pallas on TPU, ref elsewhere).
      start_tile: first band tile holding a nonzero (RHS-sparsity fast
        start).  The caller guarantees all rows above ``start_tile * t``
        are zero; the returned Y is identically zero there.

    Returns: ``(padded_n, k)`` solution panel Y.

    Recompilation: one compile per ``(grid, impl, k)``; ``start_tile`` is
    traced, so varying selections reuse the compiled sweep — but any
    nonzero ``start_tile`` uses a dynamic-bound loop variant on the ref
    path (not reverse-differentiable), so ``start_tile=0`` keeps its own
    static-bound compilation.

    Embedded factors (``factor.source_grid`` set, or ``options.policy``
    given — see ``core/gridpolicy.py``) take and return panels in the
    *source* grid's padded layout; the canonical embedding, the
    identity-prefix fast start and the restriction are handled here, and
    ``start_tile`` keeps its source-grid meaning.
    """
    opts = check_options(options)
    impl = opts.impl
    with telemetry.span("solve.forward_many", k=B.shape[-1]) as sp:
        ctsf, src, g, B, start, restrict = _embedded_panels(factor,
                                                            opts.policy, B)
        sp.tag(grid=telemetry.rung_tag(g))
        _tag_tile_block(sp, g, impl)
        bd, ba = _split_rhs(g, B)
        if start is not None:
            # caller's start_tile is in source band-tile coordinates; the
            # embedded sweep starts past the identity prefix on top of it
            eff = start + min(int(start_tile), src.n_diag_tiles) \
                if start_tile else start
            yd, ya = _forward_impl(ctsf.Dr, ctsf.R, ctsf.C, bd, ba, g, impl,
                                   eff)
        elif start_tile:
            # traced loop bound: no recompile per distinct start, but the
            # sweep becomes a dynamic-bound while_loop (not
            # reverse-differentiable) — so the common start_tile=0 path
            # keeps its static bounds below.
            yd, ya = _forward_impl(ctsf.Dr, ctsf.R, ctsf.C, bd, ba, g,
                                   impl, start_tile)
        else:
            yd, ya = _forward_impl(ctsf.Dr, ctsf.R, ctsf.C, bd, ba, g, impl)
        return restrict(_merge_panels(yd, ya))


def backward_solve_many(factor: CholeskyFactor, Y: jnp.ndarray,
                        options: Optional[SolverOptions] = None
                        ) -> jnp.ndarray:
    """Solve ``L^T X = Y`` for an (padded_n, k) panel of right-hand sides in
    one blocked sweep.  Embedded factors take/return panels in the source
    layout (cf. :func:`forward_solve_many`)."""
    opts = check_options(options)
    impl = opts.impl
    with telemetry.span("solve.backward_many", k=Y.shape[-1]) as sp:
        ctsf, _, g, Y, start, restrict = _embedded_panels(factor,
                                                          opts.policy, Y)
        sp.tag(grid=telemetry.rung_tag(g))
        _tag_tile_block(sp, g, impl)
        yd, ya = _split_rhs(g, Y)
        if start is not None:
            xd, xa = _backward_impl(ctsf.Dr, ctsf.R, ctsf.C, yd, ya, g, impl,
                                    start)
        else:
            xd, xa = _backward_impl(ctsf.Dr, ctsf.R, ctsf.C, yd, ya, g, impl)
        return restrict(_merge_panels(xd, xa))


def _refine_panels(fDr, fR, fC, mDr, mR, mC, bd, ba, xd, xa, g, impl, start):
    """One residual-checked iterative-refinement step for jitter-recovered
    factors: the perturbed factor L (of ``A + tau I``) acts as a
    preconditioner for the *original* A.  ``r = B - A X``; ``dX =
    (L L^T)^{-1} r``; the correction is accepted per RHS column only where
    it does not increase the residual norm, so refinement can only help.
    All in-graph — no host sync rides the serving path."""
    from .robustness import ctsf_matvec
    Axd, Axa = ctsf_matvec(mDr, mR, mC, xd, xa, g)
    rd, ra = bd - Axd, ba - Axa
    n0 = jnp.sum(rd * rd, axis=(0, 1)) + jnp.sum(ra * ra, axis=(0, 1))
    dd, da = _solve_panels(fDr, fR, fC, rd, ra, g, impl, start)
    xd1, xa1 = xd + dd, xa + da
    A1d, A1a = ctsf_matvec(mDr, mR, mC, xd1, xa1, g)
    n1 = (jnp.sum((bd - A1d) ** 2, axis=(0, 1))
          + jnp.sum((ba - A1a) ** 2, axis=(0, 1)))
    take = (n1 <= n0)[None, None, :]
    return jnp.where(take, xd1, xd), jnp.where(take, xa1, xa)


def solve_many(factor: CholeskyFactor, B: jnp.ndarray,
               options: Optional[SolverOptions] = None) -> jnp.ndarray:
    """``A X = B`` for a panel of right-hand sides via ``L L^T``.

    Equivalent to stacking k :func:`solve` calls but swept once: each band
    step is a ``(t, t) @ (t, k)`` matmul, so post-factorization serving cost
    is matmul-bound instead of k latency-bound substitution sweeps.

    Args:
      factor: banded-arrowhead Cholesky factor.
      B: ``(padded_n, k)`` panel in the padded layout (zero rows in the
        padding region; use ``grid.padded_index`` to place original-matrix
        entries).
      options: ``options.impl="pallas"`` = fused forward+backward sweep
        kernels (one launch per sweep), ``"ref"`` = per-tile loops,
        ``None`` = backend default.

    Returns: ``(padded_n, k)`` solution panel X.

    Recompiles once per ``(grid, impl, k)`` — serving with a fixed panel
    width never retraces; pad k up to a bucket if widths vary.

    Embedded factors (``factor.source_grid`` set by the policy-aware
    factorizations, or ``options.policy`` given) take and return panels in
    the *source* grid's padded layout: the canonical-grid embedding keys
    the compile on the canonical grid — one compile per (canonical rung, k)
    across all source grids — and both sweeps skip the identity prefix
    via their traced ``start_tile``.

    Jitter-recovered factors (``factor.info`` with a retained original
    matrix and ``tau > 0`` — see ``options.regularize`` on the
    factorizations) get one residual-checked iterative-refinement step
    against the *original* A, correcting most of the O(tau) bias the diagonal
    perturbation introduced; clean factors skip it entirely.
    """
    opts = check_options(options)
    impl = opts.impl
    with telemetry.span("solve.solve_many", k=B.shape[-1]) as sp:
        ctsf, _, g, B, start, restrict = _embedded_panels(factor,
                                                          opts.policy, B)
        sp.tag(grid=telemetry.rung_tag(g))
        _tag_tile_block(sp, g, impl)
        bd, ba = _split_rhs(g, B)
        xd, xa = _solve_panels(ctsf.Dr, ctsf.R, ctsf.C, bd, ba, g, impl,
                               start)
        info = factor.info
        if (info is not None and info.matrix is not None
                and info.matrix.grid == g and np.asarray(info.tau).ndim == 0
                and bool(np.asarray(info.tau) > 0)):
            m = info.matrix
            xd, xa = _refine_panels(ctsf.Dr, ctsf.R, ctsf.C, m.Dr, m.R, m.C,
                                    bd, ba, xd, xa, g, impl, start)
        return restrict(_merge_panels(xd, xa))


# bounded traced-callable cache for the batched solve/refine sweeps —
# keyed on (grid, impl, use_start[, "refine"]) but NOT on the panel width
# k or the batch size: k and batch land in XLA's shape-keyed compile
# cache under the one jit wrapper, so the Python-side key count stays
# O(#canonical rungs) for mixed serving traffic (cf. _BATCHED_WINDOW_CACHE)
_BATCHED_SOLVE_CACHE = LRUCache(maxsize=64, name="batched_solve")


def _batched_solve_fn(grid, opts: SolverOptions, use_start: bool):
    """One vmapped+jitted ``A X = B`` panel solve per (grid,
    ``opts.compile_key()``, has-start) — each batch element solves its
    *own* RHS panel, unlike ``concurrent_solve`` which shares one B
    across the batch.  ``use_start=True`` adds a traced identity-prefix
    depth broadcast across the batch (the rung-server canonical-grid
    path)."""
    key = (grid, opts.compile_key(), use_start)
    impl = opts.impl

    def build():
        if use_start:
            return jax.jit(jax.vmap(
                lambda dr, r, c, bd, ba, s: _solve_panels(
                    dr, r, c, bd, ba, grid, impl, s),
                in_axes=(0, 0, 0, 0, 0, None)))
        return jax.jit(jax.vmap(
            lambda dr, r, c, bd, ba: _solve_panels(dr, r, c, bd, ba, grid,
                                                   impl)))

    return _BATCHED_SOLVE_CACHE.get_or_create(key, build)


def _batched_refine_fn(grid, opts: SolverOptions, use_start: bool):
    """Vmapped per-element-masked refinement step for jitter-recovered
    batches: each element refines against its own original matrix, and
    the correction applies only where that element's ``tau > 0``.  Kept a
    *separate* dispatch from :func:`_batched_solve_fn` so clean batches
    never run it — and clean elements inside a recovered batch, whose
    corrections are masked off, stay bit-identical to an all-clean run."""
    key = (grid, opts.compile_key(), use_start, "refine")
    impl = opts.impl

    def build():
        def one(fdr, fr, fc, mdr, mr, mc, bd, ba, xd, xa, tau, s=None):
            xd1, xa1 = _refine_panels(fdr, fr, fc, mdr, mr, mc, bd, ba,
                                      xd, xa, grid, impl, s)
            use = tau > 0
            return jnp.where(use, xd1, xd), jnp.where(use, xa1, xa)

        if use_start:
            return jax.jit(jax.vmap(one, in_axes=(0,) * 11 + (None,)))
        return jax.jit(jax.vmap(
            lambda *a: one(*a), in_axes=(0,) * 11))

    return _BATCHED_SOLVE_CACHE.get_or_create(key, build)


def solve_many_batched(factor: CholeskyFactor, B: jnp.ndarray,
                       start_tile=None, bucket: bool = True,
                       options: Optional[SolverOptions] = None
                       ) -> jnp.ndarray:
    """``A_i X_i = B_i`` for a batched factor with *per-element* RHS
    panels — the rung-batch execution primitive of
    ``launch/rung_server.py`` (``concurrent_solve`` is the other batched
    solve, sharing one B across the batch; serving requests each bring
    their own).

    Args:
      factor: batched banded-arrowhead factor (leading batch axis on the
        CTSF arrays, e.g. from ``factorize_window_batched``).
      B: ``(batch, padded_n, k)`` float32 panels in the padded layout of
        ``factor.ctsf.grid``.
      start_tile: optional shared identity-prefix depth of a pre-embedded
        canonical batch (``gridpolicy.assemble_rung_batch``), threaded as
        a traced scalar so mixed pad depths share one compilation.
      bucket: pow2-pad the batch axis before dispatch (cf.
        ``factorize_window_batched``).
      options: the solver knobs; ``options.impl`` picks the sweeps'
        kernel backend.

    Returns: ``(batch, padded_n, k)`` solution panels, still in the
    factor grid's layout — callers owning an embedding restrict each
    element with ``gridpolicy.restrict_rhs``.

    Jitter-recovered factors (``factor.info`` with per-element ``tau`` and
    a retained original matrix) get one residual-checked refinement pass
    as a separate vmapped dispatch, masked per element to ``tau > 0`` —
    clean siblings of a recovered element return solutions bit-identical
    to an uncontaminated batch.
    """
    opts = check_options(options)
    ctsf = factor.ctsf
    g = ctsf.grid
    t, ndt, nat = g.t, g.n_diag_tiles, g.n_arrow_tiles
    if ctsf.Dr.ndim != 5:
        raise ValueError("solve_many_batched needs a batched factor "
                         f"(leading batch axis), got Dr.ndim={ctsf.Dr.ndim}")
    nb = ctsf.Dr.shape[0]
    if B.ndim != 3 or B.shape[0] != nb or B.shape[1] != g.padded_n:
        raise ValueError(
            f"rhs panels must be (batch={nb}, padded_n={g.padded_n}, k), "
            f"got {B.shape}")
    k = B.shape[2]
    with telemetry.span("solve.solve_many_batched", b=nb, k=k,
                        grid=telemetry.rung_tag(g)) as sp:
        _tag_tile_block(sp, g, opts.impl)
        bd = B[:, :ndt * t].reshape(nb, ndt, t, k)
        ba = B[:, ndt * t:].reshape(nb, nat, t, k)
        use_start = start_tile is not None
        fn = _batched_solve_fn(g, opts, use_start)
        if use_start:
            s = jnp.asarray(start_tile, jnp.int32)
            call = lambda dr, r, c, pd, pa: fn(dr, r, c, pd, pa, s)
        else:
            call = fn
        xd, xa = bucketed_batched_call(call, (ctsf.Dr, ctsf.R, ctsf.C,
                                              bd, ba), bucket)
        info = factor.info
        if (info is not None and info.matrix is not None
                and info.matrix.grid == g
                and np.asarray(info.tau).shape == (nb,)
                and bool(np.asarray(info.tau).max() > 0)):
            m = info.matrix
            rfn = _batched_refine_fn(g, opts, use_start)
            rcall = (lambda *a: rfn(*a, s)) if use_start else rfn
            xd, xa = bucketed_batched_call(
                rcall, (ctsf.Dr, ctsf.R, ctsf.C, m.Dr, m.R, m.C, bd, ba,
                        xd, xa, jnp.asarray(info.tau, jnp.float32)), bucket)
        return jnp.concatenate([xd.reshape(nb, ndt * t, k),
                                xa.reshape(nb, nat * t, k)], axis=1)


def forward_solve(factor: CholeskyFactor, b: jnp.ndarray,
                  options: Optional[SolverOptions] = None) -> jnp.ndarray:
    """Solve ``L y = b`` (k=1 specialization of the panel sweep)."""
    opts = check_options(options)
    return forward_solve_many(factor, b.reshape(-1, 1), options=opts)[:, 0]


def backward_solve(factor: CholeskyFactor, y: jnp.ndarray,
                   options: Optional[SolverOptions] = None) -> jnp.ndarray:
    """Solve ``L^T x = y`` (k=1 specialization of the panel sweep)."""
    opts = check_options(options)
    return backward_solve_many(factor, y.reshape(-1, 1), options=opts)[:, 0]


def solve(factor: CholeskyFactor, b: jnp.ndarray,
          options: Optional[SolverOptions] = None) -> jnp.ndarray:
    """A x = b via L L^T."""
    opts = check_options(options)
    return solve_many(factor, b.reshape(-1, 1), options=opts)[:, 0]


def logdet(factor: CholeskyFactor) -> jnp.ndarray:
    return factor.logdet()


def _rhs_grid(factor: CholeskyFactor):
    """The grid whose padded layout RHS panels use: the *source* grid for
    canonical-grid embedded factors, the factor's own grid otherwise."""
    return factor.source_grid or factor.ctsf.grid


def sample_gmrf(factor: CholeskyFactor, key: jax.Array,
                options: Optional[SolverOptions] = None) -> jnp.ndarray:
    """Draw x ~ N(0, A^{-1}) via x = L^{-T} z (the INLA sampling primitive)."""
    opts = check_options(options)
    z = jax.random.normal(key, (_rhs_grid(factor).padded_n,),
                          dtype=jnp.float32)
    return backward_solve(factor, z, options=opts)


def sample_gmrf_many(factor: CholeskyFactor, key: jax.Array, num: int,
                     options: Optional[SolverOptions] = None) -> jnp.ndarray:
    """Draw ``num`` samples x ~ N(0, A^{-1}) as one (padded_n, num) panel.

    All samples share a single blocked backward sweep (fused into one
    kernel launch under ``options.impl="pallas"``) — the serving-path
    analogue of :func:`sample_gmrf`, amortizing the factor over the whole
    batch of posterior realizations.  Recompiles once per ``(grid, impl,
    num)``.  For embedded factors ``z`` is drawn in the source layout, so a
    bucketed factor reproduces the unbucketed samples bit-for-bit per key.
    """
    opts = check_options(options)
    with telemetry.span("solve.sample_gmrf_many", num=num):
        z = jax.random.normal(key, (_rhs_grid(factor).padded_n, num),
                              dtype=jnp.float32)
        return backward_solve_many(factor, z, options=opts)


def _validate_indices(grid, indices) -> np.ndarray:
    """Validate selected indices against the *original* matrix dimension and
    map them into the padded layout (arrow indices shift past the band
    padding).  Out-of-range indices raise instead of silently gathering
    garbage from padded rows; indices must therefore be concrete."""
    s = grid.structure
    idx = np.asarray(indices)
    if idx.ndim != 1:
        raise ValueError(f"indices must be 1-D, got shape {idx.shape}")
    if idx.size and (idx.min() < 0 or idx.max() >= s.n):
        bad = idx[(idx < 0) | (idx >= s.n)]
        raise ValueError(f"indices {bad.tolist()} out of range [0, {s.n})")
    return grid.padded_index(idx)


def marginal_variances(factor: CholeskyFactor, indices: jnp.ndarray,
                       options: Optional[SolverOptions] = None) -> jnp.ndarray:
    """Selected diagonal of A^{-1} — INLA's posterior marginal variances.

    Two paths over the same factor, selected by ``options.method``:

    * ``method="selinv"`` (default, = ``options.method None``) — the
      blocked Takahashi recurrence
      (:func:`repro.core.selinv.selected_inverse`): one backward tile sweep
      computes the whole band + arrow block of Σ, cost independent of k,
      then the k selected diagonal entries are gathered.
    * ``method="panels"`` — (A^{-1})_{ii} = ‖L^{-1} e_i‖² with all k unit
      vectors riding a single multi-RHS forward sweep, started at the first
      nonzero band tile of the panel (the rows above the smallest selected
      index are identically zero).  Kept for validation/benchmarking, and
      cheaper when k is tiny relative to the bandwidth.

    Args:
      indices: 1-D concrete (host) array of element indices of the
        *original* matrix; out-of-range values raise, and arrow indices are
        remapped past the band padding rather than reading padded rows.
      options: ``options.method`` picks the path as above and
        ``options.impl`` the kernel backend of the underlying sweep
        (``"pallas"`` / ``"ref"`` / ``None`` = backend default).

    Returns: ``(k,)`` variances, ordered like ``indices``.

    Recompilation: the selinv path compiles once per ``(grid, impl)``; the
    panels path once per ``(grid, impl, k)`` — the sweep's start tile is
    traced, so *which* indices are selected never forces a retrace, only
    how many.

    Indices always refer to the *source* matrix: for canonical-grid
    embedded factors (``factor.source_grid`` set, or ``options.policy``
    given) both paths validate against the source structure and return the
    source problem's variances; the embedding/restriction rides the
    policy-aware machinery of :func:`repro.core.selinv.selected_inverse` /
    :func:`forward_solve_many`.
    """
    opts = check_options(options)
    mth = opts.method or "selinv"
    g = _rhs_grid(factor)
    padded = _validate_indices(g, indices)
    with telemetry.span("solve.marginal_variances", method=mth,
                        k=len(padded), grid=telemetry.rung_tag(g)):
        if mth == "selinv":
            from .selinv import selected_inverse
            sigma = selected_inverse(factor, options=opts)
            return jnp.take(sigma.diagonal(padded=True), jnp.asarray(padded),
                            axis=-1)
        if mth == "panels":
            k = padded.shape[0]
            E = jnp.zeros((g.padded_n, k), jnp.float32)
            E = E.at[jnp.asarray(padded), jnp.arange(k)].set(1.0)
            # RHS sparsity: unit-vector panels are zero above the selected
            # row, so the band sweep starts at the first nonzero tile.
            start = min(int(padded.min()) // g.t, g.n_diag_tiles) if k else 0
            Y = forward_solve_many(factor, E, start_tile=start, options=opts)
            return jnp.sum(Y * Y, axis=0)
        raise ValueError(
            f"unknown method {mth!r} (want 'selinv' or 'panels')")


def _marginal_variances_map(factor: CholeskyFactor,
                            indices: jnp.ndarray) -> jnp.ndarray:
    """Pre-batching reference: one forward sweep per selected index via
    ``lax.map`` (k sequential O(n·b) solves).  Used by tests and
    ``benchmarks/bench_solve.py`` as the comparison baseline."""
    g = _rhs_grid(factor)

    def one(i):
        e = jnp.zeros((g.padded_n,), jnp.float32).at[i].set(1.0)
        y = forward_solve(factor, e)
        return jnp.sum(y * y)

    return jax.lax.map(one, jnp.asarray(_validate_indices(g, indices)))
