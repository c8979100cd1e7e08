"""Concurrent Cholesky factorizations (paper Appendix A).

INLA's central-difference gradient needs 2n independent factorizations of
same-structure matrices; the paper runs them concurrently with NUMA-aware
core binding.  The TPU analogue: stack the matrices on a leading batch axis,
`vmap` the factorization, and shard the batch over the `data` mesh axis —
each device (group) owns whole factorizations, the device-local equivalent
of binding one factorization to one NUMA node.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.runtime import telemetry
from .cholesky import (CholeskyFactor, _factorize_window_impl,
                       factorize_window_batched)
from .ctsf import BandedCTSF
from .options import SolverOptions, check_options
from .selinv import SelectedInverse, _selinv_impl, selinv_batched
from .structure import TileGrid

__all__ = ["stack_ctsf", "concurrent_factorize", "concurrent_logdet",
           "concurrent_quadratic_forms", "concurrent_selinv",
           "concurrent_solve"]


def stack_ctsf(mats: list, policy=None) -> BandedCTSF:
    """Stack BandedCTSF matrices on a leading batch axis.

    Without a policy all matrices must share one grid (unequal grids raise
    ``ValueError`` — a real validation, not a stripped-under-``-O`` bare
    assert).  With a :class:`~repro.core.gridpolicy.GridBucketPolicy`,
    matrices on *unequal* grids are first embedded onto their shared
    canonical rung (``policy.join``) with identity-diagonal padding, so a
    mixed-size batch can ride one vmapped factorization.  Note the stacked
    result is a plain canonical-grid matrix batch: factorize it with
    ``factorize_window_batched(..., options=SolverOptions(policy=policy))``
    (a no-op embedding, since every grid is already canonical) to get a
    factor whose solves restrict back to the canonical — not the
    per-matrix source — layout.
    """
    if not mats:
        raise ValueError("stack_ctsf needs at least one matrix")
    if policy is not None:
        from .gridpolicy import embed_ctsf
        cgrid = policy.join([m.grid for m in mats])
        mats = [embed_ctsf(m, cgrid) for m in mats]
    grid = mats[0].grid
    for m in mats:
        if m.grid != grid:
            raise ValueError(
                "concurrent factorization needs equal structure: got grids "
                f"with (ndt, bt, nat) = "
                f"{sorted({(x.grid.n_diag_tiles, x.grid.band_tiles, x.grid.n_arrow_tiles) for x in mats})}; "
                "pass a GridBucketPolicy (policy=) to embed them onto a "
                "shared canonical rung")
    return BandedCTSF(
        grid,
        jnp.stack([m.Dr for m in mats]),
        jnp.stack([m.R for m in mats]),
        jnp.stack([m.C for m in mats]),
    )


def _per_device(fn, mesh: Mesh, axis: str, n_out: int):
    """jit ``fn`` (vmapped over a batch of whole problems) so each device
    along ``axis`` runs it on its own slice of the batch.  The Pallas
    sweeps inside cannot be partitioned by the compiler, so the batch is
    split explicitly with ``shard_map``; every output keeps the batch
    sharding."""
    spec = P(axis)
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * 3,
                                 out_specs=(spec,) * n_out,
                                 check_vma=False))


def concurrent_factorize(batch: BandedCTSF, mesh: Optional[Mesh] = None,
                         axis: str = "data", tree_chunks: int = 8,
                         options: Optional[SolverOptions] = None
                         ) -> CholeskyFactor:
    """Factorize a batch of matrices concurrently.

    With ``mesh``, the batch axis is sharded over ``axis`` — one factorization
    never spans devices (App. A's within-NUMA binding); without, it delegates
    to the cached batched serving path (``factorize_window_batched``) so
    repeated same-structure sweeps never retrace.

    With an ``options.policy`` the batch is embedded onto its canonical
    grid first (``core/gridpolicy.py``) — the sharded sweep then runs on
    the canonical grid with its identity prefix skipped, and the returned
    factor carries ``source_grid`` for the policy-aware solve/selinv
    entry points.

    ``options.regularize`` (bool or
    :class:`~repro.core.robustness.RegularizePolicy`) enables per-element
    breakdown recovery: the escalating-jitter ladder
    retries only the failed elements (on the mesh path the retries ride
    the same sharded callable; only the (B, 3) status words are read back
    to the host) and the returned
    ``factor.info`` flags each element OK / RECOVERED / FAILED instead of
    one bad θ-candidate raising mid-sweep.
    """
    opts = check_options(options)
    if mesh is None:
        return factorize_window_batched(batch, tree_chunks=tree_chunks,
                                        bucket=False, options=opts)
    from .robustness import RegularizePolicy, run_ladder
    pol = RegularizePolicy.resolve(opts.regularize)
    impl, plan = opts.impl, opts.partition_plan
    source = None
    if opts.policy is not None:
        from .cholesky import _embed_matrix
        src_ndt = batch.grid.n_diag_tiles
        batch, source, start = _embed_matrix(batch, opts.policy)
        if plan is not None:
            plan = plan.shifted(batch.grid.n_diag_tiles - src_ndt)
        fn = jax.vmap(
            lambda dr, r, c: _factorize_window_impl(
                dr, r, c, batch.grid, impl, tree_chunks, start, plan))
    else:
        fn = jax.vmap(
            lambda dr, r, c: _factorize_window_impl(
                dr, r, c, batch.grid, impl, tree_chunks, 0, plan))
    fn = _per_device(fn, mesh, axis, n_out=4)
    if pol is None:
        dr, r, c, _status = fn(batch.Dr, batch.R, batch.C)
        info = None
    else:
        dr, r, c, info = run_ladder(batch.Dr, batch.R, batch.C, batch.grid,
                                    fn, pol)
    return CholeskyFactor(BandedCTSF(batch.grid, dr, r, c),
                          source_grid=source, info=info)


def concurrent_solve(factor: CholeskyFactor, B: jnp.ndarray,
                     options: Optional[SolverOptions] = None) -> jnp.ndarray:
    """Solve ``A_i X_i = B`` for every factor in the batch, one vmapped
    multi-RHS sweep.

    Args:
      factor: *batched* factor (leading batch axis on the CTSF arrays, as
        returned by ``factorize_window_batched`` / ``concurrent_factorize``).
      B: RHS shared across the batch, shape ``(padded_n,)`` or
        ``(padded_n, k)`` in the padded layout (zero rows in the padding
        region).
      options: ``options.impl`` is the sweeps' kernel backend; ``"pallas"``
        vmaps the *fused* band-sweep kernels
        (``kernels.ops.band_forward_sweep`` / ``band_backward_sweep``) —
        the batch rides the kernel grid for free.

    Returns: ``(batch, padded_n)`` or ``(batch, padded_n, k)``.

    Combined with :func:`concurrent_factorize` this is the full batched
    serving path — a θ-sweep of factorizations amortized over a panel of
    RHS without ever leaving the device.  Recompiles once per
    ``(grid, impl, k, batch)``.

    Embedded factors (``factor.source_grid`` set, or ``options.policy``
    given) take ``B`` and return ``X`` in the *source* layout; the canonical
    embedding and the identity-prefix skip ride the batched sweep.
    """
    from .solve import _embedded_panels, _merge_panels, _solve_panels, \
        _split_rhs
    with telemetry.span("concurrent.solve"):
        opts = check_options(options)
        impl = opts.impl
        panel = B[:, None] if B.ndim == 1 else B
        ctsf, _, g, panel, start, restrict = _embedded_panels(
            factor, opts.policy, panel)
        bd, ba = _split_rhs(g, panel)
        with telemetry.span("solve.enqueue"):
            xd, xa = jax.vmap(
                lambda dr, r, c: _solve_panels(dr, r, c, bd, ba, g, impl,
                                               start))(
                ctsf.Dr, ctsf.R, ctsf.C)
        out = restrict(jax.vmap(_merge_panels)(xd, xa))
        return out[..., 0] if B.ndim == 1 else out


def concurrent_selinv(factor: CholeskyFactor, mesh: Optional[Mesh] = None,
                      axis: str = "data",
                      options: Optional[SolverOptions] = None
                      ) -> SelectedInverse:
    """Selected inversion of a batch of factors concurrently.

    With ``mesh``, the batch axis is sharded over ``axis`` — one backward
    Takahashi sweep never spans devices, matching
    :func:`concurrent_factorize`'s placement so a θ-sweep's factors and
    their posterior marginals stay device-resident end to end; without, it
    delegates to the cached batched path (:func:`selinv_batched`).

    Embedded factors (``factor.source_grid`` set, or ``options.policy``
    given) run the sweep on the canonical grid with the identity prefix skipped
    and return the selected inverse restricted to the source grid.
    """
    opts = check_options(options)
    if mesh is None:
        return selinv_batched(factor, bucket=False, options=opts)
    from .solve import _resolve_embedding
    impl = opts.impl
    ctsf, src, pad = _resolve_embedding(factor, opts.policy)
    g = ctsf.grid
    if src is not None:
        start = jnp.asarray(pad, jnp.int32)
        fn = jax.vmap(
            lambda dr, r, c: _selinv_impl(dr, r, c, g, impl, start))
    else:
        fn = jax.vmap(lambda dr, r, c: _selinv_impl(dr, r, c, g, impl))
    sd, sr, sc = _per_device(fn, mesh, axis, n_out=3)(ctsf.Dr, ctsf.R,
                                                      ctsf.C)
    out = SelectedInverse(g, sd, sr, sc)
    if src is not None:
        from .gridpolicy import restrict_selinv
        out = restrict_selinv(out, src)
    return out


def concurrent_quadratic_forms(factor: CholeskyFactor, y: jnp.ndarray,
                               options: Optional[SolverOptions] = None
                               ) -> jnp.ndarray:
    """``y^T A_i^{-1} y`` for each factor in the batch.

    Uses ``‖L_i^{-1} y‖²`` — only the *forward* sweep, vmapped over the
    batch — which is half the work of a full solve and exactly the
    quadratic-form term INLA's objective needs per θ candidate.

    Embedded factors (``factor.source_grid`` set, or ``options.policy``
    given) take ``y`` in the source layout; the identity-prefix rows of the
    embedded sweep are zero, so the squared norm needs no restriction.
    """
    from .solve import _embedded_panels, _forward_impl, _split_rhs
    opts = check_options(options)
    impl = opts.impl
    ctsf, _, g, panel, start, _ = _embedded_panels(factor, opts.policy,
                                                   y.reshape(-1, 1))
    bd, ba = _split_rhs(g, panel)
    if start is not None:
        fn = jax.vmap(
            lambda dr, r, c: _forward_impl(dr, r, c, bd, ba, g, impl, start))
    else:
        fn = jax.vmap(
            lambda dr, r, c: _forward_impl(dr, r, c, bd, ba, g, impl))
    yd, ya = fn(ctsf.Dr, ctsf.R, ctsf.C)
    return (jnp.sum(yd * yd, axis=(1, 2, 3))
            + jnp.sum(ya * ya, axis=(1, 2, 3)))


def concurrent_logdet(factor: CholeskyFactor) -> jnp.ndarray:
    """Batched log-determinants from a batched factor (INLA's per-evaluation
    quantity)."""
    with telemetry.span("concurrent.logdet"):
        ctsf = factor.ctsf
        g = ctsf.grid
        diag_band = jnp.diagonal(ctsf.Dr[:, :, 0], axis1=-2, axis2=-1)
        total = jnp.sum(jnp.log(jnp.abs(diag_band)), axis=(-2, -1))
        if g.n_arrow_tiles > 0:
            ar = jnp.arange(g.n_arrow_tiles)
            dc = jnp.diagonal(ctsf.C[:, ar, ar], axis1=-2, axis2=-1)
            total = total + jnp.sum(jnp.log(jnp.abs(dc)), axis=(-2, -1))
        return 2.0 * total
