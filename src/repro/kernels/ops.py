"""Jitted public wrappers for the tile kernels, with backend dispatch.

``impl`` selects between the Pallas TPU kernels (``"pallas"`` — validated on
CPU through interpret mode, compiled natively on TPU) and the pure-jnp
references (``"ref"`` — what XLA fuses itself; the default on CPU where
interpret-mode Python execution would dominate).  The factorization code
calls these and is oblivious to the backend; tests assert the two agree.
"""
from __future__ import annotations

import os
from typing import Literal

import jax
import jax.numpy as jnp

from . import ref
from .potrf import potrf_pallas
from .trsm import solve_panel_pallas, trsm_pallas
from .gemm import gemm_pallas, syrk_pallas, geadd_pallas
from .band_cholesky import (band_cholesky_partitioned_sweep_pallas,
                            band_cholesky_stream_sweep_pallas,
                            band_cholesky_sweep_pallas, sweep_path)
from .band_solve import band_backward_sweep_pallas, band_forward_sweep_pallas
from .selinv import selinv_step_pallas, selinv_sweep_pallas

__all__ = ["potrf", "trsm", "solve_panel", "syrk", "gemm", "geadd",
           "selinv_step", "band_forward_sweep",
           "band_backward_sweep", "band_cholesky_sweep",
           "band_cholesky_partitioned_sweep", "selinv_sweep",
           "default_impl"]

Impl = Literal["ref", "pallas"]

_VALID_IMPLS = ("ref", "pallas")


def default_impl() -> Impl:
    env = os.environ.get("REPRO_KERNEL_IMPL")
    if env is not None:
        if env not in _VALID_IMPLS:
            raise ValueError(
                f"REPRO_KERNEL_IMPL={env!r} is not a valid kernel backend; "
                f"expected one of {list(_VALID_IMPLS)} (unset the variable "
                "to let the per-backend default apply: pallas on TPU, ref "
                "elsewhere)")
        return env  # type: ignore[return-value]
    # Pallas natively on TPU; jnp-fused path on CPU (interpret mode is for
    # validation, not production CPU perf).
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _interp() -> bool:
    return jax.default_backend() != "tpu"


def potrf(a: jnp.ndarray, impl: Impl | None = None) -> jnp.ndarray:
    impl = impl or default_impl()
    if impl == "pallas":
        return potrf_pallas(a, interpret=_interp())
    return ref.potrf_ref(a) if a.ndim == 2 else jax.vmap(ref.potrf_ref)(
        a.reshape((-1,) + a.shape[-2:])).reshape(a.shape)


def trsm(l_kk: jnp.ndarray, a_mk: jnp.ndarray, impl: Impl | None = None) -> jnp.ndarray:
    impl = impl or default_impl()
    if impl == "pallas":
        return trsm_pallas(l_kk, a_mk, interpret=_interp())
    if a_mk.ndim == 2:
        return ref.trsm_ref(l_kk, a_mk)
    flat = a_mk.reshape((-1,) + a_mk.shape[-2:])
    return jax.vmap(lambda x: ref.trsm_ref(l_kk, x))(flat).reshape(a_mk.shape)


def solve_panel(l_kk: jnp.ndarray, b_panel: jnp.ndarray, trans: bool = False,
                impl: Impl | None = None) -> jnp.ndarray:
    """Multi-RHS triangular solve ``L X = B`` (``trans`` -> ``L^T X = B``)
    for a (t, k) RHS panel — the tile primitive of the batched serving path
    (`core.solve.solve_many` / one-sweep marginal variances)."""
    impl = impl or default_impl()
    if impl == "pallas":
        return solve_panel_pallas(l_kk, b_panel, trans=trans, interpret=_interp())
    return ref.solve_panel_ref(l_kk, b_panel, trans=trans)


def syrk(c_kk: jnp.ndarray, a_kn: jnp.ndarray, impl: Impl | None = None) -> jnp.ndarray:
    impl = impl or default_impl()
    if impl == "pallas":
        return syrk_pallas(c_kk, a_kn, interpret=_interp())
    return ref.syrk_ref(c_kk, a_kn)


def gemm(c_mk: jnp.ndarray, a_mn: jnp.ndarray, b_kn: jnp.ndarray,
         impl: Impl | None = None) -> jnp.ndarray:
    impl = impl or default_impl()
    if impl == "pallas":
        return gemm_pallas(c_mk, a_mn, b_kn, interpret=_interp())
    return ref.gemm_ref(c_mk, a_mn, b_kn)


def geadd(a: jnp.ndarray, b: jnp.ndarray, impl: Impl | None = None) -> jnp.ndarray:
    impl = impl or default_impl()
    if impl == "pallas":
        return geadd_pallas(a, b, interpret=_interp())
    return ref.geadd_ref(a, b)


def selinv_step(s_row: jnp.ndarray, g_col: jnp.ndarray,
                impl: Impl | None = None) -> jnp.ndarray:
    """One Takahashi selected-inversion tile step: ``u[e] = sum_j
    s_row[e, j] @ g_col[j]`` — the accumulation chain feeding one column of
    Σ = A^{-1} in ``core.selinv``'s backward recurrence (registered alongside
    :func:`solve_panel` as a serving-path tile primitive)."""
    impl = impl or default_impl()
    if impl == "pallas":
        return selinv_step_pallas(s_row, g_col, interpret=_interp())
    return ref.selinv_step_ref(s_row, g_col)


def band_forward_sweep(Dr: jnp.ndarray, R: jnp.ndarray, bd: jnp.ndarray,
                       start_tile=0, impl: Impl | None = None):
    """Whole-band multi-RHS forward sweep: solve ``L Y = B`` over all band
    tile rows and accumulate the arrow-RHS correction ``sum_m R[m] @ Y_m``
    in the same pass.  The sweep-level serving primitive: ``"pallas"`` runs
    one fused kernel (ring of recent panels in VMEM — no per-tile HBM
    round-trips), ``"ref"`` the per-tile ``fori_loop`` of
    :func:`solve_panel`.  ``start_tile`` may be traced (RHS-sparsity fast
    start; rows above it stay zero on both backends)."""
    impl = impl or default_impl()
    if impl == "pallas":
        return band_forward_sweep_pallas(Dr, R, bd, start_tile,
                                         interpret=_interp())
    return ref.band_forward_sweep_ref(Dr, R, bd, start_tile)


def band_backward_sweep(Dr: jnp.ndarray, R: jnp.ndarray, yd: jnp.ndarray,
                        xa: jnp.ndarray, start_tile=0,
                        impl: Impl | None = None) -> jnp.ndarray:
    """Whole-band multi-RHS backward sweep: solve ``L^T X = Y - R^T Xa``
    over all band tile rows in reverse — the transpose counterpart of
    :func:`band_forward_sweep`, with the same backend split.
    ``start_tile`` (traced) skips the identity-embedding prefix rows of a
    canonical grid, leaving X zero there."""
    impl = impl or default_impl()
    if impl == "pallas":
        return band_backward_sweep_pallas(Dr, R, yd, xa, start_tile,
                                          interpret=_interp())
    return ref.band_backward_sweep_ref(Dr, R, yd, xa, start_tile)


def band_cholesky_sweep(Ac: jnp.ndarray, R: jnp.ndarray, nchunks: int = 1,
                        start_tile=0, impl: Impl | None = None):
    """Whole band+arrow Cholesky factorization as one sweep-level primitive:
    ``Ac (ndt, bt+1, t, t)`` column-band tiles and ``R (ndt, nat, t, t)``
    arrow rows -> ``(panels, R_out, schur, status)`` column panels of L,
    factored arrow rows, per-chunk corner-Schur partial sums (``nchunks``
    chunks — the tree-reduction leaves for the corner factorization), and
    the (3,) float32 breakdown status word ``[min_pivot, nonfinite,
    first_bad]`` (see ``ref.sweep_status``) — detection rides the sweep
    with no host sync on either backend, so callers (the jitter ladder in
    ``core/robustness.py``) decide host-side whether to retry without the
    factorization ever raising mid-batch.

    ``"pallas"`` runs one fused kernel for the entire factorization (VMEM
    ring of the last band_tiles panels + arrow ring, in-kernel potrf, the
    panel solved by products with L_kk^{-1}, Schur accumulated on the
    fly), or where that ring cannot fit in VMEM
    (``band_cholesky.sweep_path``) the streamed kernel, which keeps the
    factor in HBM and streams each column's update through VMEM;
    ``"ref"`` the ring-buffer ``lax.scan`` that dispatches per-panel tile
    ops.  This is what ``core.cholesky._factorize_window_impl`` rides on
    every backend.

    ``start_tile`` (traced) declares the first ``start_tile`` columns an
    identity-embedding prefix (``core/gridpolicy.py``): both backends emit
    identity panels / zero arrow rows for them, and the fused kernel skips
    their compute entirely."""
    impl = impl or default_impl()
    if impl == "pallas":
        t, bt, nat = Ac.shape[-1], Ac.shape[1] - 1, R.shape[1]
        sweep = band_cholesky_sweep_pallas \
            if sweep_path(t, bt, nat) == "fused" \
            else band_cholesky_stream_sweep_pallas
        return sweep(Ac, R, nchunks=nchunks, start_tile=start_tile,
                     interpret=_interp())
    return ref.band_cholesky_sweep_ref(Ac, R, nchunks=nchunks,
                                       start_tile=start_tile)


def band_cholesky_partitioned_sweep(Ac: jnp.ndarray, R: jnp.ndarray,
                                    boundaries, start_tile=0,
                                    impl: Impl | None = None):
    """Partition-parallel band+arrow Cholesky: every independent partition
    of a block-separable band factorizes in ONE launch.

    ``boundaries`` is the static tile-boundary tuple of a
    :class:`~repro.core.ordering.PartitionPlan` (``(0, c_1, ..., ndt)``,
    hashable — the kernels layer takes the raw tuple so it stays
    decoupled from core's plan type); the input must be block-separable
    across those cuts (no band tile crossing a boundary —
    ``detect_partition_plan`` certifies it).  Returns ``(panels, R_out,
    schur, status)`` like :func:`band_cholesky_sweep`, except ``schur``
    is ``(P, nat, nat, t, t)`` — one corner-Schur tree-reduction leaf per
    partition — and ``status.first_bad`` is already global.

    ``"pallas"`` runs the 2D-grid fused kernel (parallel partition axis ×
    sequential per-partition axis: critical path O(max partition tiles)
    instead of O(ndt)); ``"ref"`` runs the per-partition ``lax.scan``
    oracle.  A trivial single-partition ``boundaries=(0, ndt)`` is valid
    but pointless — ``core.cholesky`` routes that case to
    :func:`band_cholesky_sweep` to keep it bit-identical to the
    unpartitioned sweep."""
    impl = impl or default_impl()
    boundaries = tuple(int(b) for b in boundaries)
    if impl == "pallas":
        return band_cholesky_partitioned_sweep_pallas(
            Ac, R, boundaries, start_tile=start_tile, interpret=_interp())
    return ref.band_cholesky_partitioned_sweep_ref(
        Ac, R, boundaries, start_tile=start_tile)


def selinv_sweep(lcol: jnp.ndarray, R: jnp.ndarray, sc_full: jnp.ndarray,
                 start_tile=0, impl: Impl | None = None):
    """Whole backward Takahashi recurrence as one sweep-level primitive:
    ``lcol (ndt, bt+1, t, t)`` column view of the factor, ``R`` its arrow
    rows and ``sc_full (nat, nat, t, t)`` the dense corner Σ seed ->
    ``(panels, acols)`` Σ column panels and arrow entries.

    ``"pallas"`` runs one fused kernel for the whole recurrence (Σ-column
    ring resident in VMEM across columns — the ROADMAP's selinv-fusion
    item); ``"ref"`` the per-column ``lax.scan`` of ``selinv_step``
    contractions.  Backs ``core.selinv.selected_inverse`` on every
    backend.  ``start_tile`` (traced) skips the identity-embedding prefix
    columns of a canonical grid, emitting identity Σ panels there."""
    impl = impl or default_impl()
    if impl == "pallas":
        return selinv_sweep_pallas(lcol, R, sc_full, start_tile,
                                   interpret=_interp())
    return ref.selinv_sweep_ref(lcol, R, sc_full, start_tile)

