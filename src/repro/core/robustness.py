"""Numerical fault tolerance: breakdown recovery by escalating diagonal
jitter, with per-element graceful degradation for batched serving.

The detection half lives in the kernels: both backends of
``kernels.ops.band_cholesky_sweep`` emit a (3,) status word
``[min_pivot, nonfinite, first_bad]`` as the sweep runs (in-kernel VMEM
carry on the Pallas path, ``ref.sweep_status`` on the jnp scan), so a bad
pivot is visible without any host sync or mid-batch exception.  This module
is the recovery half — the CHOLMOD-style pivot-perturbation ladder:

* on breakdown, refactorize the *original* matrix with ``tau_k * scale * I``
  added to the diagonal, ``tau_k`` escalating through
  :attr:`RegularizePolicy.taus`;
* a final Gershgorin rung (on by default) shifts failed elements into
  strict diagonal dominance, so any *finite* symmetric input is recovered —
  the 100%-recovery guarantee the injection suite gates on.  Only
  NaN/inf-contaminated inputs can exhaust the ladder, and those end as
  per-element ``STATUS_FAILED`` flags instead of exceptions;
* batched paths retry only the failed batch elements via masking: healthy
  elements keep their attempt-0 outputs bit-for-bit (one ``jnp.where``
  merge), and every retry reuses the same compiled factorization;
* the resulting :class:`FactorInfo` rides on ``CholeskyFactor`` so serving
  callers can surface per-element status, and ``solve_many`` uses the
  retained original matrix for one residual-checked refinement step
  (perturbed-factor-as-preconditioner, cf. Kim et al. in PAPERS.md).

The ladder runs a small host loop — one tiny (3,)-per-element readback per
attempt — but the clean path costs exactly one factorization plus that one
readback, which the robustness benchmark gates at <= 5% overhead.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime import telemetry
from .ctsf import BandedCTSF

__all__ = ["STATUS_OK", "STATUS_RECOVERED", "STATUS_FAILED", "STATUS_SHED",
           "RegularizePolicy", "FactorInfo", "diag_scale", "status_ok",
           "gershgorin_shift", "add_diagonal_jitter", "fold_corner_status",
           "run_ladder", "ctsf_matvec"]

_HI = jax.lax.Precision.HIGHEST

STATUS_OK = 0          # factorized clean, no jitter
STATUS_RECOVERED = 1   # breakdown detected, recovered with diagonal jitter
STATUS_FAILED = 2      # ladder exhausted (non-finite input); factor unusable
# Serving-layer terminal status: the request was never computed — shed by
# admission control, deadline expiry, an open circuit breaker, or server
# shutdown (``launch/rung_server.py``).  It completes the closed status
# taxonomy every resolved RungFuture draws from: OK/RECOVERED/FAILED/SHED.
STATUS_SHED = 3


@dataclasses.dataclass(frozen=True)
class RegularizePolicy:
    """Escalating-jitter retry policy (CHOLMOD-style pivot perturbation).

    ``taus`` are *relative* jitter magnitudes: attempt k refactorizes with
    ``taus[k] * scale * I`` added to the diagonal, where ``scale`` is the
    per-element max |diagonal| of the input (:func:`diag_scale`).  The
    default ladder starts near float32 epsilon — anything smaller is a
    no-op addition in fp32 — and escalates by ~100x per rung.

    ``gershgorin=True`` appends a final data-dependent rung: the smallest
    shift making the failed element strictly diagonally dominant (hence
    SPD), so every finite symmetric input is guaranteed to factorize.

    ``pivot_rtol`` declares breakdown when ``min_pivot <= pivot_rtol *
    scale`` (pivots are diag(L)^2, in units of A's diagonal); raise it to
    treat near-singular factors as failures worth jittering.

    ``keep_matrix=True`` retains the original (unjittered) input on the
    :class:`FactorInfo` whenever jitter was applied, enabling the
    residual-checked refinement step in ``solve_many``.
    """
    taus: Tuple[float, ...] = (1e-6, 1e-4, 1e-2)
    pivot_rtol: float = 1e-10
    gershgorin: bool = True
    gershgorin_margin: float = 1e-3
    keep_matrix: bool = True

    @staticmethod
    def resolve(regularize) -> Optional["RegularizePolicy"]:
        """Normalize a ``SolverOptions.regularize`` value: None/False ->
        None, True -> default policy, a policy -> itself."""
        if regularize is None or regularize is False:
            return None
        if regularize is True:
            return RegularizePolicy()
        if isinstance(regularize, RegularizePolicy):
            return regularize
        raise ValueError(
            f"regularize= must be None, a bool or a RegularizePolicy, "
            f"got {regularize!r}")


@dataclasses.dataclass
class FactorInfo:
    """Per-element numerical outcome of a (possibly batched) factorization.

    All array fields have the factorization's batch shape — ``()`` for a
    single matrix, ``(B,)`` for a batch:

    * ``status`` — int32 ``STATUS_OK`` / ``STATUS_RECOVERED`` /
      ``STATUS_FAILED``;
    * ``attempts`` — int32 factorization attempts consumed (1 = clean);
    * ``tau`` — float32 *absolute* diagonal shift applied (``tau_k *
      scale``; 0 for clean elements);
    * ``min_pivot`` — float32 smallest Cholesky pivot (diag(L)^2) of the
      final factor, over columns with finite diagonals;
    * ``first_bad_tile`` — int32 first failing tile index from the *clean*
      attempt (-1 if it succeeded; ``ndt`` means the arrow corner broke);
    * ``matrix`` — the original unjittered input (kept only when jitter
      was applied and the policy says so), consumed by ``solve_many``'s
      refinement step.
    """
    status: jnp.ndarray
    attempts: jnp.ndarray
    tau: jnp.ndarray
    min_pivot: jnp.ndarray
    first_bad_tile: jnp.ndarray
    matrix: Optional[BandedCTSF] = None

    def ok(self) -> np.ndarray:
        """Host bool array: which elements produced a usable factor."""
        return np.asarray(self.status) != STATUS_FAILED

    def element(self, i: int) -> dict:
        """Host-side scalar view of one batch element's outcome — the
        per-request payload a serving future carries
        (``launch/rung_server.py``): plain Python numbers, no device
        arrays, so completing a future never re-syncs.  Works on scalar
        (unbatched) info too, where ``i`` must be 0."""
        pick = lambda a, cast: cast(np.asarray(a).reshape(-1)[i])
        return {"status": pick(self.status, int),
                "attempts": pick(self.attempts, int),
                "tau": pick(self.tau, float),
                "min_pivot": pick(self.min_pivot, float),
                "first_bad_tile": pick(self.first_bad_tile, int)}


def diag_scale(Dr: jnp.ndarray, C: jnp.ndarray, grid) -> jnp.ndarray:
    """Per-element diagonal scale: max |A_ii| over band + corner diagonals
    (1.0 for an all-zero diagonal so relative jitter stays meaningful).
    Leading batch axes reduce away; NaN diagonals propagate — a
    NaN-contaminated element gets NaN jitter and ends as STATUS_FAILED."""
    parts = []
    if grid.n_diag_tiles:
        d0 = jnp.diagonal(jnp.take(Dr, 0, axis=-3), axis1=-2, axis2=-1)
        parts.append(jnp.max(jnp.abs(d0), axis=(-2, -1)))
    if grid.n_arrow_tiles:
        ct = jnp.diagonal(C, axis1=-4, axis2=-3)
        dc = jnp.diagonal(ct, axis1=-3, axis2=-2)
        parts.append(jnp.max(jnp.abs(dc), axis=(-2, -1)))
    if not parts:
        return jnp.float32(1.0)
    s = functools.reduce(jnp.maximum, parts)
    return jnp.where(s > 0, s, 1.0)


def status_ok(status_vec: jnp.ndarray, scale: jnp.ndarray,
              policy: RegularizePolicy) -> jnp.ndarray:
    """Breakdown predicate on (..., 3) status words: finite everywhere and
    every pivot above ``pivot_rtol * scale``.  (+inf min_pivot — an empty
    or all-prefix sweep — counts as healthy.)"""
    min_piv = status_vec[..., 0]
    nonfin = status_vec[..., 1]
    return (nonfin == 0.0) & (min_piv > policy.pivot_rtol * scale)


def add_diagonal_jitter(Dr: jnp.ndarray, C: jnp.ndarray, grid,
                        shift: jnp.ndarray):
    """``A + shift * I`` in CTSF layout: add ``shift`` (broadcast per batch
    element) to every band and corner diagonal entry."""
    t = grid.t
    eye = jnp.eye(t, dtype=Dr.dtype)
    sh = shift[..., None, None, None]
    if grid.n_diag_tiles:
        Dr = Dr.at[..., 0, :, :].add(sh * eye)
    nat = grid.n_arrow_tiles
    if nat:
        ar = np.arange(nat)
        C = C.at[..., ar, ar, :, :].add(sh * eye)
    return Dr, C


def gershgorin_shift(Dr: jnp.ndarray, R: jnp.ndarray, C: jnp.ndarray,
                     grid) -> jnp.ndarray:
    """Smallest diagonal shift making every Gershgorin disc positive:
    ``max_i (sum_{j != i} |A_ij| - A_ii)``, clipped at 0 — adding it (plus
    any positive margin) makes the matrix strictly diagonally dominant and
    therefore SPD.  The guaranteed final rung of the jitter ladder: NaN
    inputs yield a NaN shift (and stay failed), every finite symmetric
    input becomes factorizable.  Batch axes broadcast."""
    ndt, nat, bt = grid.n_diag_tiles, grid.n_arrow_tiles, grid.band_tiles
    b1 = bt + 1
    deltas = []
    if ndt:
        absDr = jnp.abs(Dr)
        # lower tiles: row (m, a) sums |Dr[m, d, a, :]| over d, cols
        low = jnp.sum(absDr, axis=(-3, -1))                   # (..., ndt, t)
        # upper tiles: A[m, m+d] = Dr[m+d, d]^T -> |Dr[m+d, d, :, a]|
        pad = [(0, 0)] * (Dr.ndim - 4) + [(0, bt), (0, 0), (0, 0), (0, 0)]
        Drp = jnp.pad(absDr, pad)
        m_idx = np.arange(ndt)[:, None] + np.arange(b1)[None, :]
        d_idx = np.broadcast_to(np.arange(b1)[None, :], m_idx.shape)
        Dup = Drp[..., m_idx, d_idx, :, :]                    # (..., ndt, b1, t, t)
        up = jnp.sum(Dup[..., 1:, :, :], axis=(-3, -2))       # (..., ndt, t)
        rowsum = low + up
        if nat:
            # arrow columns seen from band rows: |R[m, i, :, a]|
            rowsum = rowsum + jnp.sum(jnp.abs(R), axis=(-3, -2))
        dg = jnp.diagonal(jnp.take(Dr, 0, axis=-3), axis1=-2, axis2=-1)
        # rowsum includes |A_ii|; dominance needs A_ii > rowsum - |A_ii|
        deltas.append(jnp.max(rowsum - jnp.abs(dg) - dg, axis=(-2, -1)))
    if nat:
        rows_a = jnp.sum(jnp.abs(R), axis=(-4, -1)) if ndt else 0.0
        absC = jnp.abs(C)
        ii = np.arange(nat)[:, None]
        jj = np.arange(nat)[None, :]
        lowm = (ii >= jj)[:, :, None, None]                   # stored lower
        rows_a = rows_a + jnp.sum(jnp.where(lowm, absC, 0.0), axis=(-3, -1))
        # upper corner tiles: A[i, j>i] = C[j, i]^T -> |C[j, i, :, a]|
        upm = (ii > jj)[:, :, None, None]                     # (j, i) with j>i
        rows_a = rows_a + jnp.sum(jnp.where(upm, absC, 0.0), axis=(-4, -2))
        dcg = jnp.diagonal(jnp.diagonal(C, axis1=-4, axis2=-3),
                           axis1=-3, axis2=-2)                # (..., t, nat)
        dcg = jnp.swapaxes(dcg, -1, -2)                       # (..., nat, t)
        deltas.append(jnp.max(rows_a - jnp.abs(dcg) - dcg, axis=(-2, -1)))
    if not deltas:
        return jnp.float32(0.0)
    return jnp.maximum(functools.reduce(jnp.maximum, deltas), 0.0)


def fold_corner_status(status: jnp.ndarray, C_out: jnp.ndarray,
                       ndt: int, nat: int) -> jnp.ndarray:
    """Fold the dense-corner factor into a band status word: same per-tile
    fold as ``ref.sweep_status`` over the corner's diagonal tiles, with a
    corner breakdown reported as ``first_bad = ndt`` (one past the last
    band tile) when the band itself was clean."""
    if nat == 0:
        return status
    ar = np.arange(nat)
    dg = jnp.diagonal(C_out[..., ar, ar, :, :], axis1=-2, axis2=-1)
    fin_d = jnp.all(jnp.isfinite(dg), axis=(-2, -1))
    piv = jnp.where(fin_d, jnp.min(dg * dg, axis=(-2, -1)), jnp.inf)
    fin = jnp.all(jnp.isfinite(C_out), axis=(-4, -3, -2, -1))
    bad = ~fin | (piv <= 0.0)
    return jnp.stack(
        [jnp.minimum(status[..., 0], piv),
         jnp.maximum(status[..., 1], jnp.where(fin, 0.0, 1.0)),
         jnp.where((status[..., 2] < 0) & bad, float(ndt), status[..., 2])],
        axis=-1)


def _merge(mask: jnp.ndarray, new: jnp.ndarray, old: jnp.ndarray):
    """Per-element select: take ``new`` where ``mask`` (batch-shaped), else
    keep ``old`` — the masking that limits retries to failed elements."""
    m = mask.reshape(mask.shape + (1,) * (new.ndim - mask.ndim))
    return jnp.where(m, new, old)


@functools.partial(jax.jit, static_argnames=("grid", "policy"))
def _first_attempt_eval(sv, Dr, C, grid, policy):
    """Fused scale + breakdown predicate + the clean-path info fields — one
    dispatch on the ladder's hot path instead of the dozen eager ops it
    folds; per-op dispatch is what the <= 5% clean-overhead gate punishes
    (``policy`` is a frozen dataclass, so it keys the jit cache like the
    grid does)."""
    scale = diag_scale(Dr, C, grid)
    ok = status_ok(sv, scale, policy)
    zeros_i = jnp.zeros(ok.shape, jnp.int32)
    return (scale, ok, sv[..., 0], sv[..., 2].astype(jnp.int32),
            zeros_i, zeros_i + 1, jnp.zeros(ok.shape, jnp.float32))


def run_ladder(Dr: jnp.ndarray, R: jnp.ndarray, C: jnp.ndarray, grid,
               call: Callable, policy: RegularizePolicy):
    """Drive ``call(Dr, R, C) -> (Dr_L, R_L, C_L, status_vec)`` through the
    escalating-jitter ladder.  ``call`` may be batched (leading axes on the
    arrays and on ``status_vec[..., 3]``) — retries re-dispatch the same
    compiled callable on the full batch with only the failed elements'
    diagonals jittered, then merge so healthy elements stay bit-identical
    to their first attempt.  Returns ``(Dr_L, R_L, C_L, FactorInfo)``.

    Host control: one (3,)-per-element status readback per attempt (the
    clean path pays exactly one, then short-circuits with constant info
    fields — the <= 5% clean-path overhead the robustness benchmark
    gates), never an exception — exhausted elements come back flagged
    ``STATUS_FAILED`` with their factor left as-is.
    """
    dr, r, c, sv = call(Dr, R, C)
    (scale, ok, min_piv0, first_bad,
     status0, attempts, tau_app) = _first_attempt_eval(sv, Dr, C, grid,
                                                       policy)
    ok_host = np.asarray(ok)          # the ladder's one clean-path readback
    if ok_host.all():
        # telemetry piggybacks on the readback the ladder already pays —
        # no extra device sync rides the <= 5% clean-overhead gate
        if telemetry.enabled():
            n = int(ok_host.size)
            telemetry.inc("robustness.attempts", n)
            telemetry.inc("robustness.status", n, outcome="ok")
        info = FactorInfo(status=status0, attempts=attempts, tau=tau_app,
                          min_pivot=min_piv0, first_bad_tile=first_bad,
                          matrix=None)
        return dr, r, c, info
    shifts = [jnp.float32(tau) * scale for tau in policy.taus]
    if policy.gershgorin:
        shifts.append(gershgorin_shift(Dr, R, C, grid)
                      + jnp.float32(policy.gershgorin_margin) * scale)
    for shift in shifts:
        failed = ~ok
        sh = jnp.where(failed, shift, 0.0)
        DrJ, CJ = add_diagonal_jitter(Dr, C, grid, sh)
        n_dr, n_r, n_c, n_sv = call(DrJ, R, CJ)
        dr = _merge(failed, n_dr, dr)
        r = _merge(failed, n_r, r)
        c = _merge(failed, n_c, c)
        sv = _merge(failed, n_sv, sv)
        tau_app = jnp.where(failed, sh, tau_app)
        attempts = attempts + failed.astype(jnp.int32)
        ok = ok | (failed & status_ok(n_sv, scale, policy))
        if np.asarray(ok).all():
            break
    status = jnp.where(ok,
                       jnp.where(tau_app > 0, STATUS_RECOVERED, STATUS_OK),
                       STATUS_FAILED).astype(jnp.int32)
    jittered = bool(np.asarray(jnp.any(tau_app > 0)))
    if telemetry.enabled():
        # ladder path only — extra readbacks here are off the clean path,
        # which short-circuited above
        st_host = np.asarray(status).ravel()
        telemetry.inc("robustness.attempts",
                      int(np.asarray(attempts).sum()))
        for code, outcome in ((STATUS_OK, "ok"),
                              (STATUS_RECOVERED, "recovered"),
                              (STATUS_FAILED, "failed")):
            n = int((st_host == code).sum())
            if n:
                telemetry.inc("robustness.status", n, outcome=outcome)
    matrix = BandedCTSF(grid, Dr, R, C) \
        if (jittered and policy.keep_matrix) else None
    info = FactorInfo(status=status, attempts=attempts, tau=tau_app,
                      min_pivot=sv[..., 0], first_bad_tile=first_bad,
                      matrix=matrix)
    return dr, r, c, info


@functools.partial(jax.jit, static_argnames=("grid",))
def ctsf_matvec(Dr: jnp.ndarray, R: jnp.ndarray, C: jnp.ndarray,
                xd: jnp.ndarray, xa: jnp.ndarray, grid):
    """``Y = A @ X`` on split tile panels for a *symmetric* banded-arrowhead
    CTSF (an original matrix, not a triangular factor): xd (ndt, t, k) band
    panel, xa (nat, t, k) arrow panel -> (yd, ya) of the same shapes.
    Powers the residual ``B - A X`` of the refinement step in
    ``solve_many``; identity-prefix rows of an embedded matrix map zero
    panels to zero, so canonical-grid residuals need no special casing."""
    t = grid.t
    ndt, nat, bt = grid.n_diag_tiles, grid.n_arrow_tiles, grid.band_tiles
    b1 = bt + 1
    k = xd.shape[-1]
    if ndt:
        m_idx = np.arange(ndt)[:, None]
        d_idx = np.broadcast_to(np.arange(b1)[None, :], (ndt, b1))
        # lower: y[m] += sum_d Dr[m, d] @ x[m-d]
        xp = jnp.pad(xd, ((bt, 0), (0, 0), (0, 0)))
        yd = jnp.einsum("mdab,mdbk->mak", Dr, xp[m_idx - d_idx + bt],
                        precision=_HI)
        if bt:
            # upper: A[m, m+d] = Dr[m+d, d]^T for d >= 1
            Drp = jnp.pad(Dr, ((0, bt), (0, 0), (0, 0), (0, 0)))
            Dup = Drp[m_idx + d_idx, d_idx]               # (ndt, b1, t, t)
            xq = jnp.pad(xd, ((0, bt), (0, 0), (0, 0)))
            yd = yd + jnp.einsum("mdba,mdbk->mak", Dup[:, 1:],
                                 xq[m_idx + d_idx][:, 1:], precision=_HI)
        if nat:
            # arrow columns seen from band rows: A[m, ndt+i] = R[m, i]^T
            yd = yd + jnp.einsum("miba,ibk->mak", R, xa, precision=_HI)
    else:
        yd = xd
    if nat:
        ya = jnp.einsum("miab,mbk->iak", R, xd, precision=_HI) if ndt \
            else jnp.zeros((nat, t, k), xd.dtype)
        ii = np.arange(nat)[:, None]
        jj = np.arange(nat)[None, :]
        # stored lower corner mirrored: Cfull[i, j>i] = C[j, i]^T
        Cfull = jnp.where((ii >= jj)[:, :, None, None], C,
                          jnp.swapaxes(jnp.swapaxes(C, 0, 1), -1, -2))
        ya = ya + jnp.einsum("ijab,jbk->iak", Cfull, xa, precision=_HI)
    else:
        ya = xa
    return yd, ya
