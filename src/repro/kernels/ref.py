"""Pure-jnp reference oracles for every tile kernel.

These define the exact semantics the Pallas kernels must match
(``tests/test_kernels_*`` sweeps shapes/dtypes and asserts allclose).
All operate on single dense (t, t) tiles in the lower-triangular Cholesky
convention of Algorithm 1 (see core/symbolic.py Task docstring).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["potrf_ref", "trsm_ref", "solve_panel_ref", "syrk_ref",
           "gemm_ref", "geadd_ref", "selinv_step_ref",
           "band_forward_sweep_ref", "band_backward_sweep_ref",
           "band_cholesky_sweep_ref", "band_cholesky_partitioned_sweep_ref",
           "selinv_sweep_ref", "sweep_status", "empty_sweep_status",
           "combine_sweep_status"]

_HI = jax.lax.Precision.HIGHEST


def empty_sweep_status() -> jnp.ndarray:
    """The healthy/empty status word: ``[+inf, 0, -1]``."""
    return jnp.array([jnp.inf, 0.0, -1.0], jnp.float32)


def sweep_status(panels: jnp.ndarray, R_out: jnp.ndarray) -> jnp.ndarray:
    """Per-sweep breakdown status word, derived from the emitted factor.

    Input:  panels (ndt, b1, t, t) column panels (``panels[k, 0]`` the
            diagonal tile L_kk), R_out (ndt, nat, t, t) factored arrow rows.
    Output: (3,) float32 ``[min_pivot, nonfinite, first_bad]`` with

    * ``min_pivot`` — min over columns of ``min(diag(L_kk)^2)`` (the
      smallest Cholesky pivot), taken over columns whose diagonal is
      finite (+inf if none are);
    * ``nonfinite`` — 1.0 iff any emitted panel/arrow entry is NaN/inf;
    * ``first_bad`` — index of the first column whose output is
      non-finite or whose pivot is <= 0 (-1.0 when the sweep is clean).

    Deriving the word from the *emitted* factor (not the in-loop pivots of
    ``potrf.factorize_tile``) is what makes both kernel backends agree: the
    jnp scan's ``jnp.linalg.cholesky`` NaN-poisons on breakdown instead of
    yielding finite negative pivots, but the emitted tiles are the same
    story on both paths.  The fused Pallas kernel folds exactly this
    per-column update into a VMEM status carry as the sweep runs; this
    helper is the jnp oracle for it.

    jit-safe, no host sync, vmap/batch friendly: all three entries are
    data-dependent scalars with static shapes.
    """
    ndt = panels.shape[0]
    if ndt == 0:
        return empty_sweep_status()
    t = panels.shape[-1]
    diag = jnp.diagonal(panels[:, 0], axis1=-2, axis2=-1)      # (ndt, t)
    fin_diag = jnp.all(jnp.isfinite(diag), axis=-1)            # (ndt,)
    piv = jnp.where(fin_diag, jnp.min(diag * diag, axis=-1), jnp.inf)
    fin = (jnp.all(jnp.isfinite(panels), axis=(1, 2, 3))
           & jnp.all(jnp.isfinite(R_out), axis=(1, 2, 3)))     # (ndt,)
    bad = ~fin | (piv <= 0.0)
    first = jnp.min(jnp.where(bad, jnp.arange(ndt), ndt))
    first = jnp.where(first == ndt, -1, first)
    return jnp.stack([jnp.min(piv),
                      jnp.max(jnp.where(fin, 0.0, 1.0)),
                      first.astype(jnp.float32)])


def potrf_ref(a: jnp.ndarray) -> jnp.ndarray:
    """Cholesky of one diagonal tile: L lower with A = L L^T."""
    return jnp.linalg.cholesky(a)


def trsm_ref(l_kk: jnp.ndarray, a_mk: jnp.ndarray) -> jnp.ndarray:
    """Off-diagonal panel solve: returns L_mk = A_mk L_kk^{-T}.

    (X L^T = A  <=>  L X^T = A^T, lower forward substitution.)
    """
    xt = jax.scipy.linalg.solve_triangular(l_kk, a_mk.T, lower=True, trans=0)
    return xt.T


def solve_panel_ref(l_kk: jnp.ndarray, b_panel: jnp.ndarray,
                    trans: bool = False) -> jnp.ndarray:
    """Multi-RHS triangular panel solve: ``L X = B`` (or ``L^T X = B``).

    ``B`` is a (t, k) panel of k right-hand sides — one (t, t) @ (t, k)
    substitution sweep instead of k matvec sweeps, which is what makes the
    batched serving path matmul-bound.
    """
    return jax.scipy.linalg.solve_triangular(
        l_kk, b_panel, lower=True, trans=1 if trans else 0)


def syrk_ref(c_kk: jnp.ndarray, a_kn: jnp.ndarray) -> jnp.ndarray:
    """Symmetric rank-t update of a diagonal tile: C - A A^T."""
    return c_kk - jnp.dot(a_kn, a_kn.T, precision=_HI)


def gemm_ref(c_mk: jnp.ndarray, a_mn: jnp.ndarray, b_kn: jnp.ndarray) -> jnp.ndarray:
    """Off-diagonal accumulation: C - A B^T."""
    return c_mk - jnp.dot(a_mn, b_kn.T, precision=_HI)


def geadd_ref(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Generalized addition (tree-reduction combine step, paper Fig. 6)."""
    return a + b


def selinv_step_ref(s_row: jnp.ndarray, g_col: jnp.ndarray) -> jnp.ndarray:
    """One Takahashi tile step: block row of Σ times normalized factor column.

    Input:  s_row (e_n, j_n, t, t) — already-computed Σ tiles Σ[i_e, k_j]
            g_col (j_n, t, t)      — normalized column G[k_j] = L[k_j, j] L[j,j]^{-1}
    Output: u (e_n, t, t) with

        u[e] = sum_j  s_row[e, j] @ g_col[j]

    so that Σ[i_e, j] = -u[e] (core/selinv.py's backward recurrence).  Every
    accumulation feeding one selected-inverse column rides this single
    batched contraction.
    """
    return jnp.einsum("ejab,jbc->eac", s_row, g_col, precision=_HI)


def band_forward_sweep_ref(Dr: jnp.ndarray, R: jnp.ndarray, bd: jnp.ndarray,
                           start_tile=0):
    """Multi-RHS forward band sweep: solve ``L Y = B`` over the band rows,
    one ``solve_panel`` per tile row through a ``lax.fori_loop`` — the
    per-tile-looped semantics the fused Pallas sweep must match.

    Input:  Dr (ndt, bt+1, t, t) row-band factor tiles, Dr[m, j] = L[m, m-j]
            R  (ndt, nat, t, t)  arrow rows, R[m, i] = L[ndt+i, m]
            bd (ndt, t, k)       RHS tile panel
    Output: yd (ndt, t, k)       with L Y = B on the band
            acc_a (nat, t, k)    = sum_m R[m, i] @ Y_m  (arrow-RHS correction)

    ``start_tile`` may be a traced scalar (RHS-sparsity fast start): rows
    above it are left identically zero, and the loop becomes a dynamic-bound
    ``while_loop`` (not reverse-differentiable) only when it is nonzero.
    """
    ndt, b1, t, _ = Dr.shape
    bt = b1 - 1
    k = bd.shape[-1]
    yp = jnp.zeros((ndt + bt, t, k), bd.dtype)  # bt leading zeros

    def step(m, yp):
        # Y_m = Lmm^{-1} (B_m - sum_{j=1..bt} L[m,m-j] Y_{m-j})
        ywin = jax.lax.dynamic_slice(yp, (m, 0, 0), (bt, t, k)) if bt else yp[:0]
        # ywin[bt - j] = Y_{m-j}; Dr[m, j] = L[m, m-j]
        drm = jax.lax.dynamic_slice(Dr, (m, 0, 0, 0), (1, bt + 1, t, t))[0]
        acc = jnp.einsum("jab,jbk->ak", jnp.flip(drm[1:], axis=0), ywin,
                         precision=_HI) if bt else 0.0
        bm = jax.lax.dynamic_slice(bd, (m, 0, 0), (1, t, k))[0]
        ym = solve_panel_ref(drm[0], bm - acc)
        return jax.lax.dynamic_update_slice(yp, ym[None], (m + bt, 0, 0))

    yp = jax.lax.fori_loop(start_tile, ndt, step, yp) if ndt else yp
    yd = yp[bt:]
    acc_a = jnp.einsum("niab,nbk->iak", R, yd, precision=_HI)
    return yd, acc_a


def band_backward_sweep_ref(Dr: jnp.ndarray, R: jnp.ndarray, yd: jnp.ndarray,
                            xa: jnp.ndarray, start_tile=0) -> jnp.ndarray:
    """Multi-RHS backward band sweep: solve ``L^T X = Y - R^T Xa`` over the
    band rows in reverse, one ``solve_panel(trans=True)`` per tile row —
    the per-tile-looped reference for the fused Pallas backward sweep.

    Input:  Dr (ndt, bt+1, t, t), R (ndt, nat, t, t) as in the forward sweep
            yd (ndt, t, k)  forward-solved band panel
            xa (nat, t, k)  already-solved arrow panel
    Output: xd (ndt, t, k) with
            X_m = Lmm^{-T}(Y_m - sum_j L[m+j,m]^T X_{m+j} - sum_i R[m,i]^T Xa_i)

    ``start_tile`` mirrors the forward sweep's RHS-sparsity fast path for
    canonical-grid embeddings (``core/gridpolicy.py``): rows
    ``m < start_tile`` are an identity-diagonal prefix decoupled from the
    rest with zero RHS, so the sweep stops before them and leaves X zero
    there.  May be traced (the loop bound turns dynamic).
    """
    ndt, b1, t, _ = Dr.shape
    bt = b1 - 1
    nat = R.shape[1]
    k = yd.shape[-1]
    Drp = jnp.pad(Dr, ((0, bt), (0, 0), (0, 0), (0, 0)))  # slack for m+j reads
    xp = jnp.zeros((ndt + bt, t, k), yd.dtype)
    jr = jnp.arange(bt)

    def step(i, xp):
        m = ndt - 1 - i
        wb = jax.lax.dynamic_slice(Drp, (m + 1, 0, 0, 0), (bt, bt + 1, t, t)) \
            if bt else Drp[:0]
        # L[m+j, m] = Drp[m+j, j]  -> wb[j-1, j]
        sub = wb[jr, jr + 1] if bt else wb[:, 0]
        xwin = jax.lax.dynamic_slice(xp, (m + 1, 0, 0), (bt, t, k)) if bt else xp[:0]
        acc = jnp.einsum("jab,jak->bk", sub, xwin, precision=_HI) if bt else 0.0
        if nat:
            rm = jax.lax.dynamic_slice(R, (m, 0, 0, 0), (1, nat, t, t))[0]
            acc = acc + jnp.einsum("iab,iak->bk", rm, xa, precision=_HI)
        ym = jax.lax.dynamic_slice(yd, (m, 0, 0), (1, t, k))[0]
        lmm = jax.lax.dynamic_slice(Dr, (m, 0, 0, 0), (1, 1, t, t))[0, 0]
        xm = solve_panel_ref(lmm, ym - acc, trans=True)
        return jax.lax.dynamic_update_slice(xp, xm[None], (m, 0, 0))

    # the sweep walks m = ndt-1 .. start_tile; skipped prefix rows stay zero
    xp = jax.lax.fori_loop(0, ndt - start_tile, step, xp) if ndt else xp
    return xp[:ndt]


def band_cholesky_sweep_ref(Ac: jnp.ndarray, R: jnp.ndarray,
                            nchunks: int = 1, start_tile=0):
    """Whole band+arrow Cholesky sweep: the ring-buffer ``lax.scan``
    (originally ``core/cholesky.py``'s ring sweep) — the per-panel-looped
    semantics the fused Pallas sweep must match.

    Input:  Ac (ndt, bt+1, t, t) column-band tiles, Ac[k, e] = A[k+e, k]
            R  (ndt, nat, t, t)  arrow rows, R[k, i] = A[ndt+i, k]
    Output: panels (ndt, bt+1, t, t)      column panels of L
            R_out  (ndt, nat, t, t)       factored arrow rows
            schur  (nch, nat, nat, t, t)  per-chunk sums of R_out·R_outᵀ
                   (``nch = ring.chunk_layout(ndt, nchunks)[1]`` — the
                   tree-reduction leaves of the corner Schur complement)
            status (3,) float32           breakdown word (:func:`sweep_status`)

    Panel k only ever reads the last bt panels' outputs, so the scan
    carries a (bt, bt+1, t, t) ring of recent panels (plus the arrow
    ring): an O(b²·t²) working set, no scatters.

    ``start_tile`` (may be traced) declares columns ``k < start_tile`` an
    identity-diagonal prefix (the canonical-grid embedding of
    ``core/gridpolicy.py``): their input is *assumed* to be the identity
    embedding column and their output is its factor — an identity panel
    with zero arrow row — without reading the input, matching the fused
    kernel's compute-skip exactly.
    """
    from .ring import chunk_layout

    ndt, b1, t, _ = Ac.shape
    bt = b1 - 1
    nat = R.shape[1]
    skip = not (isinstance(start_tile, int) and start_tile == 0)

    # shifted-gather indices for the ring contraction: for ring slot j-1
    # (panel k-j) pair (offset e+j with offset j)
    jj = jnp.arange(1, bt + 1)                            # (bt,)
    e_idx = jnp.arange(b1)
    src = jnp.clip(e_idx[None, :] + jj[:, None], 0, max(bt, 0))
    valid = (e_idx[None, :] + jj[:, None]) <= bt

    def trsm_batched(lkk, a):
        return jax.vmap(lambda x: trsm_ref(lkk, x))(a)

    def body(carry, xs):
        ring, ring_a = carry                              # (bt,b1,t,t), (bt,nat,t,t)
        if skip:
            # prefix columns: replace the input by the identity embedding
            # column, whose factor the normal step computes NaN-free
            # (potrf(I)=I, trsm(I, 0)=0)
            from .ring import identity_prefix_panel
            a_col, r_col, kk = xs
            id_col = identity_prefix_panel(bt, t, Ac.dtype)
            a_col = jnp.where(kk < start_tile, id_col, a_col)
            r_col = jnp.where(kk < start_tile, jnp.zeros_like(r_col), r_col)
        else:
            a_col, r_col = xs                             # (b1,t,t), (nat,t,t)
        if bt:
            shifted = jnp.take_along_axis(
                ring, src[:, :, None, None], axis=1)      # (bt,b1,t,t)
            shifted = jnp.where(valid[:, :, None, None], shifted, 0.0)
            rhs = ring[jnp.arange(bt), jj]                # (bt,t,t) = P_{k-j}[j]
            u = jnp.einsum("jeab,jcb->eac", shifted, rhs, precision=_HI)
        else:
            u = jnp.zeros_like(a_col)
        lkk = potrf_ref(a_col[0] - u[0])
        lmk = trsm_batched(lkk, a_col[1:] - u[1:]) if bt else a_col[1:]
        panel = jnp.concatenate([lkk[None], lmk], axis=0)
        if nat:
            v = jnp.einsum("jiab,jcb->iac", ring_a, rhs, precision=_HI) \
                if bt else 0.0
            la = trsm_batched(lkk, r_col - v)
        else:
            la = r_col
        if bt:
            ring = jnp.concatenate([panel[None], ring[:-1]], axis=0)
            if nat:
                ring_a = jnp.concatenate([la[None], ring_a[:-1]], axis=0)
        return (ring, ring_a), (panel, la)

    ring0 = jnp.zeros((bt, b1, t, t), Ac.dtype)
    ring_a0 = jnp.zeros((bt, nat, t, t), Ac.dtype)
    xs = (Ac, R, jnp.arange(ndt)) if skip else (Ac, R)
    if ndt:
        _, (panels, R_out) = jax.lax.scan(body, (ring0, ring_a0), xs)
    else:
        panels, R_out = Ac, R

    # per-chunk corner-Schur partial sums (same layout as the fused kernel)
    csz, nch = chunk_layout(ndt, nchunks)
    rpad = jnp.pad(R_out, ((0, nch * csz - ndt), (0, 0), (0, 0), (0, 0)))
    rchunk = rpad.reshape((nch, csz) + R_out.shape[1:])
    schur = jnp.einsum("nkiab,nkjcb->nijac", rchunk, rchunk, precision=_HI)
    return panels, R_out, schur, sweep_status(panels, R_out)


def combine_sweep_status(words: jnp.ndarray) -> jnp.ndarray:
    """Fold per-partition status words into one global word.

    Input:  words (P, 3) — one :func:`sweep_status` word per partition,
            ``first_bad`` already in *global* column indices.
    Output: (3,) — min over pivots, max over nonfinite flags, and the
            smallest non-negative ``first_bad`` (-1 when every partition
            is clean).  An empty stack folds to :func:`empty_sweep_status`.
    """
    if words.shape[0] == 0:
        return empty_sweep_status()
    first = words[:, 2]
    best = jnp.min(jnp.where(first >= 0, first, jnp.inf))
    return jnp.stack([jnp.min(words[:, 0]),
                      jnp.max(words[:, 1]),
                      jnp.where(jnp.isfinite(best), best, -1.0)])


def band_cholesky_partitioned_sweep_ref(Ac: jnp.ndarray, R: jnp.ndarray,
                                        boundaries, start_tile=0):
    """Partition-parallel band+arrow Cholesky sweep — the oracle for the
    2D-grid fused Pallas kernel.

    ``boundaries`` is the static tuple ``(0, c_1, ..., ndt)`` of a
    :class:`~repro.core.ordering.PartitionPlan`: partition ``p`` owns
    diagonal tiles ``[boundaries[p], boundaries[p+1])``, and the input is
    assumed block-separable across those cuts (every band tile crossing a
    boundary is zero — :func:`~repro.core.ordering.detect_partition_plan`
    certifies exactly this).  Each partition then factorizes
    independently: this oracle runs :func:`band_cholesky_sweep_ref` on
    each slice with one Schur chunk per partition and concatenates.

    Output: panels (ndt, b1, t, t), R_out (ndt, nat, t, t) — same layout
            as the unpartitioned sweep;
            schur (P, nat, nat, t, t) — one corner-Schur partial sum per
            partition (the tree-reduction leaves);
            status (3,) — partition words folded by
            :func:`combine_sweep_status`, ``first_bad`` global.

    ``start_tile`` (may be traced) keeps the canonical-grid prefix
    semantics: globally, columns ``k < start_tile`` are the identity
    prefix, so partition ``p`` skips its first
    ``max(0, start_tile - boundaries[p])`` columns.
    """
    ndt = Ac.shape[0]
    bounds = tuple(int(b) for b in boundaries)
    if len(bounds) < 2 or bounds[0] != 0 or bounds[-1] != ndt or \
            any(b1_ <= b0_ for b0_, b1_ in zip(bounds, bounds[1:])):
        raise ValueError(
            f"boundaries {bounds!r} must be strictly increasing from 0 "
            f"to ndt={ndt}")
    static_start = isinstance(start_tile, int)
    panels, r_out, schurs, words = [], [], [], []
    for s0, s1 in zip(bounds, bounds[1:]):
        local_start = max(0, start_tile - s0) if static_start \
            else jnp.maximum(0, start_tile - s0)
        p, r, sch, w = band_cholesky_sweep_ref(
            Ac[s0:s1], R[s0:s1], nchunks=1, start_tile=local_start)
        panels.append(p)
        r_out.append(r)
        schurs.append(sch[0])
        words.append(w.at[2].set(jnp.where(w[2] >= 0, w[2] + s0, -1.0)))
    return (jnp.concatenate(panels, axis=0),
            jnp.concatenate(r_out, axis=0),
            jnp.stack(schurs, axis=0),
            combine_sweep_status(jnp.stack(words, axis=0)))


def selinv_sweep_ref(lcol: jnp.ndarray, R: jnp.ndarray,
                     sc_full: jnp.ndarray, start_tile=0):
    """Whole backward Takahashi recurrence: the Σ-column ring ``lax.scan``
    (originally ``core/selinv.py``'s backward sweep) — the per-column-looped
    semantics the fused Pallas selinv sweep must match.

    Input:  lcol (ndt, bt+1, t, t) column view of the factor,
            lcol[j, d] = L[j+d, j] (zero past ndt)
            R (ndt, nat, t, t) arrow rows, R[j, i] = L[ndt+i, j]
            sc_full (nat, nat, t, t) full (symmetric) corner Σ seed
    Output: panels (ndt, bt+1, t, t)  Σ columns: panels[j, e] = Σ[j+e, j]
            acols  (ndt, nat, t, t)   arrow entries: acols[j, i] = Σ[ndt+i, j]

    Each step contracts the Σ block row visible from column j (band window
    + arrow rows + corner) against the normalized factor column
    G_kj = L_kj L_jj^{-1} (one :func:`selinv_step_ref`), walking columns
    j = ndt-1..0 with a ring of the last bt computed Σ columns.

    ``start_tile`` (may be traced) declares columns ``j < start_tile`` an
    identity-diagonal prefix (canonical-grid embedding): their factor
    column is *assumed* to be the identity embedding column, so their Σ
    panel is the identity (``Σ = blockdiag(I, Σ_orig)``) — emitted without
    reading the input, matching the fused kernel's compute-skip.
    """
    ndt, b1, t, _ = lcol.shape
    bt = b1 - 1
    nat = R.shape[1]
    eye = jnp.eye(t, dtype=lcol.dtype)
    e_i = jnp.arange(1, bt + 1)[:, None]
    d_i = jnp.arange(1, bt + 1)[None, :]
    skip = not (isinstance(start_tile, int) and start_tile == 0)

    def body(carry, xs):
        # ring[s, e'] = Σ_{(j+1+s)+e', j+1+s}; ring_a[s, i] = Σ_{ndt+i, j+1+s}
        ring, ring_a = carry
        if skip:
            # prefix columns (walked last): feed the identity embedding
            # column through the normal step — winv = I, G = 0, so the
            # emitted Σ panel is exactly the identity panel
            from .ring import identity_prefix_panel
            lc, rc, jj = xs
            id_col = identity_prefix_panel(bt, t, lcol.dtype)
            lc = jnp.where(jj < start_tile, id_col, lc)
            rc = jnp.where(jj < start_tile, jnp.zeros_like(rc), rc)
        else:
            lc, rc = xs                                   # (b1,t,t), (nat,t,t)
        ljj = lc[0]
        winv = solve_panel_ref(ljj, eye)                  # L_jj^{-1}
        s0 = jnp.dot(winv.T, winv, precision=_HI)         # (L_jj L_jj^T)^{-1}
        # normalized column: G_d = L_{j+d,j} L_jj^{-1}; arrow Ga_i = R[j,i] L_jj^{-1}
        g = jnp.einsum("dab,bc->dac", lc[1:], winv, precision=_HI)
        ga = jnp.einsum("iab,bc->iac", rc, winv, precision=_HI) if nat \
            else rc
        gcat = jnp.concatenate([g, ga], axis=0)           # (bt+nat, t, t)

        # Σ block row visible from column j, rows (j+1..j+bt, arrow):
        #   band e, band d:  e>=d -> ring[d-1, e-d]; e<d -> ring[e-1, d-e]^T
        #   band e, arrow i: ring_a[e-1, i]^T
        #   arrow i, band d: ring_a[d-1, i];  arrow i, arrow i': Σ_cc[i, i']
        if bt:
            lower = ring[d_i - 1, jnp.clip(e_i - d_i, 0, bt)]
            upper = jnp.swapaxes(ring[e_i - 1, jnp.clip(d_i - e_i, 0, bt)],
                                 -1, -2)
            swin = jnp.where((e_i >= d_i)[:, :, None, None], lower, upper)
            row_band = jnp.concatenate(
                [swin, jnp.swapaxes(ring_a, -1, -2)], axis=1) if nat else swin
        else:
            row_band = jnp.zeros((0, bt + nat, t, t), lcol.dtype)
        if nat:
            row_arr = jnp.concatenate(
                [ring_a.transpose(1, 0, 2, 3), sc_full], axis=1)
            srow = jnp.concatenate([row_band, row_arr], axis=0)
        else:
            srow = row_band

        off = -selinv_step_ref(srow, gcat)                # (bt+nat, t, t)
        # diagonal: Σ_jj = s0 - Σ_{k>j} Σ_kj^T G_kj  (off = the fresh Σ_kj)
        corr = jnp.einsum("kba,kbc->ac", off, gcat, precision=_HI)
        sjj = s0 - corr
        sjj = 0.5 * (sjj + sjj.T)
        panel = jnp.concatenate([sjj[None], off[:bt]], axis=0)   # (b1, t, t)
        acol = off[bt:]                                          # (nat, t, t)
        if bt:
            ring = jnp.concatenate([panel[None], ring[:-1]], axis=0)
            if nat:
                ring_a = jnp.concatenate([acol[None], ring_a[:-1]], axis=0)
        return (ring, ring_a), (panel, acol)

    if ndt == 0:
        return lcol, R
    ring0 = jnp.zeros((bt, b1, t, t), lcol.dtype)
    ring_a0 = jnp.zeros((bt, nat, t, t), lcol.dtype)
    xs = (jnp.flip(lcol, 0), jnp.flip(R, 0))
    if skip:
        xs = xs + (jnp.flip(jnp.arange(ndt)),)
    _, (panels_rev, acols_rev) = jax.lax.scan(body, (ring0, ring_a0), xs)
    return jnp.flip(panels_rev, 0), jnp.flip(acols_rev, 0)

