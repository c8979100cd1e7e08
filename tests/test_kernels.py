"""Per-kernel validation: sweep shapes/dtypes, assert allclose vs the
pure-jnp oracles in kernels/ref.py (Pallas in interpret mode on CPU)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.band_cholesky import band_cholesky_sweep_pallas
from repro.kernels.band_solve import (band_backward_sweep_pallas,
                                      band_forward_sweep_pallas)
from repro.kernels.gemm import gemm_pallas, geadd_pallas, syrk_pallas
from repro.kernels.potrf import potrf_pallas
from repro.kernels.ring import band_row_to_col
from repro.kernels.selinv import selinv_step_pallas, selinv_sweep_pallas
from repro.kernels.trsm import trsm_pallas
from repro.core.options import SolverOptions

TILES = [8, 16, 32, 64]
DTYPES = [jnp.float32]


def _spd(rng, t, dtype):
    a = rng.standard_normal((t, t)).astype(np.float32)
    return jnp.asarray(a @ a.T + t * np.eye(t), dtype)


@pytest.mark.parametrize("t", TILES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_potrf(rng, t, dtype):
    a = _spd(rng, t, dtype)
    out = potrf_pallas(a, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.potrf_ref(a)),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("t", TILES)
def test_potrf_batched(rng, t):
    a = jnp.stack([_spd(rng, t, jnp.float32) for _ in range(3)])
    out = potrf_pallas(a, interpret=True)
    for i in range(3):
        np.testing.assert_allclose(np.asarray(out[i]),
                                   np.asarray(ref.potrf_ref(a[i])),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("t", TILES)
def test_trsm(rng, t):
    l = ref.potrf_ref(_spd(rng, t, jnp.float32))
    a = jnp.asarray(rng.standard_normal((t, t)), jnp.float32)
    np.testing.assert_allclose(np.asarray(trsm_pallas(l, a, interpret=True)),
                               np.asarray(ref.trsm_ref(l, a)),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("t", TILES)
def test_trsm_batched(rng, t):
    l = ref.potrf_ref(_spd(rng, t, jnp.float32))
    a = jnp.asarray(rng.standard_normal((4, t, t)), jnp.float32)
    out = trsm_pallas(l, a, interpret=True)
    for i in range(4):
        np.testing.assert_allclose(np.asarray(out[i]),
                                   np.asarray(ref.trsm_ref(l, a[i])),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("t", TILES)
@pytest.mark.parametrize("kblock", [8, 64])
def test_gemm_syrk(rng, t, kblock):
    c = jnp.asarray(rng.standard_normal((t, t)), jnp.float32)
    a = jnp.asarray(rng.standard_normal((t, t)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((t, t)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(gemm_pallas(c, a, b, kblock=kblock, interpret=True)),
        np.asarray(ref.gemm_ref(c, a, b)), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(syrk_pallas(c, a, kblock=kblock, interpret=True)),
        np.asarray(ref.syrk_ref(c, a)), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("t", TILES)
def test_geadd(rng, t):
    a = jnp.asarray(rng.standard_normal((5, t, t)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((5, t, t)), jnp.float32)
    np.testing.assert_allclose(np.asarray(geadd_pallas(a, b, interpret=True)),
                               np.asarray(a + b), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("e_n,j_n", [(1, 1), (3, 5), (4, 9), (2, 17)])
@pytest.mark.parametrize("t", [8, 16, 32])
@pytest.mark.parametrize("jblock", [2, 8])
def test_selinv_step(rng, e_n, j_n, t, jblock):
    s = jnp.asarray(rng.standard_normal((e_n, j_n, t, t)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((j_n, t, t)), jnp.float32)
    out = selinv_step_pallas(s, g, jblock=jblock, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.selinv_step_ref(s, g)),
                               rtol=2e-4, atol=2e-4)


def test_selinv_step_empty():
    s = jnp.zeros((0, 3, 8, 8), jnp.float32)
    g = jnp.zeros((3, 8, 8), jnp.float32)
    assert selinv_step_pallas(s, g, interpret=True).shape == (0, 8, 8)
    s2 = jnp.zeros((2, 0, 8, 8), jnp.float32)
    g2 = jnp.zeros((0, 8, 8), jnp.float32)
    assert np.abs(np.asarray(selinv_step_pallas(s2, g2, interpret=True))).max() == 0.0


def _band_factor(rng, ndt, bt, nat, t):
    """Random row-band factor tiles with the BandedCTSF conventions:
    well-conditioned lower-triangular diagonal tiles, structural zeros
    above the band (Dr[m, j] = 0 for j > m)."""
    Dr = rng.standard_normal((ndt, bt + 1, t, t)).astype(np.float32)
    for m in range(ndt):
        Dr[m, 0] = np.tril(Dr[m, 0]) + t * np.eye(t)
        Dr[m, min(m, bt) + 1:] = 0.0
    R = rng.standard_normal((ndt, nat, t, t)).astype(np.float32)
    return jnp.asarray(Dr), jnp.asarray(R)


# grids cover: single tile (bt=0), no arrow, bandwidth > 1, deep band
SWEEP_GRIDS = [(1, 0, 0), (5, 1, 0), (6, 2, 2), (9, 4, 1)]
# the band solves' diagonal tiles: the t-step substitution at t = 8, the
# blocked inverse and one product at t = 64 (``potrf.tile_block`` 32)
SOLVE_TILES = [8, 64]


@pytest.mark.parametrize("t", SOLVE_TILES)
@pytest.mark.parametrize("ndt,bt,nat", SWEEP_GRIDS)
@pytest.mark.parametrize("k", [1, 13])
def test_band_forward_sweep(rng, ndt, bt, nat, k, t):
    Dr, R = _band_factor(rng, ndt, bt, nat, t)
    bd = jnp.asarray(rng.standard_normal((ndt, t, k)), jnp.float32)
    yd, acca = band_forward_sweep_pallas(Dr, R, bd, interpret=True)
    yr, accr = ref.band_forward_sweep_ref(Dr, R, bd)
    np.testing.assert_allclose(np.asarray(yd), np.asarray(yr),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(acca), np.asarray(accr),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("t", SOLVE_TILES)
@pytest.mark.parametrize("start_tile", [1, 3, 6])
def test_band_forward_sweep_start_tile(rng, start_tile, t):
    """Rows above start_tile come out identically zero on both backends,
    even when the RHS is nonzero there (the reference never writes them)."""
    ndt, bt, nat, k = 7, 2, 1, 4
    Dr, R = _band_factor(rng, ndt, bt, nat, t)
    bd = jnp.asarray(rng.standard_normal((ndt, t, k)), jnp.float32)
    yd, acca = band_forward_sweep_pallas(Dr, R, bd, start_tile=start_tile,
                                           interpret=True)
    yr, accr = ref.band_forward_sweep_ref(Dr, R, bd, start_tile=start_tile)
    np.testing.assert_allclose(np.asarray(yd), np.asarray(yr),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(acca), np.asarray(accr),
                               rtol=2e-4, atol=2e-4)
    assert np.abs(np.asarray(yd[:start_tile])).max() == 0.0


@pytest.mark.parametrize("t", SOLVE_TILES)
@pytest.mark.parametrize("ndt,bt,nat", SWEEP_GRIDS)
@pytest.mark.parametrize("k", [1, 13])
def test_band_backward_sweep(rng, ndt, bt, nat, k, t):
    Dr, R = _band_factor(rng, ndt, bt, nat, t)
    yd = jnp.asarray(rng.standard_normal((ndt, t, k)), jnp.float32)
    xa = jnp.asarray(rng.standard_normal((nat, t, k)), jnp.float32)
    xd = band_backward_sweep_pallas(Dr, R, yd, xa, interpret=True)
    xr = ref.band_backward_sweep_ref(Dr, R, yd, xa)
    np.testing.assert_allclose(np.asarray(xd), np.asarray(xr),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("t", SOLVE_TILES)
def test_band_sweeps_vmap(rng, t):
    """Batched factors (concurrent_solve's shape) ride the fused kernels
    through jax.vmap; the shared RHS panel is broadcast."""
    ndt, bt, nat, k, nb = 6, 2, 1, 5, 3
    Drs, Rs = zip(*[_band_factor(rng, ndt, bt, nat, t) for _ in range(nb)])
    Drb, Rb = jnp.stack(Drs), jnp.stack(Rs)
    bd = jnp.asarray(rng.standard_normal((ndt, t, k)), jnp.float32)
    xa = jnp.asarray(rng.standard_normal((nat, t, k)), jnp.float32)
    yb, ab = jax.vmap(lambda d, r: band_forward_sweep_pallas(
        d, r, bd, interpret=True))(Drb, Rb)
    xb = jax.vmap(lambda d, r: band_backward_sweep_pallas(
        d, r, bd, xa, interpret=True))(Drb, Rb)
    for i in range(nb):
        yr, ar = ref.band_forward_sweep_ref(Drb[i], Rb[i], bd)
        np.testing.assert_allclose(np.asarray(yb[i]), np.asarray(yr),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(ab[i]), np.asarray(ar),
                                   rtol=2e-4, atol=2e-4)
        xr = ref.band_backward_sweep_ref(Drb[i], Rb[i], bd, xa)
        np.testing.assert_allclose(np.asarray(xb[i]), np.asarray(xr),
                                   rtol=2e-4, atol=2e-4)


def test_band_sweep_ref_semantics(rng):
    """Cross-check the sweep reference against naive per-row substitution."""
    import scipy.linalg
    ndt, bt, nat, t, k = 5, 2, 1, 8, 3
    Dr, R = _band_factor(rng, ndt, bt, nat, t)
    bd = rng.standard_normal((ndt, t, k)).astype(np.float32)
    Drn, Rn = np.asarray(Dr), np.asarray(R)
    want = np.zeros((ndt, t, k), np.float32)
    for m in range(ndt):
        acc = sum(Drn[m, j] @ want[m - j] for j in range(1, min(m, bt) + 1))
        want[m] = scipy.linalg.solve_triangular(Drn[m, 0], bd[m] - acc,
                                                lower=True)
    want_acc = np.einsum("niab,nbk->iak", Rn, want)
    yd, acca = ref.band_forward_sweep_ref(Dr, R, jnp.asarray(bd))
    np.testing.assert_allclose(np.asarray(yd), want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(acca), want_acc, rtol=2e-4, atol=2e-4)


def _spd_ctsf(n, bw, ar, t, seed=0):
    """A real SPD banded-arrowhead CTSF (the fused factorization/selinv
    sweeps need genuinely factorizable inputs, unlike the solve sweeps)."""
    from repro.core import BandedCTSF, TileGrid
    from repro.data import make_arrowhead
    A, st = make_arrowhead(n, bw, ar, rho=0.6, seed=seed)
    grid = TileGrid(st, t=t)
    return BandedCTSF.from_sparse(A, grid), grid


def _corner_sigma(C, nat, t):
    """Dense corner seed Σ_cc = L_c^{-T} L_c^{-1} (mirrors core/selinv.py)."""
    if not nat:
        return jnp.zeros((0, 0, t, t), C.dtype)
    nc = nat * t
    cd = C.transpose(0, 2, 1, 3).reshape(nc, nc)
    winv = jax.scipy.linalg.solve_triangular(
        cd, jnp.eye(nc, dtype=C.dtype), lower=True)
    return jnp.dot(winv.T, winv).reshape(nat, t, nat, t).transpose(0, 2, 1, 3)


# grids cover: single tile (bt=0), bt=0 + arrow, nat=0 with bt=1, thick
# arrow / wide band, deep band with small tiles; then every band width
# bt in {1, 2, 4, 8} at every tile size t in {8, 16, 32}, full bands of
# bt + 3 columns, six of them with one or two arrow tiles — the in-VMEM
# left-looking update at each (bt, t)
CHOLESKY_GRIDS = [(16, 4, 0, 16), (30, 6, 14, 16), (160, 8, 0, 16),
                  (130, 40, 30, 16), (96, 40, 16, 8),
                  (34, 7, 6, 8), (112, 31, 0, 32),
                  (36, 15, 0, 8), (84, 31, 12, 16), (200, 63, 56, 32),
                  (66, 31, 14, 8), (104, 63, 0, 16), (232, 127, 24, 32),
                  (84, 63, 0, 8), (196, 127, 28, 16), (336, 255, 0, 32)]


@pytest.mark.parametrize("n,bw,ar,t", CHOLESKY_GRIDS)
@pytest.mark.parametrize("nchunks", [1, 3])
def test_band_cholesky_sweep(n, bw, ar, t, nchunks):
    """One-launch factorization matches the ring-scan oracle: panels,
    factored arrow rows and the per-chunk corner-Schur partial sums."""
    bm, grid = _spd_ctsf(n, bw, ar, t)
    Ac = band_row_to_col(bm.Dr)
    got = band_cholesky_sweep_pallas(Ac, bm.R, nchunks=nchunks,
                                     interpret=True)
    want = ref.band_cholesky_sweep_ref(Ac, bm.R, nchunks=nchunks)
    for g, w, name in zip(got, want, ("panels", "R_out", "schur", "status")):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_band_cholesky_sweep_vmap(rng):
    """Batched matrices (factorize_window_batched's shape) ride the fused
    kernel through jax.vmap."""
    mats = [_spd_ctsf(130, 40, 30, 16, seed=s)[0] for s in range(3)]
    Acb = jnp.stack([band_row_to_col(m.Dr) for m in mats])
    Rb = jnp.stack([m.R for m in mats])
    got = jax.vmap(lambda a, r: band_cholesky_sweep_pallas(
        a, r, nchunks=2, interpret=True))(
        Acb, Rb)
    for i in range(3):
        want = ref.band_cholesky_sweep_ref(Acb[i], Rb[i], nchunks=2)
        for g, w, name in zip(got, want, ("panels", "R_out", "schur", "status")):
            np.testing.assert_allclose(np.asarray(g[i]), np.asarray(w),
                                       rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("n,bw,ar,t", CHOLESKY_GRIDS)
def test_selinv_sweep(n, bw, ar, t):
    """One-launch Takahashi recurrence matches the per-column scan oracle."""
    from repro.core import factorize_window
    bm, grid = _spd_ctsf(n, bw, ar, t)
    f = factorize_window(bm, options=SolverOptions(impl="ref")).ctsf
    lcol = band_row_to_col(f.Dr)
    sc = _corner_sigma(f.C, grid.n_arrow_tiles, t)
    gp, ga = selinv_sweep_pallas(lcol, f.R, sc, interpret=True)
    wp, wa = ref.selinv_sweep_ref(lcol, f.R, sc)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(wp),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(wa),
                               rtol=2e-4, atol=2e-4)


def test_selinv_sweep_vmap():
    from repro.core import factorize_window
    facs, grids = zip(*[(_spd_ctsf(96, 40, 16, 8, seed=s)) for s in range(2)])
    fs = [factorize_window(m, options=SolverOptions(impl="ref")).ctsf for m in facs]
    lcolb = jnp.stack([band_row_to_col(f.Dr) for f in fs])
    Rb = jnp.stack([f.R for f in fs])
    scb = jnp.stack([_corner_sigma(f.C, grids[0].n_arrow_tiles, 8)
                     for f in fs])
    gp, ga = jax.vmap(functools.partial(selinv_sweep_pallas,
                                        interpret=True))(lcolb, Rb, scb)
    for i in range(2):
        wp, wa = ref.selinv_sweep_ref(lcolb[i], Rb[i], scb[i])
        np.testing.assert_allclose(np.asarray(gp[i]), np.asarray(wp),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(ga[i]), np.asarray(wa),
                                   rtol=2e-4, atol=2e-4)


def test_fused_sweeps_are_single_launch():
    """The whole factorization / selinv recurrence is exactly one Pallas
    launch (vs 3·ndt / 2·ndt per-panel dispatches for the scan paths).
    Uses the same jaxpr counter the CI launch-count gate gates on."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    try:
        from benchmarks.bench_cholesky import count_pallas_launches
    finally:
        sys.path.pop(0)
    _count_pallas_calls = count_pallas_launches
    bm, grid = _spd_ctsf(130, 40, 30, 16)
    Ac = band_row_to_col(bm.Dr)
    jx = jax.make_jaxpr(
        lambda a, r: band_cholesky_sweep_pallas(a, r, nchunks=4,
                                                interpret=True))(Ac, bm.R)
    assert _count_pallas_calls(jx) == 1
    sc = jnp.zeros((2, 2, 16, 16), jnp.float32)   # tracing only needs shapes
    jx2 = jax.make_jaxpr(functools.partial(selinv_sweep_pallas,
                                         interpret=True))(Ac, bm.R, sc)
    assert _count_pallas_calls(jx2) == 1


@pytest.mark.parametrize("start_tile", [2, 5])
def test_band_cholesky_sweep_start_tile(start_tile):
    """With a start_tile prefix, both backends emit identity panels / zero
    arrow rows for the prefix and the exact factor of the identity-embedded
    matrix elsewhere — the canonical-grid embedding contract
    (core/gridpolicy.py)."""
    from repro.core import embed_ctsf, GridBucketPolicy, TileGrid
    bm, grid = _spd_ctsf(96, 16, 8, 8)
    cgrid = TileGrid.from_tile_counts(
        8, grid.n_diag_tiles + start_tile, grid.band_tiles,
        grid.n_arrow_tiles)
    emb = embed_ctsf(bm, cgrid)
    Ac = band_row_to_col(emb.Dr)
    # traced start (as the serving path passes it) and both backends
    st = jnp.asarray(start_tile, jnp.int32)
    got = band_cholesky_sweep_pallas(Ac, emb.R, nchunks=3, start_tile=st,
                                     interpret=True)
    want = ref.band_cholesky_sweep_ref(Ac, emb.R, nchunks=3, start_tile=st)
    for g, w, name in zip(got, want, ("panels", "R_out", "schur", "status")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4, err_msg=name)
    panels = np.asarray(got[0])
    np.testing.assert_allclose(panels[:start_tile, 0],
                               np.broadcast_to(np.eye(8), (start_tile, 8, 8)),
                               atol=1e-6)
    assert np.abs(panels[:start_tile, 1:]).max() == 0.0
    # prefix skip leaves the suffix identical to the unembedded sweep
    plain = ref.band_cholesky_sweep_ref(band_row_to_col(bm.Dr), bm.R)
    np.testing.assert_allclose(panels[start_tile:], np.asarray(plain[0]),
                               rtol=2e-4, atol=2e-4)


def test_selinv_sweep_start_tile():
    """Prefix columns of the fused/ref Takahashi sweeps emit identity Σ
    panels (Σ_embedded = blockdiag(I, Σ)); the suffix matches the
    unembedded recurrence."""
    from repro.core import embed_ctsf, factorize_window, TileGrid
    bm, grid = _spd_ctsf(96, 16, 8, 8)
    pad = 3
    cgrid = TileGrid.from_tile_counts(
        8, grid.n_diag_tiles + pad, grid.band_tiles, grid.n_arrow_tiles)
    f = factorize_window(embed_ctsf(bm, cgrid), options=SolverOptions(impl="ref")).ctsf
    lcol = band_row_to_col(f.Dr)
    sc = _corner_sigma(f.C, cgrid.n_arrow_tiles, 8)
    st = jnp.asarray(pad, jnp.int32)
    gp, ga = selinv_sweep_pallas(lcol, f.R, sc, start_tile=st,
                                 interpret=True)
    wp, wa = ref.selinv_sweep_ref(lcol, f.R, sc, start_tile=st)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(wp),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(wa),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(gp)[:pad, 0],
                               np.broadcast_to(np.eye(8), (pad, 8, 8)),
                               atol=1e-6)
    assert np.abs(np.asarray(gp)[:pad, 1:]).max() == 0.0
    f0 = factorize_window(bm, options=SolverOptions(impl="ref")).ctsf
    wp0, _ = ref.selinv_sweep_ref(band_row_to_col(f0.Dr), f0.R,
                                  _corner_sigma(f0.C, grid.n_arrow_tiles, 8))
    np.testing.assert_allclose(np.asarray(gp)[pad:], np.asarray(wp0),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("start_tile", [1, 4])
def test_band_backward_sweep_start_tile(rng, start_tile):
    """Rows below start_tile come out identically zero on both backends
    (the reverse-sweep mirror of the forward fast start)."""
    ndt, bt, nat, t, k = 7, 2, 1, 8, 4
    Dr, R = _band_factor(rng, ndt, bt, nat, t)
    yd = jnp.asarray(rng.standard_normal((ndt, t, k)), jnp.float32)
    xa = jnp.asarray(rng.standard_normal((nat, t, k)), jnp.float32)
    st = jnp.asarray(start_tile, jnp.int32)
    xd = band_backward_sweep_pallas(Dr, R, yd, xa, start_tile=st,
                                    interpret=True)
    xr = ref.band_backward_sweep_ref(Dr, R, yd, xa, start_tile=st)
    np.testing.assert_allclose(np.asarray(xd), np.asarray(xr),
                               rtol=2e-4, atol=2e-4)
    assert np.abs(np.asarray(xd)[:start_tile]).max() == 0.0
    # rows >= start_tile agree with the full sweep (suffix decouples
    # upward: X_m only reads X_{m+j}, never the skipped prefix)
    xfull = ref.band_backward_sweep_ref(Dr, R, yd, xa)
    np.testing.assert_allclose(np.asarray(xd)[start_tile:],
                               np.asarray(xfull)[start_tile:],
                               rtol=2e-4, atol=2e-4)


def _near_singular_ctsf(n, bw, ar, t, cond=1e4):
    """A banded-arrowhead CTSF whose cond(Q) is about ``cond``: the
    smallest eigenvalue of :func:`near_singular_arrowhead` is set from the
    spread of the spectrum, so cond(L_kk) reaches about sqrt(cond)."""
    from repro.core import BandedCTSF, TileGrid
    from repro.data import near_singular_arrowhead
    A1, _ = near_singular_arrowhead(n, bw, ar, seed=0, eig_min=1.0)
    spread = np.linalg.eigvalsh(A1.toarray())[-1] - 1.0
    A, st = near_singular_arrowhead(n, bw, ar, seed=0,
                                    eig_min=spread / (cond - 1.0))
    ev = np.linalg.eigvalsh(A.toarray())
    assert 0.5 * cond < ev[-1] / ev[0] < 2.0 * cond
    grid = TileGrid(st, t=t)
    return BandedCTSF.from_sparse(A, grid), grid


def _factor_error(panels, r_out, L, ndt, t):
    """Frobenius error of the emitted band panels and arrow rows against
    the float64 dense factor ``L``, over the norm of the same tiles of L."""
    panels = np.asarray(panels, np.float64)
    r_out = np.asarray(r_out, np.float64)
    got, want = [], []
    for k in range(ndt):
        blk = slice(k * t, (k + 1) * t)
        for e in range(panels.shape[1]):
            row = (k + e) * t
            ref_tile = (L[row:row + t, blk] if k + e < ndt
                        else np.zeros((t, t)))
            got.append(panels[k, e])
            want.append(ref_tile)
        for i in range(r_out.shape[1]):
            row = (ndt + i) * t
            got.append(r_out[k, i])
            want.append(L[row:row + t, blk])
    got, want = np.stack(got), np.stack(want)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("sweep", ["plain", "partitioned"])
@pytest.mark.parametrize("nat", [0, 1, 2])
@pytest.mark.parametrize("bt", [0, 2, 3])
def test_band_cholesky_sweep_accuracy_poorly_conditioned(bt, nat, sweep):
    """On a band with cond(Q) about 1e4 the fused sweep, which applies
    L_kk^{-1} to each column's tiles by one MXU product, is no less accurate
    against a float64 dense Cholesky than the ``ref`` backend, which
    substitutes each tile against L_kk: at most twice its factor error.
    The partitioned kernel runs a one-partition plan: a cut through a
    near-singular band would change the matrix."""
    from repro.kernels.band_cholesky import (
        band_cholesky_partitioned_sweep_pallas)
    t = 8
    nd, bw = {0: (8, 3), 2: (64, 12), 3: (64, 20)}[bt]
    bm, grid = _near_singular_ctsf(nd + 6 * nat, bw, 6 * nat, t)
    ndt = grid.n_diag_tiles
    assert (grid.band_tiles, grid.n_arrow_tiles) == (bt, nat)
    L = np.linalg.cholesky(bm.to_dense(lower_only=False).astype(np.float64))
    Ac = band_row_to_col(bm.Dr)
    if sweep == "plain":
        got = band_cholesky_sweep_pallas(Ac, bm.R, interpret=True)
        want = ref.band_cholesky_sweep_ref(Ac, bm.R)
    else:
        got = band_cholesky_partitioned_sweep_pallas(Ac, bm.R, (0, ndt),
                                                     interpret=True)
        want = ref.band_cholesky_partitioned_sweep_ref(Ac, bm.R, (0, ndt))
    err = _factor_error(got[0], got[1], L, ndt, t)
    err_ref = _factor_error(want[0], want[1], L, ndt, t)
    assert np.isfinite(err) and err <= 2.0 * err_ref, (err, err_ref)


@pytest.mark.parametrize("sweep", ["plain", "stream"])
def test_band_cholesky_sweep_accuracy_poorly_conditioned_t128(sweep):
    """The check above at t = 128, where each column's diagonal tile is
    factored and inverted by 32-row blocks (``potrf.tile_block``): on a
    band with cond(Q) about 1e4 the ring and streamed sweeps err at most
    twice as much as the ``ref`` backend against a float64 Cholesky."""
    from repro.kernels.band_cholesky import band_cholesky_stream_sweep_pallas
    from repro.kernels.potrf import tile_block
    t = 128
    assert tile_block(t) < t
    bm, grid = _near_singular_ctsf(5 * t + 64, 2 * t - 8, 64, t)
    ndt = grid.n_diag_tiles
    assert (ndt, grid.band_tiles, grid.n_arrow_tiles) == (5, 2, 1)
    L = np.linalg.cholesky(bm.to_dense(lower_only=False).astype(np.float64))
    Ac = band_row_to_col(bm.Dr)
    sweep_fn = {"plain": band_cholesky_sweep_pallas,
                "stream": band_cholesky_stream_sweep_pallas}[sweep]
    got = sweep_fn(Ac, bm.R, interpret=True)
    want = ref.band_cholesky_sweep_ref(Ac, bm.R)
    err = _factor_error(got[0], got[1], L, ndt, t)
    err_ref = _factor_error(want[0], want[1], L, ndt, t)
    assert np.isfinite(err) and err <= 2.0 * err_ref, (err, err_ref)
