"""Numerical factorization: both backends vs dense Cholesky, solves,
logdet, sampling, tree reduction."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BandedCTSF, TileGrid, TileMatrix, factorize_tasklist,
                        factorize_window, forward_solve, backward_solve,
                        logdet, sample_gmrf, solve)
from repro.data import make_arrowhead
from repro.core.options import SolverOptions

CASES = [
    # (n, bandwidth, arrow, tile, rho)
    (200, 24, 16, 16, 0.7),      # classic arrowhead
    (200, 24, 16, 16, 0.0),      # block-diagonal + arrow (paper ids 1,7,..)
    (160, 8, 0, 16, 0.5),        # pure band, no arrow
    (130, 40, 30, 16, 0.6),      # thick arrow, wide band (uneven tiles)
    (96, 90, 0, 32, 0.4),        # nearly dense band
]


def _setup(n, bw, ar, t, rho, seed=0):
    A, st = make_arrowhead(n, bw, ar, rho=rho, seed=seed)
    g = TileGrid(st, t=t)
    bm = BandedCTSF.from_sparse(A, g)
    dense = bm.to_dense(lower_only=False)
    return A, g, bm, dense


@pytest.mark.parametrize("n,bw,ar,t,rho", CASES)
def test_window_backend_matches_dense(n, bw, ar, t, rho):
    A, g, bm, dense = _setup(n, bw, ar, t, rho)
    f = factorize_window(bm)
    Lref = np.linalg.cholesky(dense)
    err = np.abs(f.ctsf.to_dense() - np.tril(Lref)).max()
    assert err < 1e-3 * max(1.0, np.abs(Lref).max())


@pytest.mark.parametrize("n,bw,ar,t,rho", CASES[:3])
def test_tasklist_backend_matches_dense(n, bw, ar, t, rho):
    A, g, bm, dense = _setup(n, bw, ar, t, rho)
    tm = TileMatrix.from_sparse(A, g)
    tiles = factorize_tasklist(tm)
    Lref = np.linalg.cholesky(dense)
    err = np.abs(np.tril(tm.to_dense(tiles)) - np.tril(Lref)).max()
    assert err < 1e-3 * max(1.0, np.abs(Lref).max())


def test_backends_agree():
    A, g, bm, dense = _setup(200, 24, 16, 16, 0.7)
    f = factorize_window(bm)
    tm = TileMatrix.from_sparse(A, g)
    tiles = factorize_tasklist(tm)
    assert np.allclose(np.tril(tm.to_dense(tiles)), f.ctsf.to_dense(),
                       atol=5e-4)


def test_tree_reduction_equivalent():
    """Alg. 3 changes association order only (paper §IV-A)."""
    A, g, bm, dense = _setup(200, 24, 16, 16, 0.7)
    f1 = factorize_window(bm, tree_chunks=1)
    f8 = factorize_window(bm, tree_chunks=8)
    assert np.allclose(f1.ctsf.to_dense(), f8.ctsf.to_dense(), atol=1e-4)
    tm = TileMatrix.from_sparse(A, g)
    t_seq = factorize_tasklist(tm, tree_reduction=False)
    t_tree = factorize_tasklist(tm, tree_reduction=True, tree_workers=4)
    assert np.allclose(np.asarray(t_seq), np.asarray(t_tree), atol=1e-4)


def test_solve_and_logdet():
    A, g, bm, dense = _setup(200, 24, 16, 16, 0.7)
    f = factorize_window(bm)
    rng = np.random.default_rng(0)
    b = rng.standard_normal(g.padded_n).astype(np.float32)
    x = solve(f, jnp.asarray(b))
    xref = np.linalg.solve(dense, b)
    assert np.abs(np.asarray(x) - xref).max() < 1e-3 * np.abs(xref).max()
    sign, ldref = np.linalg.slogdet(dense)
    assert sign > 0
    assert abs(float(logdet(f)) - ldref) < 1e-2 * abs(ldref)


def test_forward_backward_are_triangular_solves():
    A, g, bm, dense = _setup(160, 8, 16, 16, 0.5)
    f = factorize_window(bm)
    L = np.tril(f.ctsf.to_dense())
    rng = np.random.default_rng(1)
    b = rng.standard_normal(g.padded_n).astype(np.float32)
    y = forward_solve(f, jnp.asarray(b))
    yref = np.linalg.solve(L, b)
    assert np.abs(np.asarray(y) - yref).max() < 1e-3 * np.abs(yref).max()
    x = backward_solve(f, jnp.asarray(y))
    xref = np.linalg.solve(L.T, np.asarray(y))
    assert np.abs(np.asarray(x) - xref).max() < 1e-3 * np.abs(xref).max()


def test_gmrf_sampling_covariance():
    """x = L^{-T} z has covariance A^{-1}: check via quadratic forms."""
    A, g, bm, dense = _setup(96, 8, 16, 16, 0.5)
    f = factorize_window(bm)
    keys = jax.random.split(jax.random.PRNGKey(0), 256)
    xs = np.stack([np.asarray(sample_gmrf(f, k)) for k in keys])
    emp = xs.T @ xs / xs.shape[0]
    cov = np.linalg.inv(dense)
    # loose statistical check on the dominant entries
    scale = np.abs(cov).max()
    assert np.abs(emp - cov).max() < 12 * scale / np.sqrt(xs.shape[0])


def test_pallas_impl_matches_ref_end_to_end():
    """impl="pallas" now rides the single-launch fused band-Cholesky sweep
    (the dispatch picks "fused" on the Pallas backend)."""
    A, g, bm, dense = _setup(128, 16, 16, 16, 0.6)
    f_ref = factorize_window(bm, options=SolverOptions(impl="ref"))
    f_pl = factorize_window(bm, options=SolverOptions(impl="pallas"))
    assert np.allclose(f_ref.ctsf.to_dense(), f_pl.ctsf.to_dense(), atol=2e-4)


@pytest.mark.parametrize("n,bw,ar,t,rho", CASES)
def test_fused_sweep_matches_dense(n, bw, ar, t, rho):
    """The one-launch factorization (impl="pallas") is a drop-in for the
    scan path (impl="ref") on every grid shape, not just where Pallas is
    the default."""
    A, g, bm, dense = _setup(n, bw, ar, t, rho)
    f = factorize_window(bm, options=SolverOptions(impl="pallas"))
    Lref = np.linalg.cholesky(dense)
    err = np.abs(f.ctsf.to_dense() - np.tril(Lref)).max()
    assert err < 1e-3 * max(1.0, np.abs(Lref).max())
    f_ring = factorize_window(bm, options=SolverOptions(impl="ref"))
    assert np.allclose(f.ctsf.to_dense(), f_ring.ctsf.to_dense(), atol=2e-4)


def test_factorize_window_batched_rides_fused_sweep():
    """End-to-end through the batched θ-sweep entry point: impl="pallas"
    (fused kernel under vmap) matches the looped ref factorizations."""
    from repro.core import factorize_window_batched
    mats = []
    for s in range(3):
        A, g, bm, dense = _setup(160, 8, 16, 16, 0.5, seed=s)
        mats.append(bm)
    fb = factorize_window_batched(mats, options=SolverOptions(impl="pallas"))    # bucket pads 3 -> 4
    assert fb.ctsf.Dr.shape[0] == 3
    for i, m in enumerate(mats):
        fi = factorize_window(m, options=SolverOptions(impl="ref"))
        np.testing.assert_allclose(np.asarray(fb.ctsf.Dr[i]),
                                   np.asarray(fi.ctsf.Dr),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(fb.ctsf.R[i]),
                                   np.asarray(fi.ctsf.R),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(fb.ctsf.C[i]),
                                   np.asarray(fi.ctsf.C),
                                   rtol=2e-4, atol=2e-4)


# degenerate grids: single diag tile (bt=0), bt=0 + arrow, pure band
# (nat=0), and a single-tile matrix — the task-list backend's tree
# reduction was previously only exercised on the default grids
DEGENERATE_CASES = [
    (16, 4, 0, 16),      # one diagonal tile, no arrow (bt=0, nat=0)
    (30, 6, 14, 16),     # one diagonal tile + arrow (bt=0, nat=1)
    (64, 7, 0, 16),      # multi-tile pure band (nat=0)
    (48, 30, 12, 16),    # wide band + arrow, uneven tiles
]


@pytest.mark.parametrize("n,bw,ar,t", DEGENERATE_CASES)
def test_tasklist_tree_reduction_degenerate_grids(n, bw, ar, t):
    """factorize_tasklist(tree_reduction=True) parity against
    factorize_window across the degenerate grids."""
    A, g, bm, dense = _setup(n, bw, ar, t, 0.6)
    fw = factorize_window(bm)
    tm = TileMatrix.from_sparse(A, g)
    tiles = factorize_tasklist(tm, tree_reduction=True, tree_workers=4)
    assert np.allclose(np.tril(tm.to_dense(tiles)), fw.ctsf.to_dense(),
                       atol=5e-4)
    # and against the dense oracle directly
    Lref = np.linalg.cholesky(dense)
    err = np.abs(np.tril(tm.to_dense(tiles)) - np.tril(Lref)).max()
    assert err < 1e-3 * max(1.0, np.abs(Lref).max())
