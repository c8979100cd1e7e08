"""The blocked diagonal-tile routines of the Cholesky sweeps' column
finish (``potrf.factorize_tile``, ``trsm.invert_lower_tile``) as plain
jnp, and the sweeps that run them at t = 128 in Pallas interpret mode.

The block is chosen from the tile size alone (``potrf.tile_block``):
tiles of t >= 64 whose size 32 divides are factored and inverted by
32-row blocks, smaller tiles keep the unblocked loops."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core.structure import TileGrid
from repro.data import make_arrowhead, near_singular_arrowhead
from repro.kernels import ref
from repro.kernels.band_cholesky import (band_cholesky_stream_sweep_pallas,
                                         band_cholesky_sweep_pallas)
from repro.kernels.potrf import (_factorize_unblocked, factorize_tile,
                                 tile_block)
from repro.kernels.ring import band_row_to_col, eye_tile
from repro.kernels.trsm import invert_lower_tile, substitute_panel
from repro.runtime import telemetry

SEEDS = range(4)


def _well_conditioned(t, seed):
    a = np.random.default_rng(seed).standard_normal((t, t))
    return a @ a.T + t * np.eye(t)


def _near_singular(t, cond, seed):
    """A (t, t) arrowhead tile whose cond is about ``cond``:
    ``near_singular_arrowhead``'s smallest eigenvalue set from the spread
    of the spectrum."""
    bw, ar = t // 5, t // 16
    a1, _ = near_singular_arrowhead(t, bw, ar, seed=seed, eig_min=1.0)
    spread = np.linalg.eigvalsh(a1.toarray())[-1] - 1.0
    a, _ = near_singular_arrowhead(t, bw, ar, seed=seed,
                                   eig_min=spread / (cond - 1.0))
    a = a.toarray()
    ev = np.linalg.eigvalsh(a)
    assert 0.5 * cond < ev[-1] / ev[0] < 2.0 * cond
    return a


def _rel(x, want):
    return np.linalg.norm(np.asarray(x, np.float64) - want) \
        / np.linalg.norm(want)


@pytest.mark.parametrize("t", [64, 128])
def test_tile_block_is_chosen_from_t(t):
    assert tile_block(t) == 32
    assert [tile_block(s) for s in (8, 16, 32, 48, 80)] == [8, 16, 32, 48, 80]


@pytest.mark.parametrize("cond", [None, 1e4, 1e6])
@pytest.mark.parametrize("t", [64, 128])
def test_blocked_factor_and_inverse_accuracy(t, cond):
    """Against float64 ``np.linalg.cholesky`` and its inverse, the blocked
    factor and its blocked inverse err at most twice as much as the
    unblocked loop and ``substitute_panel(l, eye)``.  Errors are summed
    over four seeded tiles: a single tile's float32 rounding scatters
    either pair's error by a factor of about two either way."""
    errs = np.zeros(4)
    for seed in SEEDS:
        a64 = (_well_conditioned(t, seed) if cond is None
               else _near_singular(t, cond, seed))
        a = jnp.asarray(a64, jnp.float32)
        lo = np.linalg.cholesky(a64)
        inv = np.linalg.inv(lo)
        assert tile_block(t) < t
        lb = factorize_tile(a)
        lu = _factorize_unblocked(a, False)
        errs += [_rel(lb, lo), _rel(lu, lo),
                 _rel(invert_lower_tile(lb), inv),
                 _rel(substitute_panel(lu, eye_tile(t)), inv)]
        # exactly triangular, as the sweeps store them
        assert np.all(np.triu(np.asarray(lb), 1) == 0.0)
        assert np.all(np.triu(np.asarray(invert_lower_tile(lb)), 1) == 0.0)
    assert np.all(np.isfinite(errs))
    assert errs[0] <= 2.0 * errs[1], errs
    assert errs[2] <= 2.0 * errs[3], errs


@pytest.mark.parametrize("t", [64, 128])
def test_blocked_raw_pivot_matches_unblocked(t):
    """``return_status=True`` reports the minimum raw pre-rsqrt pivot on
    the blocked path as the unblocked loop does: on a healthy tile, on one
    whose last pivot is negative, and as a breakdown mid-tile."""
    spd = jnp.asarray(_well_conditioned(t, 0), jnp.float32)
    lb, pb = factorize_tile(spd, return_status=True)
    lu, pu = _factorize_unblocked(spd, True)
    np.testing.assert_array_equal(np.asarray(lb),
                                  np.asarray(factorize_tile(spd)))
    assert float(pb) > 0
    np.testing.assert_allclose(float(pb), float(pu), rtol=1e-5)
    last = spd.at[t - 1, t - 1].add(-100.0 * t)
    _, pb = factorize_tile(last, return_status=True)
    _, pu = _factorize_unblocked(last, True)
    assert float(pb) < 0
    np.testing.assert_allclose(float(pb), float(pu), rtol=1e-5)
    mid = spd - 100.0 * t * jnp.eye(t)
    _, pb = factorize_tile(mid, return_status=True)
    assert not float(pb) > 0


@pytest.mark.parametrize("t", [8, 16, 32])
def test_small_tiles_keep_the_unblocked_routines(t):
    """Below t = 64 the tile routines are the unblocked loops: the same
    jaxpr and bit-identical results."""
    a = jnp.asarray(_well_conditioned(t, 1), jnp.float32)
    assert tile_block(t) == t
    for status in (False, True):
        got = factorize_tile(a, return_status=status)
        want = _factorize_unblocked(a, status)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert str(jax.make_jaxpr(
            lambda x: factorize_tile(x, return_status=status))(a)) == \
            str(jax.make_jaxpr(lambda x: _factorize_unblocked(x, status))(a))
    lo = factorize_tile(a)
    np.testing.assert_array_equal(
        np.asarray(invert_lower_tile(lo)),
        np.asarray(substitute_panel(lo, eye_tile(t))))
    assert str(jax.make_jaxpr(invert_lower_tile)(lo)) == \
        str(jax.make_jaxpr(lambda x: substitute_panel(x, eye_tile(t)))(lo))


# ------------------------------------------------------------ sweeps, t = 128

T = 128
SWEEPS = {"fused": band_cholesky_sweep_pallas,
          "stream": band_cholesky_stream_sweep_pallas}


def _t128_ctsf(seed=0):
    """ndt 5, bt 2, one arrow tile at t = 128."""
    a, st = make_arrowhead(5 * T, 2 * T - 8, T // 2, rho=0.6, seed=seed)
    grid = TileGrid(st, t=T)
    assert (grid.n_diag_tiles, grid.band_tiles, grid.n_arrow_tiles) \
        == (5, 2, 1)
    return api.BandedCTSF.from_sparse(a, grid)


@pytest.mark.parametrize("sweep", sorted(SWEEPS))
def test_t128_sweep_matches_the_oracle(sweep):
    """Panels, arrow rows, Schur sums and the status word of a clean band
    at t = 128 (the blocked finish) agree with the ring-scan oracle."""
    bm = _t128_ctsf()
    ac = band_row_to_col(bm.Dr)
    got = SWEEPS[sweep](ac, bm.R, nchunks=2, interpret=True)
    want = ref.band_cholesky_sweep_ref(ac, bm.R, nchunks=2)
    for g, w, name in zip(got[:3], want[:3], ("panels", "R_out", "schur")):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4, err_msg=name)
    sp, sr = np.asarray(got[3]), np.asarray(want[3])
    np.testing.assert_allclose(sp[0], sr[0], rtol=2e-4)
    assert sp[1] == sr[1] == 0.0
    assert sp[2] == sr[2] == -1.0


@pytest.mark.parametrize("sweep", sorted(SWEEPS))
def test_t128_sweep_flags_an_indefinite_tile(sweep):
    """An indefinite diagonal tile at t = 128 gives the oracle's status
    word: the same nonfinite bit and first failing column."""
    bm = _t128_ctsf()
    tile = 2
    diag = jnp.diagonal(bm.Dr[:, 0], axis1=-2, axis2=-1)
    drop = 3.0 * jnp.mean(jnp.abs(diag))
    dr = bm.Dr.at[tile, 0].add(-drop * jnp.eye(T, dtype=bm.Dr.dtype))
    ac = band_row_to_col(dr)
    *_, sp = SWEEPS[sweep](ac, bm.R, interpret=True)
    *_, sr = ref.band_cholesky_sweep_ref(ac, bm.R)
    sp, sr = np.asarray(sp), np.asarray(sr)
    assert sp[1] == sr[1] == 1.0
    assert sp[2] == sr[2] == float(tile)
    np.testing.assert_allclose(sp[0], sr[0], rtol=2e-4, atol=1e-6)


def test_t128_factorization_span_tags_the_tile_block():
    """The factorization's span records the row block its column finish
    took: 32 at t = 128, beside the sweep."""
    bm = _t128_ctsf()
    telemetry.reset()
    with telemetry.capture():
        api.factorize_window_batched(
            [bm, _t128_ctsf(seed=1)],
            options=api.SolverOptions(impl="pallas"))
        snap = telemetry.snapshot()
    telemetry.reset()
    span, = [s for s in snap["spans"]
             if s["name"] == "factorize.window_batched"]
    assert span["tags"]["sweep"] == "fused"
    assert span["tags"]["tile_block"] == 32


# ------------------------------------------------------ band solves, t = 64

ST = 64


def _t64_problem(cond, seed):
    """A banded-arrowhead SPD matrix at t = 64 (ndt 5, bt 2, one arrow
    tile), well conditioned or with cond about ``cond``."""
    n, bw, ar = 5 * ST + 16, 2 * ST - 8, 16
    if cond is None:
        a, st = make_arrowhead(n, bw, ar, rho=0.6, seed=seed)
    else:
        a1, st = near_singular_arrowhead(n, bw, ar, seed=seed, eig_min=1.0)
        spread = np.linalg.eigvalsh(a1.toarray())[-1] - 1.0
        a, st = near_singular_arrowhead(n, bw, ar, seed=seed,
                                        eig_min=spread / (cond - 1.0))
    grid = TileGrid(st, t=ST)
    assert (grid.n_diag_tiles, grid.band_tiles, grid.n_arrow_tiles) \
        == (5, 2, 1)
    return a.toarray(), grid, api.BandedCTSF.from_sparse(a, grid)


@pytest.mark.parametrize("cond", [None, 1e4])
def test_blocked_band_solve_accuracy(cond):
    """One factor (``ref`` backend) solved by the Pallas band sweeps, whose
    diagonal tiles take the blocked L⁻¹ and one product at t = 64, and by
    the ``ref`` sweeps' triangular solves: against float64
    ``np.linalg.solve`` the blocked solve errs at most twice as much.
    Errors are summed over four seeds, as for the tile routines above."""
    assert tile_block(ST) < ST
    errs = np.zeros(2)
    for seed in SEEDS:
        a64, grid, bm = _t64_problem(cond, seed)
        f = api.factorize_window(bm, options=api.SolverOptions(impl="ref"))
        b = np.random.default_rng(seed).standard_normal((a64.shape[0], 3))
        rows = grid.padded_index(np.arange(a64.shape[0]))
        B = np.zeros((grid.padded_n, 3), np.float32)
        B[rows] = b
        want = np.linalg.solve(a64, b)
        for i, impl in enumerate(("pallas", "ref")):
            x = api.solve_many(f, jnp.asarray(B),
                               options=api.SolverOptions(impl=impl))
            errs[i] += _rel(np.asarray(x)[rows], want)
    assert np.all(np.isfinite(errs))
    assert errs[0] <= 2.0 * errs[1], errs


@pytest.mark.parametrize("t", [8, ST])
def test_concurrent_solve_span_tags_the_tile_block(t):
    """``concurrent.solve`` on the Pallas backend records the row block
    its band sweeps solve the diagonal tiles by: 32 at t = 64, where the
    blocked inverse runs, t at t = 8, where the substitution does."""
    a, st = make_arrowhead(3 * t + 4, t + 4, 4, rho=0.6, seed=0)
    grid = TileGrid(st, t=t)
    bm = api.BandedCTSF.from_sparse(a, grid)
    f = api.factorize_window_batched(
        [bm, bm], options=api.SolverOptions(impl="ref"))
    telemetry.reset()
    with telemetry.capture():
        api.concurrent_solve(f, jnp.ones((grid.padded_n,), jnp.float32),
                             options=api.SolverOptions(impl="pallas"))
        api.concurrent_solve(f, jnp.ones((grid.padded_n,), jnp.float32),
                             options=api.SolverOptions(impl="ref"))
        snap = telemetry.snapshot()
    telemetry.reset()
    pallas, ref_ = [s for s in snap["spans"]
                    if s["name"] == "concurrent.solve"]
    assert pallas["tags"]["tile_block"] == (32 if t == ST else t)
    assert "tile_block" not in ref_["tags"]
