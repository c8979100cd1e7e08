"""Numerical fault tolerance: in-sweep breakdown detection (status words),
the escalating-jitter recovery ladder, per-element graceful degradation,
refinement, and the hardened (assert-free) validation paths."""
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BandedCTSF, GridBucketPolicy, TileGrid,
                        STATUS_FAILED, STATUS_OK, STATUS_RECOVERED,
                        FactorInfo, RegularizePolicy, factorize_window,
                        factorize_window_batched, solve_many)
from repro.core.cholesky import CholeskyFactor
from repro.core.robustness import add_diagonal_jitter, gershgorin_shift
from repro.data import (indefinite_arrowhead, make_arrowhead,
                        nan_contaminated_arrowhead, near_singular_arrowhead)
from repro.kernels import ref
from repro.kernels.band_cholesky import band_cholesky_sweep_pallas
from repro.kernels.potrf import factorize_tile
from repro.kernels.ring import band_row_to_col
from repro.runtime.fault_tolerance import NumericalFaultInjector
from repro.core.options import SolverOptions

GRIDS = [(16, 4, 0, 16), (30, 6, 14, 16), (160, 8, 0, 16),
         (130, 40, 30, 16), (96, 40, 16, 8)]


def _spd(n, bw, ar, t, seed=0, rho=0.6):
    A, st = make_arrowhead(n, bw, ar, rho=rho, seed=seed)
    g = TileGrid(st, t=t)
    bm = BandedCTSF.from_sparse(A, g)
    return g, bm, bm.to_dense(lower_only=False)


def _corrupt_diag(bm, tile=0, shift=10.0):
    """Make one band diagonal tile indefinite (mean-diagonal-scaled drop)."""
    diag = jnp.diagonal(bm.Dr[:, 0], axis1=-2, axis2=-1)
    drop = shift * jnp.mean(jnp.abs(diag))
    Dr = bm.Dr.at[tile, 0].add(-drop * jnp.eye(bm.grid.t, dtype=bm.Dr.dtype))
    return BandedCTSF(bm.grid, Dr, bm.R, bm.C)


# ---------------------------------------------------------------- detection

@pytest.mark.parametrize("n,bw,ar,t", GRIDS)
def test_status_word_parity_clean(n, bw, ar, t):
    """Both sweep backends emit the same [min_pivot, nonfinite, first_bad]
    word on SPD inputs: finite, positive pivot, first_bad == -1."""
    g, bm, _ = _spd(n, bw, ar, t)
    Ac = band_row_to_col(bm.Dr)
    *_, sp = band_cholesky_sweep_pallas(Ac, bm.R, interpret=True)
    *_, sr = ref.band_cholesky_sweep_ref(Ac, bm.R)
    sp, sr = np.asarray(sp), np.asarray(sr)
    np.testing.assert_allclose(sp[0], sr[0], rtol=2e-4)
    assert sp[1] == sr[1] == 0.0
    assert sp[2] == sr[2] == -1.0
    assert sp[0] > 0


@pytest.mark.parametrize("n,bw,ar,t", GRIDS)
def test_status_word_parity_corrupted(n, bw, ar, t):
    """An indefinite tile is flagged identically by both backends — same
    nonfinite bit and same first failing tile, with no exception raised."""
    g, bm, _ = _spd(n, bw, ar, t)
    tile = g.n_diag_tiles // 2
    bad = _corrupt_diag(bm, tile=tile)
    Ac = band_row_to_col(bad.Dr)
    *_, sp = band_cholesky_sweep_pallas(Ac, bad.R, interpret=True)
    *_, sr = ref.band_cholesky_sweep_ref(Ac, bad.R)
    sp, sr = np.asarray(sp), np.asarray(sr)
    assert sp[1] == sr[1]
    assert sp[2] == sr[2]
    assert sp[2] >= 0.0  # breakdown localized, at or after the bad tile
    np.testing.assert_allclose(sp[0], sr[0], rtol=2e-4, atol=1e-6)


def test_factorize_tile_raw_pivot():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((8, 8)).astype(np.float32)
    spd = jnp.asarray(a @ a.T + 8 * np.eye(8, dtype=np.float32))
    l0 = factorize_tile(spd)
    l1, piv = factorize_tile(spd, return_status=True)
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))
    assert float(piv) > 0
    # corrupt only the LAST diagonal entry: every earlier pivot stays clean,
    # so the raw (signed, pre-rsqrt) pivot of the broken column survives the
    # min-fold un-poisoned
    _, piv_bad = factorize_tile(spd.at[7, 7].add(-100.0), return_status=True)
    assert float(piv_bad) < 0  # true signed pivot, pre-rsqrt
    # a mid-tile breakdown NaN-poisons later pivots; the status still
    # reads as breakdown (never a false positive)
    _, piv_mid = factorize_tile(spd - 100.0 * jnp.eye(8), return_status=True)
    assert not float(piv_mid) > 0


# ----------------------------------------------------------------- recovery

def test_ladder_recovers_indefinite_single():
    """Breakdown -> escalating jitter -> RECOVERED, and the emitted factor
    is exactly the Cholesky factor of A + tau*I."""
    g, bm, dense = _spd(96, 16, 8, 8)
    bad = _corrupt_diag(bm, tile=2)
    f = factorize_window(bad, options=SolverOptions(regularize=True))
    info = f.info
    assert int(np.asarray(info.status)) == STATUS_RECOVERED
    assert int(np.asarray(info.attempts)) > 1
    tau = float(np.asarray(info.tau))
    assert tau > 0
    L = np.tril(f.ctsf.to_dense())
    target = np.asarray(bad.to_dense(lower_only=False)) \
        + tau * np.eye(g.padded_n, dtype=np.float32)
    scale = max(1.0, np.abs(target).max())
    assert np.abs(L @ L.T - target).max() < 5e-3 * scale
    assert info.ok()


def test_ladder_leaves_spd_untouched():
    """regularize=True on a clean SPD input: zero jitter, one attempt, and
    a bit-identical factor to the unregularized call."""
    g, bm, _ = _spd(130, 40, 30, 16)
    f0 = factorize_window(bm)
    f1 = factorize_window(bm, options=SolverOptions(regularize=True))
    info = f1.info
    assert int(np.asarray(info.status)) == STATUS_OK
    assert int(np.asarray(info.attempts)) == 1
    assert float(np.asarray(info.tau)) == 0.0
    assert int(np.asarray(info.first_bad_tile)) == -1
    assert info.matrix is None
    for a, b in [(f0.ctsf.Dr, f1.ctsf.Dr), (f0.ctsf.R, f1.ctsf.R),
                 (f0.ctsf.C, f1.ctsf.C)]:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gershgorin_rung_guarantees_finite_recovery():
    """A violently indefinite (but finite) input exhausts the relative taus
    and lands on the Gershgorin rung — still RECOVERED, never FAILED."""
    g, bm, _ = _spd(64, 8, 0, 8)
    bad = _corrupt_diag(bm, tile=1, shift=1e4)
    sh = float(np.asarray(gershgorin_shift(bad.Dr, bad.R, bad.C, g)))
    assert sh > 0
    f = factorize_window(bad, options=SolverOptions(regularize=True))
    assert int(np.asarray(f.info.status)) == STATUS_RECOVERED
    assert np.isfinite(np.asarray(f.ctsf.Dr)).all()


# ---------------------------------------------- batched graceful degradation

def test_batched_injection_end_to_end():
    """Injected faults in a batch: indefinite -> RECOVERED, NaN -> FAILED
    (flagged, not raised), healthy elements bit-identical to the same
    batched call without regularize=."""
    B = 4
    mats = []
    for s in range(B):
        _, bm, _ = _spd(96, 16, 8, 8, seed=s)
        mats.append(bm)
    g = mats[0].grid
    batch = BandedCTSF(g, jnp.stack([m.Dr for m in mats]),
                       jnp.stack([m.R for m in mats]),
                       jnp.stack([m.C for m in mats]))
    inj = NumericalFaultInjector(seed=0, shift=10.0)
    corrupted = inj.corrupt(batch, {1: "indefinite", 2: "nan"})
    assert [(i, m) for i, m, _ in inj.injected] == [(1, "indefinite"),
                                                    (2, "nan")]

    f = factorize_window_batched(corrupted, bucket=False, options=SolverOptions(regularize=True))
    status = np.asarray(f.info.status)
    assert status.shape == (B,)
    assert status[0] == STATUS_OK and status[3] == STATUS_OK
    assert status[1] == STATUS_RECOVERED
    assert status[2] == STATUS_FAILED
    np.testing.assert_array_equal(f.info.ok(), [True, True, False, True])
    assert float(np.asarray(f.info.tau)[1]) > 0
    assert int(np.asarray(f.info.first_bad_tile)[1]) >= 0
    assert int(np.asarray(f.info.first_bad_tile)[0]) == -1

    plain = factorize_window_batched(corrupted, bucket=False)
    for i in (0, 3):  # healthy: bit-for-bit their first attempt
        np.testing.assert_array_equal(np.asarray(f.ctsf.Dr[i]),
                                      np.asarray(plain.ctsf.Dr[i]))
        np.testing.assert_array_equal(np.asarray(f.ctsf.C[i]),
                                      np.asarray(plain.ctsf.C[i]))
        assert np.isfinite(np.asarray(f.ctsf.Dr[i])).all()


def test_batched_bucketed_gridpolicy_ladder():
    """The ladder composes with pow2 bucketing and the canonical-grid
    policy: a 3-element (padded-to-4) embedded batch comes back with (3,)
    per-element status and the injected element recovered."""
    B = 3
    mats = [_spd(96, 16, 8, 8, seed=s)[1] for s in range(B)]
    g = mats[0].grid
    batch = BandedCTSF(g, jnp.stack([m.Dr for m in mats]),
                       jnp.stack([m.R for m in mats]),
                       jnp.stack([m.C for m in mats]))
    corrupted = NumericalFaultInjector(seed=1).corrupt(batch,
                                                       {1: "indefinite"})
    pol = GridBucketPolicy()
    f = factorize_window_batched(corrupted, bucket=True, options=SolverOptions(policy=pol, regularize=True))
    assert f.source_grid == g
    status = np.asarray(f.info.status)
    assert status.shape == (B,)
    assert status[1] == STATUS_RECOVERED
    assert status[0] == STATUS_OK and status[2] == STATUS_OK
    plain = factorize_window_batched(corrupted, bucket=True, options=SolverOptions(policy=pol))
    for i in (0, 2):
        np.testing.assert_array_equal(np.asarray(f.ctsf.Dr[i]),
                                      np.asarray(plain.ctsf.Dr[i]))


def test_concurrent_factorize_ladder_mesh():
    """regularize= threads through concurrent_factorize, both the vmapped
    default and the sharded mesh path (status replicated per element)."""
    from jax.sharding import Mesh
    from repro.core import concurrent_factorize
    from repro.core.concurrent import stack_ctsf
    mats = [_spd(96, 16, 8, 8, seed=s)[1] for s in range(4)]
    bad = NumericalFaultInjector(seed=0).corrupt(stack_ctsf(mats),
                                                 {2: "indefinite"})
    f = concurrent_factorize(bad, options=SolverOptions(regularize=True))
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    fm = concurrent_factorize(bad, mesh=mesh, options=SolverOptions(regularize=True))
    for fi in (f, fm):
        status = np.asarray(fi.info.status)
        assert status[2] == STATUS_RECOVERED
        assert (status[[0, 1, 3]] == STATUS_OK).all()
        assert np.isfinite(np.asarray(fi.ctsf.Dr)).all()


def test_nan_single_flagged_not_raised():
    A, st = nan_contaminated_arrowhead(64, 8, 4, seed=0)
    g = TileGrid(st, t=8)
    bm = BandedCTSF.from_sparse(A, g)
    f = factorize_window(bm, options=SolverOptions(regularize=True))  # must not raise
    assert int(np.asarray(f.info.status)) == STATUS_FAILED
    assert not f.info.ok()


# -------------------------------------------------- pathological generators

def test_pathological_generators():
    n, bw, ar = 64, 8, 4
    A_ind, _ = indefinite_arrowhead(n, bw, ar, seed=0)
    eig_ind = np.linalg.eigvalsh(A_ind.toarray())
    assert eig_ind.min() < 0

    A_ns, _ = near_singular_arrowhead(n, bw, ar, seed=0, eig_min=1e-5)
    eig_ns = np.linalg.eigvalsh(A_ns.toarray())
    np.testing.assert_allclose(eig_ns.min(), 1e-5, rtol=1e-2)

    A_nan, _ = nan_contaminated_arrowhead(n, bw, ar, seed=0)
    D = A_nan.toarray()
    assert np.isnan(D).any()
    # symmetry preserved, NaN included
    assert ((D == D.T) | (np.isnan(D) & np.isnan(D.T))).all()


def test_indefinite_generator_recovers_through_ladder():
    A, st = indefinite_arrowhead(96, 16, 8, seed=3)
    g = TileGrid(st, t=8)
    bm = BandedCTSF.from_sparse(A, g)
    f = factorize_window(bm, options=SolverOptions(regularize=True))
    assert int(np.asarray(f.info.status)) == STATUS_RECOVERED
    assert np.isfinite(np.asarray(f.ctsf.Dr)).all()


# --------------------------------------------------------------- refinement

def test_solve_many_refines_jittered_factor():
    """A perturbed factor used as preconditioner: one residual-checked
    refinement step against the retained original matrix shrinks the
    solve residual vs using the jittered factor alone."""
    g, bm, dense = _spd(96, 16, 8, 8)
    # one refinement step contracts each residual mode by tau/(lambda+tau);
    # tau = lambda_min/2 bounds that by 1/3 across the whole spectrum
    tau = 0.5 * float(np.linalg.eigvalsh(dense).min())
    DrJ, CJ = add_diagonal_jitter(bm.Dr, bm.C, g, jnp.float32(tau))
    fJ = factorize_window(BandedCTSF(g, DrJ, bm.R, CJ))
    info = FactorInfo(status=jnp.asarray(STATUS_RECOVERED, jnp.int32),
                      attempts=jnp.asarray(2, jnp.int32),
                      tau=jnp.asarray(tau, jnp.float32),
                      min_pivot=jnp.asarray(1.0, jnp.float32),
                      first_bad_tile=jnp.asarray(0, jnp.int32),
                      matrix=bm)
    refined = CholeskyFactor(fJ.ctsf, info=info)

    rng = np.random.default_rng(0)
    B = jnp.asarray(rng.standard_normal((g.padded_n, 3)).astype(np.float32))
    X_plain = np.asarray(solve_many(fJ, B))
    X_ref = np.asarray(solve_many(refined, B))
    r_plain = np.linalg.norm(dense @ X_plain - np.asarray(B), axis=0)
    r_ref = np.linalg.norm(dense @ X_ref - np.asarray(B), axis=0)
    assert (r_ref <= r_plain).all()          # never accepted a worse column
    assert r_ref.max() < 0.6 * r_plain.max()  # and it genuinely helped


# ------------------------------------------------- hardened validation paths

def test_policy_resolve():
    assert RegularizePolicy.resolve(None) is None
    assert RegularizePolicy.resolve(False) is None
    assert RegularizePolicy.resolve(True) == RegularizePolicy()
    pol = RegularizePolicy(taus=(1e-3,), gershgorin=False)
    assert RegularizePolicy.resolve(pol) is pol
    with pytest.raises(ValueError, match="regularize"):
        RegularizePolicy.resolve("yes")


def test_validation_survives_optimized_mode():
    """The hardened checks raise ValueError (not bare assert, which
    `python -O` strips)."""
    g, bm, _ = _spd(64, 8, 4, 8)
    f = factorize_window(bm)
    with pytest.raises(ValueError, match="rhs panel"):
        solve_many(f, jnp.zeros((g.padded_n + 1, 2)))
    with pytest.raises(ValueError, match="rhs panel"):
        solve_many(f, jnp.zeros((g.padded_n,)))

    from repro.sharding.pipeline import pipeline_forward, split_stages
    with pytest.raises(ValueError, match="not divisible"):
        split_stages({"w": jnp.zeros((5, 2))}, 2)
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
    with pytest.raises(ValueError, match="microbatches"):
        pipeline_forward(lambda p, h: h, {"w": jnp.zeros((1, 1, 2))},
                         jnp.zeros((5, 2)), mesh, n_microbatches=2)

    from repro import configs
    from repro.models.zamba2 import _n_super
    cfg = dataclasses.replace(configs.get("zamba2-2.7b"), n_layers=7)
    with pytest.raises(ValueError, match="divisible"):
        _n_super(cfg)

    from repro.core.concurrent import stack_ctsf
    with pytest.raises(ValueError, match="at least one"):
        stack_ctsf([])

    inj = NumericalFaultInjector()
    batch = BandedCTSF(g, bm.Dr[None], bm.R[None], bm.C[None])
    with pytest.raises(ValueError, match="corruption mode"):
        inj.corrupt(batch, {0: "gamma-ray"})


def test_lru_cache_thread_safety():
    from repro.core.batching import LRUCache
    with pytest.raises(ValueError):
        LRUCache(maxsize=0)
    cache = LRUCache(maxsize=16)
    errors = []

    def hammer(tid):
        try:
            for i in range(400):
                k = (tid * 7 + i) % 40
                cache.put(k, tid * 1000 + i)
                cache.get((k + 1) % 40)
                len(cache)
                k in cache
        except Exception as exc:  # pragma: no cover - failure path
            errors.append(exc)

    threads = [threading.Thread(target=hammer, args=(t,)) for t in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    assert len(cache) <= 16


# ------------------------------------------------------- property (optional)

try:
    from hypothesis import given, settings, strategies as st_h
    _HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    SETTINGS = dict(max_examples=10, deadline=None)

    @st_h.composite
    def spd_problem(draw):
        ndt = draw(st_h.integers(2, 5))
        t = 8
        bw = draw(st_h.integers(1, t))
        arrow = draw(st_h.sampled_from([0, t // 2]))
        rho = draw(st_h.sampled_from([0.0, 0.5]))
        seed = draw(st_h.integers(0, 2 ** 16))
        return ndt * t + arrow, bw, arrow, t, rho, seed

    @given(spd_problem())
    @settings(**SETTINGS)
    def test_ladder_is_identity_on_spd(problem):
        """Property: for any SPD input the ladder applies no jitter and the
        factor is bit-identical to the unregularized path."""
        n, bw, arrow, t, rho, seed = problem
        A, stc = make_arrowhead(n, bw, arrow, rho=rho, seed=seed)
        g = TileGrid(stc, t=t)
        bm = BandedCTSF.from_sparse(A, g)
        f0 = factorize_window(bm)
        f1 = factorize_window(bm, options=SolverOptions(regularize=True))
        assert float(np.asarray(f1.info.tau)) == 0.0
        assert int(np.asarray(f1.info.status)) == STATUS_OK
        np.testing.assert_array_equal(np.asarray(f0.ctsf.Dr),
                                      np.asarray(f1.ctsf.Dr))
        np.testing.assert_array_equal(np.asarray(f0.ctsf.C),
                                      np.asarray(f1.ctsf.C))
