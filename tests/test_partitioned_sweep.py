"""Multi-partition fused Cholesky sweep: parity, bit-identity, batching.

The partitioned sweep runs one 2D Pallas launch — a parallel axis over
the independent band partitions of a block-separable problem (the
adaptive-ND shape, paper §III-A) and a sequential axis within each
partition — with per-partition corner Schur chunks combined by the GEADD
tree before the shared separator factorization.  These tests pin the
numerical contracts:

* ref and Pallas backends agree at 1/2/4 partitions;
* within a backend, the partitioned sweep is *bit-identical* to the
  fused single-partition sweep on block-separable inputs (the partitions
  really are independent — same tile math, same order);
* a trivial (single-partition) plan routes to the existing fused path
  and reproduces it bit for bit, corner included;
* ``start_tile`` identity prefixes and ``vmap`` compose with the 2D grid.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from repro.core import (BandedCTSF, SolverOptions, TileGrid,
                        detect_partition_plan, factorize_window,
                        factorize_window_batched)
from repro.core.ordering import PartitionPlan
from repro.data import block_separable_arrowhead, make_arrowhead
from repro.kernels import ops, ref
from repro.kernels.ring import band_row_to_col
from repro.runtime import telemetry

CASE = dict(n=100, bandwidth=5, arrow=4, t=8)


def _split_inputs(n_parts, seed=0, **case):
    case = {**CASE, **case}
    A, st, bounds = block_separable_arrowhead(
        n_parts=n_parts, seed=seed, **case)
    g = TileGrid(st, case["t"])
    m = BandedCTSF.from_sparse(A, g)
    return A, g, m, bounds


@pytest.mark.parametrize("n_parts", [1, 2, 4])
def test_partitioned_sweep_ref_matches_pallas(n_parts):
    _, g, m, bounds = _split_inputs(n_parts)
    Ac = band_row_to_col(m.Dr)
    out_ref = ref.band_cholesky_partitioned_sweep_ref(Ac, m.R, bounds)
    out_pl = ops.band_cholesky_partitioned_sweep(Ac, m.R, bounds,
                                                 impl="pallas")
    for a, b in zip(out_ref, out_pl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("n_parts", [2, 4])
def test_partitioned_bit_identical_to_fused_within_backend(impl, n_parts):
    """On a genuinely block-separable input the fused sweep performs the
    identical per-partition tile math, so panels and arrow rows match bit
    for bit; only the Schur *chunking* differs (one chunk per partition
    vs nchunks), so the corner contributions agree to a sum reorder."""
    _, g, m, bounds = _split_inputs(n_parts)
    Ac = band_row_to_col(m.Dr)
    p_f, r_f, sch_f, st_f = ops.band_cholesky_sweep(Ac, m.R, nchunks=1,
                                                    impl=impl)
    p_p, r_p, sch_p, st_p = ops.band_cholesky_partitioned_sweep(
        Ac, m.R, bounds, impl=impl)
    assert np.asarray(p_f).tobytes() == np.asarray(p_p).tobytes()
    assert np.asarray(r_f).tobytes() == np.asarray(r_p).tobytes()
    np.testing.assert_allclose(np.asarray(sch_f[0]),
                               np.asarray(sch_p.sum(0)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(st_f), np.asarray(st_p))


def test_single_partition_plan_reproduces_fused_factorization():
    A, st = make_arrowhead(**{k: CASE[k] for k in ("n", "bandwidth")},
                           arrow=CASE["arrow"], seed=3)
    g = TileGrid(st, CASE["t"])
    m = BandedCTSF.from_sparse(A, g)
    plan = PartitionPlan.trivial(g.n_diag_tiles)
    base = factorize_window(m, options=SolverOptions(impl="ref"))
    via_plan = factorize_window(
        m, options=SolverOptions(impl="ref", partition_plan=plan))
    for a, b in zip(base.ctsf.arrays(), via_plan.ctsf.arrays()):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_partitioned_factorization_matches_dense_cholesky(impl):
    A, g, m, bounds = _split_inputs(3)
    plan = PartitionPlan(boundaries=bounds, sep_tiles=g.n_arrow_tiles)
    f = factorize_window(
        m, options=SolverOptions(impl=impl, partition_plan=plan))
    L = np.linalg.cholesky(m.to_dense(lower_only=True)
                           + np.triu(m.to_dense(lower_only=True).T, 1))
    err = np.abs(f.ctsf.to_dense() - np.tril(L)).max()
    assert err < 1e-3 * max(1.0, np.abs(L).max())


def test_detect_partition_plan_certifies_generator_cuts():
    A, g, m, bounds = _split_inputs(3)
    plan = detect_partition_plan(A, g.structure, g.t)
    assert plan.boundaries == bounds
    assert plan.n_partitions == 3
    assert plan.sep_tiles == g.n_arrow_tiles
    # a dense-band matrix detects as a single partition
    A1, st1 = make_arrowhead(CASE["n"], CASE["bandwidth"], CASE["arrow"],
                             seed=1)
    assert detect_partition_plan(A1, st1, CASE["t"]).n_partitions == 1


def test_auto_sweep_dispatches_partitioned_only_for_multi_partition_plans():
    """The sweep is chosen from the input alone: a two-partition plan takes
    the partitioned sweep; a one-partition plan or none takes the band
    sweep (the ref backend's ring scan).  Read from the ``cholesky.sweep
    {path=}`` counter and the entry point's span tag."""
    _, g, m, bounds = _split_inputs(2)
    plan = PartitionPlan(boundaries=bounds, sep_tiles=g.n_arrow_tiles)
    assert plan.n_partitions == 2
    for p, want in ((plan, "partitioned"),
                    (PartitionPlan.trivial(g.n_diag_tiles), "ring"),
                    (None, "ring")):
        telemetry.reset()
        with telemetry.capture():
            factorize_window(
                m, options=SolverOptions(impl="ref", partition_plan=p))
            snap = telemetry.snapshot()
        telemetry.reset()
        assert snap["counters"][f"cholesky.sweep{{path={want}}}"] == 1.0
        span, = [s for s in snap["spans"] if s["name"] == "factorize.window"]
        assert span["tags"]["sweep"] == want
    # a plan for another grid is refused, not silently misapplied
    with pytest.raises(ValueError, match="rebuild the plan"):
        factorize_window(m, options=SolverOptions(
            impl="ref", partition_plan=PartitionPlan.trivial(
                g.n_diag_tiles + 1)))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_partitioned_start_tile_prefix(impl):
    """A start_tile identity prefix (the canonical-grid embedding skip)
    emits identity panels before ``start_tile`` and the real
    factorization after, exactly like the fused sweep."""
    _, g, m, bounds = _split_inputs(2)
    Ac = np.asarray(band_row_to_col(m.Dr))
    start = bounds[1]            # skip the whole first partition
    eye = np.zeros_like(Ac)
    eye[:, 0] = np.eye(g.t, dtype=Ac.dtype)
    Ac_embedded = np.where(
        (np.arange(g.n_diag_tiles) < start)[:, None, None, None], eye, Ac)
    R_embedded = np.asarray(m.R).copy()
    R_embedded[:start] = 0.0
    p, r, sch, _ = ops.band_cholesky_partitioned_sweep(
        jnp.asarray(Ac_embedded), jnp.asarray(R_embedded), bounds,
        start_tile=start, impl=impl)
    np.testing.assert_array_equal(np.asarray(p[:start]), eye[:start])
    np.testing.assert_array_equal(np.asarray(r[:start]), 0.0)
    p_full, r_full, _, _ = ops.band_cholesky_partitioned_sweep(
        jnp.asarray(Ac), jnp.asarray(m.R), bounds, impl=impl)
    np.testing.assert_allclose(np.asarray(p[start:]),
                               np.asarray(p_full[start:]),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_partitioned_sweep_vmaps(impl):
    _, g, m, bounds = _split_inputs(2)
    _, _, m2, _ = _split_inputs(2, seed=1)
    Ac = jnp.stack([band_row_to_col(m.Dr), band_row_to_col(m2.Dr)])
    R = jnp.stack([m.R, m2.R])
    fn = jax.vmap(lambda a, r: ops.band_cholesky_partitioned_sweep(
        a, r, bounds, impl=impl))
    p, ro, sch, st = fn(Ac, R)
    for i, mm in enumerate((m, m2)):
        p1, r1, s1, st1 = ops.band_cholesky_partitioned_sweep(
            band_row_to_col(mm.Dr), mm.R, bounds, impl=impl)
        np.testing.assert_allclose(np.asarray(p[i]), np.asarray(p1),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(ro[i]), np.asarray(r1),
                                   rtol=1e-5, atol=1e-6)


def test_factorize_window_batched_with_plan():
    A, g, m, bounds = _split_inputs(2)
    _, _, m2, _ = _split_inputs(2, seed=1)
    plan = PartitionPlan(boundaries=bounds, sep_tiles=g.n_arrow_tiles)
    opts = SolverOptions(impl="ref", partition_plan=plan)
    fb = factorize_window_batched([m, m2], options=opts)
    for i, mm in enumerate((m, m2)):
        fi = factorize_window(mm, options=opts)
        np.testing.assert_allclose(np.asarray(fb.ctsf.Dr[i]),
                                   np.asarray(fi.ctsf.Dr),
                                   rtol=1e-5, atol=1e-6)


def test_partition_plan_validation():
    with pytest.raises(ValueError):
        PartitionPlan(boundaries=(0,))             # too short
    with pytest.raises(ValueError):
        PartitionPlan(boundaries=(1, 4))           # must start at 0
    with pytest.raises(ValueError):
        PartitionPlan(boundaries=(0, 4, 4))        # strictly increasing
    with pytest.raises(ValueError):
        PartitionPlan(boundaries=(0, 4), sep_tiles=-1)
    plan = PartitionPlan(boundaries=(0, 3, 8), sep_tiles=2)
    assert plan.n_partitions == 2
    assert plan.n_tiles == 8
    assert plan.sizes == (3, 5)
    assert plan.max_tiles == 5
    assert plan.shifted(2).boundaries == (0, 5, 10)
    assert PartitionPlan.trivial(6).boundaries == (0, 6)
    # a plan sized for a different grid is rejected at dispatch
    _, g, m, _ = _split_inputs(2)
    bad = PartitionPlan.trivial(g.n_diag_tiles + 1)
    with pytest.raises(ValueError):
        factorize_window(m, options=SolverOptions(partition_plan=bad))


def test_partitioned_sweep_property_random_block_separable():
    pytest.importorskip("hypothesis",
                       reason="property tests need the hypothesis package")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(n_parts=st.integers(1, 4), seed=st.integers(0, 99),
           bw=st.integers(3, 9))
    def run(n_parts, seed, bw):
        A, g, m, bounds = _split_inputs(n_parts, seed=seed, bandwidth=bw)
        Ac = band_row_to_col(m.Dr)
        p_f, r_f, _, _ = ops.band_cholesky_sweep(Ac, m.R, nchunks=1,
                                                 impl="ref")
        p_p, r_p, _, _ = ops.band_cholesky_partitioned_sweep(
            Ac, m.R, bounds, impl="ref")
        assert np.asarray(p_f).tobytes() == np.asarray(p_p).tobytes()
        assert np.asarray(r_f).tobytes() == np.asarray(r_p).tobytes()

    run()
