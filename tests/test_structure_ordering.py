"""Preprocessing phase: structure measurement, tile mapping, orderings."""
import numpy as np
import pytest
import scipy.sparse as sp

from repro.core import (ArrowheadStructure, TileGrid, measure_arrowhead,
                        tile_pattern_from_coo, banded_arrowhead_tile_pattern,
                        symbolic_factorize)
from repro.core.ordering import (adaptive_nd_ordering, amd_ordering,
                                 apply_permutation, best_ordering,
                                 rcm_ordering, tile_fill_in)
from repro.data import make_arrowhead


def test_measure_arrowhead_recovers_structure():
    A, st = make_arrowhead(300, 20, 12, seed=0)
    m = measure_arrowhead(A, arrow_hint=12)
    assert m.n == 300 and m.arrow == 12
    assert m.bandwidth <= 20 + 1  # generator band <= requested


def test_tile_grid_counts():
    st = ArrowheadStructure(n=200, bandwidth=24, arrow=16)
    g = TileGrid(st, t=16)
    assert g.n_diag_tiles == 12 and g.n_arrow_tiles == 1
    assert g.band_tiles == 2
    assert g.padded_n == 13 * 16


@pytest.mark.parametrize("n,bw,arrow,t,grid_bw", [
    (96, 10, 0, 16, None),      # no arrow, tile-aligned
    (100, 12, 5, 16, None),     # diagonal and arrow tile padding
    (130, 20, 30, 8, None),     # nat > 1
    (120, 30, 8, 8, 9),         # band entries past band_tiles are dropped
])
def test_banded_from_sparse_matches_dense_path(n, bw, arrow, t, grid_bw):
    """The COO scatter builds exactly the arrays the padded dense matrix
    yields, bit for bit."""
    from repro.core import BandedCTSF
    from repro.core.ctsf import _dense_padded
    A, st = make_arrowhead(n, bw, arrow, rho=0.6, seed=1)
    if grid_bw is not None:
        st = ArrowheadStructure(n=st.n, bandwidth=grid_bw, arrow=st.arrow)
    g = TileGrid(st, t=t)
    got = BandedCTSF.from_sparse(A, g)
    want = BandedCTSF.from_dense_padded(_dense_padded(A, g), g)
    for a, b in zip(got.arrays(), want.arrays()):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_padded_index_vectorized():
    g = TileGrid(ArrowheadStructure(n=100, bandwidth=12, arrow=5), t=16)
    idx = np.arange(100)
    got = g.padded_index(idx)
    assert got.dtype == np.int64
    assert got.tolist() == [g.padded_index(int(i)) for i in idx]
    assert g.padded_index(94) == 94 and g.padded_index(95) == 96


def test_tile_pattern_matches_band():
    A, st = make_arrowhead(200, 24, 16, seed=1)
    g = TileGrid(st, t=16)
    tiles = tile_pattern_from_coo(A, g)
    full = banded_arrowhead_tile_pattern(g)
    # actual nonzero tiles are a subset of the structural band pattern
    assert not (tiles & ~full).any()
    # diagonal always present
    assert tiles.diagonal().all()


def test_density_formula():
    st = ArrowheadStructure(n=100, bandwidth=5, arrow=4)
    d = st.density()
    assert 0 < d < 1


@pytest.mark.parametrize("partial", [True, False])
def test_rcm_is_permutation(partial):
    A, st = make_arrowhead(150, 16, 8, seed=2)
    perm = rcm_ordering(A, st, partial=partial)
    assert sorted(perm.tolist()) == list(range(150))
    if partial:
        # arrow region untouched (paper Fig. 3)
        assert (perm[-8:] == np.arange(142, 150)).all()


def test_amd_is_permutation():
    A, st = make_arrowhead(120, 12, 6, seed=3)
    perm = amd_ordering(A, st, partial=True)
    assert sorted(perm.tolist()) == list(range(120))
    assert (perm[-6:] == np.arange(114, 120)).all()


def test_adaptive_nd_partitions_independent():
    # rho=0 -> block diagonal: adaptive ND must produce independent parts
    A, st = make_arrowhead(256 + 16, 16, 16, rho=0.0, seed=4)
    res = adaptive_nd_ordering(A, st, n_parts=2)
    assert res.accepted
    assert sorted(res.perm.tolist()) == list(range(272))
    permuted = apply_permutation(A, res.perm)
    # partitions must not couple: check block structure of permuted matrix
    p_ids = res.partitions
    part0 = np.nonzero(p_ids == 0)[0]
    part1 = np.nonzero(p_ids == 1)[0]
    sub = sp.csr_matrix(permuted)[part0][:, part1]
    assert sub.nnz == 0


def test_fill_in_acceptance_rule():
    """The paper: 'if there is no improvement, the method is not used.'"""
    A, st = make_arrowhead(200, 24, 8, seed=5)
    res = best_ordering(A, st, t=16)
    assert res.fill_after <= res.fill_before
    if not res.accepted:
        assert (res.perm == np.arange(200)).all()


def test_scrambled_matrix_ordering_reduces_fill():
    """Scramble a banded matrix; RCM must recover (reduce tile fill)."""
    A, st = make_arrowhead(240, 12, 0, seed=6)
    rng = np.random.default_rng(0)
    perm = rng.permutation(240)
    scrambled = apply_permutation(A, perm)
    s_struct = measure_arrowhead(scrambled, arrow_hint=0)
    fill_scrambled = tile_fill_in(scrambled, s_struct, 16, total=True)
    res = best_ordering(scrambled, s_struct, t=16)
    assert res.accepted
    assert res.fill_after < fill_scrambled


def test_symbolic_thin_dag_for_arrowhead():
    """Fig. 2: the arrowhead DAG is thinner than the dense one."""
    n = 8
    dense = np.tril(np.ones((n, n), bool))
    arrow = np.zeros((n, n), bool)
    for k in range(n):
        arrow[k, k] = True
        if k + 1 < n - 1:
            arrow[k + 1, k] = True
        arrow[n - 1, k] = True
    sd = symbolic_factorize(dense)
    sa = symbolic_factorize(np.tril(arrow))
    assert sa.max_parallelism() < sd.max_parallelism()
    assert len(sa.tasks) < len(sd.tasks)


def test_symbolic_fill_counted():
    n = 6
    patt = np.eye(n, dtype=bool)
    patt[n - 1, :] = True  # arrow row -> no fill (already last)
    s = symbolic_factorize(np.tril(patt))
    assert s.fill_tiles == 0
    # first-column spike -> fills the whole trailing block
    patt2 = np.eye(n, dtype=bool)
    patt2[:, 0] = True
    s2 = symbolic_factorize(np.tril(patt2))
    assert s2.fill_tiles > 0
