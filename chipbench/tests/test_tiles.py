"""The closed-form tile generator against the program's own Table II
generator and sparse-to-tile conversion."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

from chipbench import tiles  # noqa: E402


def _dep(n, bandwidth, arrow, rho):
    return tiles.Deployment(n=n, bandwidth=bandwidth, arrow=arrow, rho=rho,
                            coupling=0.4, temporal_jitter=1e-3,
                            spatial_tau=1.0, x_scale=0.5, schur_slack=1e-3)


@pytest.mark.parametrize("n,bandwidth,arrow,t", [
    (1030, 60, 30, 16),      # n_diag = 1000 is not a multiple of ns
    (1230, 100, 30, 32),
    (2080, 50, 80, 128),
])
@pytest.mark.parametrize("rho", [0.0, 0.7])
def test_generator_matches_from_sparse(n, bandwidth, arrow, t, rho):
    from repro.core.ctsf import BandedCTSF
    from repro.core.structure import TileGrid
    from repro.data.gmrf import make_arrowhead

    A, st = make_arrowhead(n, bandwidth, arrow, rho=rho, seed=3)
    want = BandedCTSF.from_sparse(A, TileGrid(st, t=t))
    dep = _dep(n, bandwidth, arrow, rho)
    g = tiles.grid(dep, t)
    assert g == want.grid
    # the same draws of X as make_arrowhead
    x = np.random.default_rng(3).standard_normal((dep.n_diag, arrow)) \
        * (0.5 / np.sqrt(dep.n_diag))
    _, R, c = tiles.coupling_data(dep, g, x.astype(np.float32))
    got = tiles.tiles(dep, g, [1.0, 1.0, 1.0], R, c)
    for name, a, b in zip("Dr R C".split(), got, want.arrays()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-7,
                                   atol=1e-7, err_msg=name)
    assert tiles.tile_bytes(g) == want.nbytes()


def test_probe_former_scales_each_part():
    dep = _dep(1030, 60, 30, 0.7)
    g = tiles.grid(dep, 16)
    x, R, c = tiles.make_data(dep, g, seed=2**33 + 5)
    thetas = np.array([[1.0, 1.0, 1.0], [2.0, 1.0, 1.0], [1.0, 3.0, 0.5]],
                      np.float32)
    form = tiles.Former(dep, g)
    dr, rr, cc = form.batch(thetas, R, c)
    one = tiles.tiles(dep, g, thetas[0], R, c)
    tt = tiles.tiles(dep, g, [1.0, 0.0, 0.0], R, c)[0]
    ts = tiles.tiles(dep, g, [0.0, 1.0, 0.0], R, c)[0]
    # bit for bit the closed form
    for i in range(3):
        want = tiles.tiles(dep, g, thetas[i], R, c)
        for a, b in zip((dr[i], rr[i], cc[i]), want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(form.one(thetas[i], R, c), want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # away from padding the band is θ_t·(temporal) + θ_s·(spatial)
    rows = dep.n_diag // g.t
    np.testing.assert_allclose(np.asarray(dr[2])[:rows],
                               np.asarray(tt + 3.0 * ts)[:rows], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(rr[1]), np.asarray(R))
    assert float(cc[2][0, 0, 0, 0]) == pytest.approx(0.5 * float(c))
    # the same seed draws the same data; another seed does not
    x2 = tiles.make_data(dep, g, seed=2**33 + 5)[0]
    x3 = tiles.make_data(dep, g, seed=5)[0]
    assert np.array_equal(np.asarray(x), np.asarray(x2))
    assert not np.array_equal(np.asarray(x), np.asarray(x3))
