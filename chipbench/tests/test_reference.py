"""The float64 spectral reference against dense float64 linear algebra of
the same Q(θ), assembled from the repository's Table II generator."""
import os
import sys

import numpy as np
import pytest
import scipy.sparse as sp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

from chipbench import tiles  # noqa: E402
from chipbench.reference import Reference  # noqa: E402


def dense_q(dep, theta, x, c):
    from repro.data.gmrf import ar1_precision, lattice_precision
    nt, ns = dep.nt, dep.ns
    kt = sp.kron(ar1_precision(nt, dep.rho), sp.eye(ns))
    ks = sp.kron(sp.eye(nt), lattice_precision(ns, dep.coupling))
    k = (theta[0] * kt + theta[1] * ks).toarray()
    return np.block([[k, x], [x.T, theta[2] * c * np.eye(dep.arrow)]])


@pytest.mark.parametrize("rho", [0.0, 0.7])
def test_reference_matches_dense(rho):
    dep = tiles.Deployment(n=12 * 9 + 7, bandwidth=9, arrow=7, rho=rho,
                           coupling=0.4, temporal_jitter=1e-3,
                           spatial_tau=1.0, x_scale=0.5, schur_slack=1e-3)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((dep.n_diag, dep.arrow)) * 0.05
    c = 7.5
    theta = np.array([1.4, 0.7, 1.2])
    q = dense_q(dep, theta, x, c)
    ref = Reference(dep, x, c)
    y = rng.standard_normal(dep.n)
    ld, quad, x = ref.probe(theta, ref.spectral(y[:dep.n_diag]),
                          y[dep.n_diag:])
    want_x = np.linalg.solve(q, y)
    assert ld == pytest.approx(np.linalg.slogdet(q)[1], rel=1e-12)
    assert quad == pytest.approx(y @ want_x, rel=1e-12)
    np.testing.assert_allclose(x, want_x, rtol=1e-10, atol=1e-13)
    band = np.array([[5, 5], [40, 31], [80, 79], [100, 100]])
    arrow = np.array([[0, 3], [6, 107]])
    var, b, a = ref.inverse(theta, band, arrow)
    qi = np.linalg.inv(q)
    np.testing.assert_allclose(var, np.diag(qi), rtol=1e-11)
    np.testing.assert_allclose(b, qi[band[:, 0], band[:, 1]], rtol=1e-10)
    np.testing.assert_allclose(a, qi[dep.n_diag + arrow[:, 0], arrow[:, 1]],
                               rtol=1e-10)


def test_reference_refuses_a_truncated_time_block():
    dep = tiles.Deployment(n=1030, bandwidth=60, arrow=30, rho=0.7,
                           coupling=0.4, temporal_jitter=1e-3,
                           spatial_tau=1.0, x_scale=0.5, schur_slack=1e-3)
    with pytest.raises(ValueError):
        Reference(dep, np.zeros((dep.n_diag, 30)), 1.0)
