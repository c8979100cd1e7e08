"""Float64 reference for the Table II matrices Q(θ) of ``tiles.py``.

It imports nothing of the program.  The latent block is a Kronecker sum,
θ_t·(Q_t⊗I) + θ_s·(I⊗Q_s) = W·diag(μ)·Wᵀ with W = U⊗V the eigenvectors of
the two tridiagonal factors and μ_pq = θ_t·λ_p + θ_s·σ_q.  So the latent
block's log-determinant, solves and inverse entries are exact float64 sums
over its spectrum, and the dense arrow enters through its Schur complement
S = θ_f·c·I − Xᵀ K⁻¹ X.  One transform of X (and of y) serves every θ.

This needs ``n_diag = nt·ns`` (no truncated time block), which holds for
every configuration of the benchmark.
"""
from __future__ import annotations

import numpy as np
import scipy.linalg as sl

from .tiles import Deployment


class Reference:
    """Exact float64 answers for one run's data: the coupling ``x``
    (n_diag, arrow) and ``c``, as the benchmark made them in float32."""

    def __init__(self, dep: Deployment, x: np.ndarray, c: float):
        nd, ns, nt = dep.n_diag, dep.ns, dep.nt
        if nt * ns != nd:
            raise ValueError(f"n_diag={nd} is not nt·ns={nt}·{ns}: the "
                             "latent block is no Kronecker sum")
        self.dep, self.c = dep, float(c)
        rho = dep.rho
        qt_d = np.full(nt, 1.0 + rho * rho)
        qt_d[0] = qt_d[-1] = 1.0
        qt_d += dep.temporal_jitter
        self.lt, self.U = sl.eigh_tridiagonal(qt_d, np.full(nt - 1, -rho))
        b = np.arange(ns)
        qs_d = (dep.coupling * ((b > 0).astype(float) + (b < ns - 1))
                + dep.spatial_tau)
        self.ls, self.V = sl.eigh_tridiagonal(qs_d,
                                              np.full(ns - 1, -dep.coupling))
        self.xt = self._to_spectral(np.asarray(x, np.float64))

    def _to_spectral(self, v):
        """Wᵀ v for v (n_diag, k) -> (nt, ns, k)."""
        nt, ns = self.dep.nt, self.dep.ns
        v = v.reshape(nt, ns, -1)
        v = np.einsum("bq,abk->aqk", self.V, v, optimize=True)
        return np.einsum("ap,aqk->pqk", self.U, v, optimize=True)

    def _from_spectral(self, v):
        """W v for v (nt, ns, k) -> (n_diag, k)."""
        v = np.einsum("ap,pqk->aqk", self.U, v, optimize=True)
        v = np.einsum("bq,aqk->abk", self.V, v, optimize=True)
        return v.reshape(self.dep.n_diag, -1)

    def _mu(self, theta):
        return theta[0] * self.lt[:, None] + theta[1] * self.ls[None, :]

    def _schur(self, theta, mu):
        a = self.dep.arrow
        w = (self.xt / np.sqrt(mu)[..., None]).reshape(-1, a)
        return theta[2] * self.c * np.eye(a) - w.T @ w

    def spectral(self, y_diag):
        """Wᵀ y_diag, computed once for every θ of a run."""
        y = np.asarray(y_diag, np.float64)[:, None]
        return self._to_spectral(y)[..., 0]

    def probe(self, theta, yt, y_arrow):
        """(log det Q(θ), yᵀQ(θ)⁻¹y, Q(θ)⁻¹y) for y = (y_diag, y_arrow),
        with ``yt = spectral(y_diag)``."""
        theta = np.asarray(theta, np.float64)
        mu = self._mu(theta)
        ls = np.linalg.cholesky(self._schur(theta, mu))
        z = np.asarray(y_arrow, np.float64) \
            - np.einsum("pqk,pq->k", self.xt, yt / mu)
        z2 = sl.solve_triangular(ls, z, lower=True)
        xa = sl.solve_triangular(ls, z2, lower=True, trans="T")
        xd = self._from_spectral(((yt - self.xt @ xa) / mu)[..., None])
        logdet = float(np.log(mu).sum() + 2.0 * np.log(np.diag(ls)).sum())
        return (logdet, float((yt * yt / mu).sum() + z2 @ z2),
                np.concatenate([xd[:, 0], xa]))

    def inverse(self, theta, band_pairs, arrow_pairs):
        """Entries of Q(θ)⁻¹: (variances of all n rows, Σ_ij for the band
        pairs (i, j < n_diag), Σ for (arrow index k, band row j) pairs)."""
        theta = np.asarray(theta, np.float64)
        dep = self.dep
        mu = self._mu(theta)
        sinv = np.linalg.inv(self._schur(theta, mu))
        g = self._from_spectral(self.xt / mu[..., None])      # K⁻¹X
        gs = g @ sinv
        m = 1.0 / mu
        kdiag = ((self.U ** 2) @ m @ (self.V ** 2).T).reshape(-1)
        var = np.concatenate([kdiag + np.einsum("ik,ik->i", gs, g),
                              np.diag(sinv)])
        i, j = np.asarray(band_pairs).T
        ai, bi = np.divmod(i, dep.ns)
        aj, bj = np.divmod(j, dep.ns)
        kij = np.einsum("ep,pq,eq->e", self.U[ai] * self.U[aj], m,
                        self.V[bi] * self.V[bj])
        band = kij + np.einsum("ek,ek->e", gs[i], g[j])
        k, j = np.asarray(arrow_pairs).T
        return var, band, -gs[j, k]
