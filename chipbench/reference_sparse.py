"""Float64 reference for Q(θ) of ``tiles.py`` where the last time block is
cut (n_diag not a multiple of ns), which the spectral ``reference.py``
cannot take.

It imports nothing of the program.  The latent block K = θ_t·(Q_t⊗I_ns) +
θ_s·(I_nt⊗Q_s), cut to its first n_diag rows and columns, is built from
the formula of the ``tiles.py`` docstring as a scipy sparse matrix with
the diagonals 0, ±1 and ±ns.  Conjugate gradients solve it for y's latent
rows and the coupling X's columns together, to a relative residual of at
most 1e-13 each, and the dense arrow enters through its Schur complement
S = θ_f·c·I − Xᵀ K⁻¹ X.

K is a principal submatrix of the uncut Kronecker sum, so by interlacing
cond(K) is at most μ_max/μ_min of the uncut sum, μ_pq = θ_t·λ_p + θ_s·σ_q.
For Table II ID 19 (nt 4, ns 15,000, ρ 0.7) λ(Q_t) lies in [0.188,
2.515] and σ(Q_s) in [1.000, 2.600]; over the θ-sweep traffic's θ ∈
e^±0.55 that bounds cond(K) by 12.9, so the solves' forward error is
under 2e-12, six orders of magnitude under any limit of the check.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from .tiles import Deployment

RTOL = 1e-13
MAX_ITERATIONS = 2000


def latent_block(dep: Deployment, theta) -> sp.csr_matrix:
    """K(θ) as a float64 sparse matrix (n_diag × n_diag)."""
    nd, ns = dep.n_diag, dep.ns
    nt = -(-nd // ns)
    i = np.arange(nd)
    a, b = i // ns, i % ns
    qt = np.where((a == 0) | (a == nt - 1), 1.0, 1.0 + dep.rho ** 2) \
        + dep.temporal_jitter
    qs = dep.coupling * ((b > 0).astype(float) + (b < ns - 1)) \
        + dep.spatial_tau
    diag = theta[0] * qt + theta[1] * qs
    # ±1 couples spatial neighbours inside a time block, ±ns the same
    # spatial node in consecutive time blocks
    side = np.where(b[:-1] < ns - 1, -dep.coupling * theta[1], 0.0)
    time = np.full(max(nd - ns, 0), -dep.rho * theta[0])
    return sp.diags([diag, side, side, time, time], [0, 1, -1, ns, -ns],
                    shape=(nd, nd), format="csr")


def conjugate_gradients(k: sp.spmatrix, rhs: np.ndarray,
                        rtol: float = RTOL) -> np.ndarray:
    """X with ‖rhs − kX‖ ≤ rtol·‖rhs‖ in every column of ``rhs`` (n, m),
    each column its own conjugate-gradient run, all of them stepped
    together; convergence is judged on the residual recomputed from X."""
    x = np.zeros_like(rhs)
    r = rhs.copy()
    p = r.copy()
    rr = np.einsum("ij,ij->j", r, r)
    goal = (rtol * np.linalg.norm(rhs, axis=0)) ** 2
    for _ in range(MAX_ITERATIONS):
        true = rhs - k @ x
        if np.all(np.einsum("ij,ij->j", true, true) <= goal):
            return x
        kp = k @ p
        pkp = np.einsum("ij,ij->j", p, kp)
        alpha = np.divide(rr, pkp, out=np.zeros_like(rr), where=pkp > 0)
        x += alpha * p
        r -= alpha * kp
        rr_next = np.einsum("ij,ij->j", r, r)
        beta = np.divide(rr_next, rr, out=np.zeros_like(rr), where=rr > 0)
        p = r + beta * p
        rr = rr_next
    raise RuntimeError(f"conjugate gradients did not reach {rtol} in "
                       f"{MAX_ITERATIONS} iterations")


class SparseReference:
    """Exact float64 solves with Q(θ) for one run's data: the coupling
    ``x`` (n_diag, arrow) and ``c``, as the benchmark made them in
    float32."""

    def __init__(self, dep: Deployment, x: np.ndarray, c: float):
        self.dep, self.c = dep, float(c)
        self.x = np.asarray(x, np.float64)

    def solve(self, theta, y: np.ndarray) -> np.ndarray:
        """Q(θ)⁻¹ y for y (n,), float64."""
        dep = self.dep
        nd = dep.n_diag
        theta = np.asarray(theta, np.float64)
        y = np.asarray(y, np.float64)
        sol = conjugate_gradients(latent_block(dep, theta),
                                  np.column_stack([y[:nd], self.x]))
        w, z = sol[:, 0], sol[:, 1:]                 # K⁻¹y_d, K⁻¹X
        schur = theta[2] * self.c * np.eye(dep.arrow) - self.x.T @ z
        xa = np.linalg.solve(schur, y[nd:] - self.x.T @ w)
        return np.concatenate([w - z @ xa, xa])
