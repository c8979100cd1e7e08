"""INLA's mode search on a matrix whose last time block is cut, checked
against the sparse reference: the θ-sweep kind (``kinds/theta_sweep.py``,
loaded by its name) with its own ``compare``."""
import os

import numpy as np

from chipbench import harness
from chipbench.workload import rng, tally

ThetaSweep = harness.load_kind(
    "theta_sweep", os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class Workload(ThetaSweep):

    def compare(self, ref_data: dict, limits: dict, max_answers: int):
        """Worst gaps over the probes of a seeded sample of the window's
        steps (``max_answers`` probes at most, whole steps), as the
        θ-sweep kind reads them: ``x_rel``, ``xa_rel`` and ``quad_rel``.
        The log-determinant is timed, not compared: the cut block has no
        cheap exact float64 log-determinant."""
        from chipbench.reference_sparse import SparseReference
        dep = self.dep
        ref = SparseReference(dep, ref_data["x"], ref_data["c"])
        n_pick = max(1, max_answers // len(self.offsets))
        pick = np.arange(len(self.answers))
        if len(pick) > n_pick:
            pick = np.sort(rng(self.seed, 2).choice(len(pick), n_pick,
                                                    replace=False))
        rows = self.grid.padded_index(np.arange(dep.n))
        nd = dep.n_diag
        y = self.y_host.astype(np.float64)
        got = []
        for k in pick:
            _, th, _, x = self.answers[k]
            for i in range(len(th)):
                want_x = ref.solve(th[i], y)
                want_q = float(want_x @ y)
                xi = np.asarray(x[i], np.float64)[rows]
                gap = np.abs(xi - want_x)
                got.append({
                    "x_rel": float(gap[:nd].max() / np.abs(want_x[:nd]).max()),
                    "xa_rel": float(gap[nd:].max()
                                    / np.abs(want_x[nd:]).max()),
                    "quad_rel": abs(float(xi @ y) - want_q) / want_q})
        return tally(got, limits, sum(len(a[1]) for a in self.answers))
