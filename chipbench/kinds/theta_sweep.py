"""INLA's mode search.  Each step forms P probes Q(θ) on the device (θ₀
drawn from the seed, plus the traffic's offsets in log θ), factorizes
them in one batched call with default options, and blocks on their
log-determinants and their conditional means Q(θ)⁻¹y for a seeded y: the
mode x* of the Gaussian approximation, whose product with y is the
quadratic form of INLA's objective.  One work unit is one probe."""
import jax
import numpy as np
from jax.profiler import TraceAnnotation as _at

from chipbench import tiles
from chipbench.workload import Base, rng, tally


class Workload(Base):
    unit = "probes"

    def prepare(self):
        """The run's data and probe schedule, without calling the
        program."""
        dep, g, tr = self.dep, self.grid, self.traffic
        self._data()
        y = rng(self.seed, 0).standard_normal(dep.n).astype(np.float32)
        yp = np.zeros(g.padded_n, np.float32)
        yp[g.padded_index(np.arange(dep.n))] = y
        self.y_host, self.y = y, jax.device_put(yp)
        offsets = np.asarray(tr["probe_offsets"], np.float64)
        p = 1
        while (2 * p <= len(offsets)
               and 2 * p * 2 * tiles.tile_bytes(g) <= tr["batch_bytes_max"]):
            p *= 2
        self.offsets = offsets[:p]

    def setup(self):
        self.prepare()
        self.form = tiles.Former(self.dep, self.grid).batch
        self.step(-1)                       # warm every shape of a step
        self.answers.clear()

    def thetas(self, s: int) -> np.ndarray:
        tr = self.traffic
        log0 = rng(self.seed, 1, s + 1).uniform(
            -tr["theta_spread"], tr["theta_spread"], 3)
        return np.exp(log0 + tr["probe_step"] * self.offsets).astype(
            np.float32)

    def step(self, s: int) -> int:
        api = self.api
        th = self.thetas(s)
        with _at("bench.form"):
            dr, r, c = self.form(th, self.R, self.c)
        with _at("bench.factorize"):
            fac = api.factorize_window_batched(
                api.BandedCTSF(self.grid, dr, r, c))
        del dr, r, c
        with _at("bench.logdet"):
            ld = api.concurrent_logdet(fac)
        with _at("bench.solve"):
            x = api.concurrent_solve(fac, self.y)
        with _at("bench.readback"):
            ld, x = np.asarray(ld), np.asarray(x)
        del fac
        self.answers.append((s, th, ld, x))
        return len(th)

    def free(self):
        del self.R, self.form

    def compare(self, ref_data: dict, limits: dict, max_answers: int):
        """Worst gaps over the probes of a seeded sample of the window's
        steps (``max_answers`` probes at most, whole steps): the
        conditional mean's largest elementwise error over its largest
        entry, on the latent rows (``x_rel``) and on the fixed-effect rows
        (``xa_rel``); the relative errors of the quadratic form yᵀx
        (``quad_rel``) and of the log-determinant (``logdet_rel``); and
        the relative error of each probe's log-determinant difference from
        its step's first probe (``ld_diff_rel``, the finite difference
        INLA's optimiser takes; 0 for the first probe)."""
        from chipbench.reference import Reference
        dep = self.dep
        ref = Reference(dep, ref_data["x"], ref_data["c"])
        per_step = len(self.offsets)
        n_pick = max(1, max_answers // per_step)
        pick = np.arange(len(self.answers))
        if len(pick) > n_pick:
            pick = np.sort(rng(self.seed, 2).choice(len(pick), n_pick,
                                                    replace=False))
        yt = ref.spectral(self.y_host[:dep.n_diag])
        ya = self.y_host[dep.n_diag:]
        rows = self.grid.padded_index(np.arange(dep.n))
        nd = dep.n_diag
        got = []
        for k in pick:
            _, th, ld, x = self.answers[k]
            ld = np.asarray(ld, np.float64)
            want_ld0 = None
            for i in range(len(th)):
                want_ld, want_q, want_x = ref.probe(th[i], yt, ya)
                if i == 0:
                    want_ld0, diff = want_ld, 0.0
                else:
                    want_d = want_ld - want_ld0
                    diff = abs(ld[i] - ld[0] - want_d) / abs(want_d)
                xi = np.asarray(x[i], np.float64)[rows]
                gap = np.abs(xi - want_x)
                got.append({
                    "x_rel": float(gap[:nd].max() / np.abs(want_x[:nd]).max()),
                    "xa_rel": float(gap[nd:].max()
                                    / np.abs(want_x[nd:]).max()),
                    "quad_rel": abs(float(xi @ self.y_host) - want_q) / want_q,
                    "logdet_rel": abs(float(ld[i]) - want_ld) / abs(want_ld),
                    "ld_diff_rel": float(diff)})
        return tally(got, limits, sum(len(a[1]) for a in self.answers))
