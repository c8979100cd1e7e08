"""INLA's posterior step.  Set-up factorizes the design points; each step
runs the selected inversion of the next factor, round robin, and reads
back the marginal variances of every row and a seeded sample of band and
arrow entries.  One work unit is one selected inversion."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as _at

from chipbench import tiles
from chipbench.workload import Base, rng, tally


class Workload(Base):
    unit = "selinvs"

    def prepare(self):
        """The run's data, design points and sampled entries, without
        calling the program."""
        dep, tr = self.dep, self.traffic
        self._data()
        r = rng(self.seed, 0)
        self.design = np.exp(r.uniform(-tr["theta_spread"],
                                       tr["theta_spread"],
                                       (tr["design_points"], 3))
                             ).astype(np.float32)
        nd = dep.n_diag
        i = r.integers(0, nd, tr["band_entries"])
        j = i - r.integers(0, np.minimum(dep.bandwidth, i) + 1)
        self.band_pairs = np.stack([i, j], 1)
        self.arrow_pairs = np.stack([r.integers(0, dep.arrow,
                                                tr["arrow_entries"]),
                                     r.integers(0, nd,
                                                tr["arrow_entries"])], 1)

    def setup(self):
        api = self.api
        self.prepare()
        form = tiles.Former(self.dep, self.grid)
        self.factors = []
        for th in self.design:
            dr, r, c = form.one(th, self.R, self.c)
            self.factors.append(api.factorize_window(
                api.BandedCTSF(self.grid, dr, r, c)))
            del dr, r, c
        del form
        self.gather = self._gatherer()
        self.step(-1)
        self.answers.clear()

    def _gatherer(self):
        """A jitted read of the sampled entries from the selected
        inverse's tiles (the program's documented layout)."""
        t = self.grid.t
        i, j = self.band_pairs.T
        bi, ri = np.divmod(i, t)
        bj, rj = np.divmod(j, t)
        k, jj = self.arrow_pairs.T
        ia, rk = np.divmod(k, t)
        ba, ra = np.divmod(jj, t)
        band_idx = tuple(jnp.asarray(v) for v in (bi, bi - bj, ri, rj))
        arrow_idx = tuple(jnp.asarray(v) for v in (ba, ia, rk, ra))
        return jax.jit(lambda dr, r: (dr[band_idx], r[arrow_idx]))

    def step(self, s: int) -> int:
        k = (s + 1) % len(self.factors) if s >= 0 else 0
        with _at("bench.selinv"):
            sig = self.api.selected_inverse(self.factors[k])
        with _at("bench.diagonal"):
            var = sig.diagonal()
        with _at("bench.gather"):
            entries = self.gather(sig.Dr, sig.R)
        del sig
        with _at("bench.readback"):
            var = np.asarray(var)
            band, arrow = (np.asarray(v) for v in entries)
        self.answers.append((s, k, var, band, arrow))
        return 1

    def free(self):
        del self.factors, self.R, self.gather

    def compare(self, ref_data: dict, limits: dict, max_answers: int):
        """Worst gaps over every selected inversion of the window: relative
        variance errors over all rows, and sampled band and arrow entries
        against the reference, scaled by sqrt(Σ_ii·Σ_jj)."""
        from chipbench.reference import Reference
        dep = self.dep
        ref = Reference(dep, ref_data["x"], ref_data["c"])
        nd = dep.n_diag
        want = {k: ref.inverse(th, self.band_pairs, self.arrow_pairs)
                for k, th in enumerate(self.design)
                if any(a[1] == k for a in self.answers)}
        i, j = self.band_pairs.T
        ka, ja = self.arrow_pairs.T
        got = []
        for _, k, var, band, arrow in self.answers[:max_answers]:
            wv, wb, wa = want[k]
            scale_b = np.sqrt(wv[i] * wv[j])
            scale_a = np.sqrt(wv[nd + ka] * wv[ja])
            got.append({
                "var_rel": float(np.max(np.abs(var - wv) / wv)),
                "cov_rel": float(max(np.max(np.abs(band - wb) / scale_b),
                                     np.max(np.abs(arrow - wa) / scale_a)))})
        return tally(got, limits, len(self.answers))
