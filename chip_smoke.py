#!/usr/bin/env python3
"""Smoke test of the solver's main path on a TPU at Table II size.

    python3 chip_smoke.py              # one chip: main, INLA and serving phases
    python3 chip_smoke.py --chips 4    # four chips: sharded θ-sweep and
                                       # distributed factorization only

One chip.  Three phases, through ``repro.api`` with default
``SolverOptions`` (the fused Pallas kernels on a TPU):

* main — Table II IDs 10 and 11 at full size (100,200 rows, bandwidth 1000
  and 2000, arrow 200) on a t=128 grid: factorize, logdet, an 8-column
  solve, marginal variances and the selected inverse.  The factorization
  must lower to one Pallas launch and report a clean status word; the same
  calls with ``impl="ref"`` on the chip must agree at the tier-1 parity
  tolerances; logdet, residual and variances must meet fixed bounds
  against a float64 host oracle (banded Cholesky of the band block plus
  the dense Schur complement of the arrow).
* INLA — one batched factorization of 7 θ-probes of ID 10, then their
  logdets and quadratic forms, checked against the same oracle and against
  the probes factorized one at a time.
* serving — a ``RungServer`` on the wall clock answers 12 requests spread
  over three Table II 10k-row grids; every result must be ``ok()`` and
  solve its system.

Four chips (``--chips 4``): ``concurrent_factorize`` of 8 ID 10 probes
sharded over a 4-device ``data`` mesh, and ``distributed_factorize`` of a
block-diagonal ID 10-shaped matrix with 64,000 band rows (4 tile-aligned
partitions of 125 tiles), each compared with one chip.

Earlier lines print per-step wall times on the host clock (labelled smoke
timings: cold includes compilation; they are not benchmark numbers).  The
last line is one JSON object naming the device.  Any failed check raises,
so the script exits non-zero and prints no result; so does a host without
a TPU, or ``REPRO_KERNEL_IMPL`` forcing a backend other than Pallas.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

T = 128                         # tile size of every grid below
MAIN_IDS = (10, 11)
INLA_ID = 10
INLA_SCALES = (1.0, 1.05, 0.95, 1.1, 0.9, 1.02, 0.98)   # τ of the latent field
SERVE_IDS = (1, 5, 3)           # three 10k-row grids on distinct rungs
SERVE_REQUESTS = 12
NRHS = 8
SEED = 0

# tier-1 parity tolerance between the Pallas and ref backends
# (tests/test_cholesky.py, test_selinv.py, test_solve_batched.py)
PARITY = dict(rtol=2e-4, atol=2e-4)
# float32 vs the float64 oracle, fixed from the float32 ref path on the
# host before any chip run (see CHANGES.md): relative logdet error,
# relative residual ||Qx-b||/||b|| and relative marginal-variance error
ORACLE_BOUNDS = dict(logdet=1e-4, residual=2e-6, variance=3e-6, quad=5e-6)


# ---------------------------------------------------------------------------
# Float64 host oracle
# ---------------------------------------------------------------------------

class Oracle:
    """Float64 reference for Q = [[τK, X], [Xᵀ, D]]: ``cholesky_banded`` of
    the band block K, then the dense Cholesky of the arrow's Schur
    complement D - Xᵀ(τK)⁻¹X.  ``extra`` (n_diag, m) are band-part
    right-hand sides carried through L_K⁻¹ alongside X."""

    def __init__(self, A, n_diag: int, extra):
        import numpy as np
        import scipy.linalg as sl
        import scipy.sparse as sp

        A = sp.csr_matrix(A)
        nd = n_diag
        low = sp.tril(A[:nd, :nd]).tocoo()
        keep = low.data != 0
        r, c, v = low.row[keep], low.col[keep], low.data[keep]
        bw = int((r - c).max()) if r.size else 0
        ab = np.zeros((bw + 1, nd))
        ab[r - c, c] = v
        cb = sl.cholesky_banded(ab, lower=True)
        self.logdet_k = 2.0 * float(np.log(cb[0]).sum())
        self.n_diag = nd
        rhs = np.concatenate([A[nd:, :nd].toarray().T, extra], axis=1)
        w = _band_forward(cb, rhs, max(bw, 1024))
        na = A.shape[0] - nd
        self.wx, self.we = w[:, :na], w[:, na:]
        self.d = A[nd:, nd:].toarray()

    def _schur(self, tau):
        import numpy as np
        return np.linalg.cholesky(self.d - self.wx.T @ self.wx / tau)

    def logdet(self, tau: float = 1.0) -> float:
        import numpy as np
        ls = self._schur(tau)
        return (self.logdet_k + self.n_diag * np.log(tau)
                + 2.0 * float(np.log(np.diag(ls)).sum()))

    def quad(self, col: int, y_arrow, tau: float = 1.0) -> float:
        """yᵀQ⁻¹y for y = (extra[:, col], y_arrow)."""
        import numpy as np
        import scipy.linalg as sl
        z1 = self.we[:, col] / np.sqrt(tau)
        z2 = sl.solve_triangular(self._schur(tau),
                                 y_arrow - self.wx.T @ self.we[:, col] / tau,
                                 lower=True)
        return float(z1 @ z1 + z2 @ z2)

    def variance(self, col=None, arrow=None, tau: float = 1.0) -> float:
        """(Q⁻¹)_ii for the band unit vector ``extra[:, col]`` or arrow
        index ``arrow`` (counted from the first arrow row)."""
        import numpy as np
        import scipy.linalg as sl
        ls = self._schur(tau)
        if arrow is not None:
            e = np.zeros(self.d.shape[0])
            e[arrow] = 1.0
            z2 = sl.solve_triangular(ls, e, lower=True)
            return float(z2 @ z2)
        z1 = self.we[:, col] / np.sqrt(tau)
        z2 = sl.solve_triangular(ls, -self.wx.T @ self.we[:, col] / tau,
                                 lower=True)
        return float(z1 @ z1 + z2 @ z2)


def _band_forward(cb, rhs, blk: int):
    """L⁻¹·rhs for a lower banded factor in LAPACK lower form
    (``cb[d, j] = L[j+d, j]``), by dense row blocks of ``blk >= bandwidth``
    rows: one GEMM against the previous block and one triangular solve."""
    import numpy as np
    import scipy.linalg as sl
    bw, n = cb.shape[0] - 1, cb.shape[1]
    out = np.empty_like(rhs)
    for s in range(0, n, blk):
        e, lo = min(s + blk, n), max(s - blk, 0)
        d = np.arange(s, e)[:, None] - np.arange(lo, e)[None, :]
        cols = np.broadcast_to(np.arange(lo, e)[None, :], d.shape)
        lb = np.where((d >= 0) & (d <= bw), cb[np.clip(d, 0, bw), cols], 0.0)
        acc = rhs[s:e] - lb[:, :s - lo] @ out[lo:s]
        out[s:e] = sl.solve_triangular(lb[:, s - lo:], acc, lower=True)
    return out


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------

def _timed(label: str, fn, arrays=lambda out: out):
    """Run ``fn`` cold (compiling) and warm; print both wall times; return
    the warm result.  ``arrays`` picks what to block on from the result."""
    import jax
    out = None
    times = []
    for _ in range(2):
        out = None                      # free the cold result first
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(arrays(out))
        times.append(time.perf_counter() - t0)
    print(f"smoke-timing {label} cold_s={times[0]!r} warm_s={times[1]!r}",
          flush=True)
    return out


def _check(cond: bool, what: str):
    if not cond:
        raise AssertionError(what)
    print(f"check ok: {what}", flush=True)


def _close(a, b, what: str, rtol: float, atol: float):
    """Elementwise |a-b| <= atol + rtol·|b| on the device."""
    import jax.numpy as jnp
    err = float(jnp.max(jnp.abs(a - b) - rtol * jnp.abs(b)))
    _check(err <= atol, f"{what} (max |a-b|-rtol|b| = {err!r} <= {atol})")


def _rel(got: float, want: float) -> float:
    return float(abs(got - want) / abs(want))


def _rhs(grid, n: int, k: int, seed: int):
    """(padded_n, k) float32 panel, zero on padding rows."""
    import numpy as np
    b = np.zeros((grid.padded_n, k), np.float32)
    rows = grid.padded_index(np.arange(n))
    b[rows] = np.random.default_rng(seed).standard_normal((n, k))
    return b


def _scale_band(Dr, taus, grid):
    """Band tiles of Q(τ) = [[τK, X], [Xᵀ, D]] for each τ in ``taus``
    (leading axis): K scales, the identity padding of the band stays 1."""
    import jax.numpy as jnp
    t, ndt = grid.t, grid.n_diag_tiles
    taus = jnp.asarray(taus, jnp.float32)
    out = Dr[None] * taus[:, None, None, None, None]
    pad = (jnp.arange(ndt * t).reshape(ndt, t) >= grid.structure.n_diag)
    pad = pad[:, :, None] & jnp.eye(t, dtype=bool)[None]
    return out.at[:, :, 0].set(jnp.where(pad, 1.0, out[:, :, 0]))


def _require_tpu(count: int):
    import jax
    from repro.kernels import ops
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"chip_smoke: JAX backend is {backend!r}; this "
                         "script runs only on a TPU")
    impl = ops.default_impl()
    if impl != "pallas":
        raise SystemExit(f"chip_smoke: kernel backend is {impl!r} "
                         "(REPRO_KERNEL_IMPL); the smoke runs the Pallas "
                         "kernels only")
    if len(jax.devices()) < count:
        raise SystemExit(f"chip_smoke: needs {count} chips, JAX sees "
                         f"{len(jax.devices())}")


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------

def main_phase(matrix_id: int, scale: float = 1.0, t: int = T):
    """Factorize → logdet → 8-RHS solve → marginal variances → selected
    inverse on one Table II matrix, checked three ways."""
    import jax
    import numpy as np
    from repro import api
    from repro.data.gmrf import table2_matrix
    from repro.runtime.telemetry import count_pallas_launches

    tag = f"id={matrix_id}"
    t0 = time.perf_counter()
    A, st = table2_matrix(matrix_id, scale=scale, seed=SEED)
    grid = api.TileGrid(st, t=t)
    m = api.BandedCTSF.from_sparse(A, grid)
    jax.block_until_ready(m.Dr)
    print(f"smoke-timing {tag} build_s={time.perf_counter() - t0!r} "
          f"n={st.n} bandwidth={st.bandwidth} arrow={st.arrow} "
          f"ndt={grid.n_diag_tiles} bt={grid.band_tiles} "
          f"nat={grid.n_arrow_tiles}", flush=True)

    launches = count_pallas_launches(jax.make_jaxpr(
        lambda dr, r, c: api.factorize_window(
            api.BandedCTSF(grid, dr, r, c)).ctsf.arrays())(m.Dr, m.R, m.C))
    _check(launches == 1, f"{tag} factorization lowers to {launches} "
                          "Pallas launch(es), want 1")

    f = _timed(f"{tag} step=factorize", lambda: api.factorize_window(m),
               lambda f: f.ctsf.arrays())
    fs = api.factorize_window(m, options=api.SolverOptions(regularize=True))
    info = fs.info
    _check(int(info.status) == api.STATUS_OK and int(info.attempts) == 1,
           f"{tag} status word OK on the first attempt "
           f"(min_pivot={float(info.min_pivot)!r})")
    _check(all(bool((a == b).all()) for a, b in
               zip(fs.ctsf.arrays(), f.ctsf.arrays())),
           f"{tag} status-checked factor is bit-identical")
    del fs

    ld = float(_timed(f"{tag} step=logdet", lambda: api.logdet(f)))
    B = _rhs(grid, st.n, NRHS, SEED + 1)
    X = _timed(f"{tag} step=solve_many", lambda: api.solve_many(f, B))
    nd = st.n_diag
    band_idx = [0, nd // 3, nd - 1]
    idx = np.asarray(band_idx + [nd, st.n - 1])
    mv = _timed(f"{tag} step=marginal_variances",
                lambda: api.marginal_variances(f, idx))
    sig = _timed(f"{tag} step=selected_inverse",
                 lambda: api.selected_inverse(f), lambda s: s.arrays())
    _close(np.asarray(sig.diagonal())[idx], mv,
           f"{tag} selected-inverse diagonal matches marginal_variances",
           rtol=1e-6, atol=0.0)

    # the same calls on the jnp reference backend, on the chip
    ref = api.SolverOptions(impl="ref")
    fr = api.factorize_window(m, options=ref)
    for a, b, name in zip(f.ctsf.arrays(), fr.ctsf.arrays(), ("Dr", "R", "C")):
        _close(a, b, f"{tag} factor {name} pallas~ref", **PARITY)
    _close(X, api.solve_many(fr, B, options=ref),
           f"{tag} solve pallas~ref", **PARITY)
    _close(mv, api.marginal_variances(fr, idx, options=ref),
           f"{tag} marginal variances pallas~ref", **PARITY)
    sr = api.selected_inverse(fr, options=ref)
    for a, b, name in zip(sig.arrays(), sr.arrays(), ("Dr", "R", "C")):
        _close(a, b, f"{tag} selected inverse {name} pallas~ref", **PARITY)
    del fr, sr, sig

    # float64 host oracle
    t0 = time.perf_counter()
    E = np.zeros((nd, len(band_idx)))
    E[band_idx, range(len(band_idx))] = 1.0
    orc = Oracle(A, nd, E)
    print(f"smoke-timing {tag} oracle_s={time.perf_counter() - t0!r}",
          flush=True)
    bounds = ORACLE_BOUNDS
    e_ld = _rel(ld, orc.logdet())
    _check(e_ld <= bounds["logdet"],
           f"{tag} logdet {ld!r} vs float64 {float(orc.logdet())!r}: rel "
           f"{e_ld!r} <= {bounds['logdet']}")
    rows = grid.padded_index(np.arange(st.n))
    xh = np.asarray(X, np.float64)[rows]
    bh = B.astype(np.float64)[rows]
    res = np.linalg.norm(A @ xh - bh, axis=0) / np.linalg.norm(bh, axis=0)
    _check(res.max() <= bounds["residual"],
           f"{tag} residual ||Qx-b||/||b|| max {float(res.max())!r} <= "
           f"{bounds['residual']}")
    want = [orc.variance(col=j) for j in range(len(band_idx))] + \
        [orc.variance(arrow=0), orc.variance(arrow=st.arrow - 1)]
    e_v = max(_rel(float(g), w) for g, w in zip(np.asarray(mv), want))
    _check(e_v <= bounds["variance"],
           f"{tag} marginal variances max rel err {e_v!r} <= "
           f"{bounds['variance']}")


def inla_phase(matrix_id: int = INLA_ID, scale: float = 1.0, t: int = T):
    """One batched factorization of 7 θ-probes, then their logdets and
    quadratic forms — the vmapped-kernel path of examples/inla_gmrf.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import api
    from repro.data.gmrf import table2_matrix

    tag = f"inla id={matrix_id}"
    A, st = table2_matrix(matrix_id, scale=scale, seed=SEED)
    grid = api.TileGrid(st, t=t)
    base = api.BandedCTSF.from_sparse(A, grid)
    nb = len(INLA_SCALES)
    # probe τ scales the latent block K (the band): Q(τ) = [[τK, X], [Xᵀ, D]]
    batch = api.BandedCTSF(
        grid, _scale_band(base.Dr, INLA_SCALES, grid),
        jnp.broadcast_to(base.R, (nb,) + base.R.shape),
        jnp.broadcast_to(base.C, (nb,) + base.C.shape))
    y = _rhs(grid, st.n, 1, SEED + 2)[:, 0]

    def sweep():
        fac = api.factorize_window_batched(batch, bucket=False)
        return (fac, api.concurrent_logdet(fac),
                api.concurrent_quadratic_forms(fac, jnp.asarray(y)))

    fac, lds, quads = _timed(f"{tag} step=batched_factorize+logdet+quad",
                             sweep, lambda o: (o[0].ctsf.arrays(), o[1:]))
    del fac
    lds, quads = np.asarray(lds), np.asarray(quads)

    # the same probes one at a time on one chip (unbatched kernel)
    Y = np.repeat(y[:, None], NRHS, axis=1)
    for i, tau in enumerate(INLA_SCALES):
        fi = api.factorize_window(api.BandedCTSF(
            grid, _scale_band(base.Dr, [tau], grid)[0], base.R, base.C))
        ldi = float(api.logdet(fi))
        qi = float(jnp.dot(api.solve_many(fi, Y)[:, 0], y))
        _check(_rel(lds[i], ldi) <= 1e-6 and _rel(quads[i], qi) <= 1e-4,
               f"{tag} probe {i}: batched logdet {float(lds[i])!r} / quad "
               f"{float(quads[i])!r} match one-at-a-time {ldi!r} / {qi!r}")
    del fi

    nd = st.n_diag
    rows = grid.padded_index(np.arange(st.n))
    yo = y[rows].astype(np.float64)
    orc = Oracle(A, nd, yo[:nd, None])
    for i, tau in enumerate(INLA_SCALES):
        e_ld = _rel(lds[i], orc.logdet(tau))
        e_q = _rel(quads[i], orc.quad(0, yo[nd:], tau))
        _check(e_ld <= ORACLE_BOUNDS["logdet"]
               and e_q <= ORACLE_BOUNDS["quad"],
               f"{tag} probe {i} τ={tau}: logdet rel {e_ld!r}, quadratic "
               f"form rel {e_q!r} vs float64")


def serving_phase(ids=SERVE_IDS, scale: float = 1.0, t: int = T,
                  n_requests: int = SERVE_REQUESTS):
    """A wall-clock RungServer answering requests over three grids."""
    import numpy as np
    from repro import api
    from repro.data.gmrf import table2_matrix

    problems = []
    for mid in ids:
        A, st = table2_matrix(mid, scale=scale, seed=SEED)
        grid = api.TileGrid(st, t=t)
        problems.append((mid, A, st, grid,
                         api.BandedCTSF.from_sparse(A, grid)))

    def serve(wave: int):
        server = api.RungServer(max_batch=4, max_delay=5e-3)
        server.start()
        sent = []
        try:
            for r in range(n_requests):
                mid, A, st, grid, m = problems[r % len(problems)]
                b = _rhs(grid, st.n, 4, 1000 * wave + r)
                sent.append((mid, A, st, grid, b, server.submit(m, b)))
                time.sleep(1e-3)
            results = [s[-1].result(timeout=900) for s in sent]
        finally:
            server.stop(timeout=900)
        return sent, results

    for wave, label in ((0, "cold"), (1, "warm")):
        t0 = time.perf_counter()
        sent, results = serve(wave)
        print(f"smoke-timing serving wave={label} requests={len(results)} "
              f"wall_s={time.perf_counter() - t0!r}", flush=True)
        for (mid, A, st, grid, b, _), res in zip(sent, results):
            _check(res.ok(), f"serving rid={res.rid} id={mid} ok "
                             f"(status={res.status}, rung={res.rung})")
            rows = grid.padded_index(np.arange(st.n))
            x = np.asarray(res.x, np.float64)[rows]
            bh = b.astype(np.float64)[rows]
            rr = float((np.linalg.norm(A @ x - bh, axis=0)
                        / np.linalg.norm(bh, axis=0)).max())
            _check(rr <= ORACLE_BOUNDS["residual"],
                   f"serving rid={res.rid} residual {rr!r}")


# ---------------------------------------------------------------------------
# Four-chip phase
# ---------------------------------------------------------------------------

def four_chip_phase(matrix_id: int = INLA_ID, scale: float = 1.0,
                    t: int = T, n_diag: int = 64_000, n_probes: int = 8):
    """Sharded θ-sweep and distributed factorization, each against one
    chip."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro import api
    from repro.core.distributed import (assemble_factor,
                                        distributed_factorize,
                                        partition_banded)
    from repro.data.gmrf import make_arrowhead, table2_matrix

    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("data",))
    one = devs[0]

    def on_devices(x):
        return {s.device for s in x.addressable_shards}

    # --- concurrent θ-sweep: 8 probes sharded over the data axis ----------
    A, st = table2_matrix(matrix_id, scale=scale, seed=SEED)
    grid = api.TileGrid(st, t=t)
    base = api.BandedCTSF.from_sparse(A, grid)
    taus = np.linspace(0.9, 1.1, n_probes).astype(np.float32)
    shard = NamedSharding(mesh, P("data"))
    dr = jax.device_put(_scale_band(base.Dr, taus, grid), shard)
    r = jax.device_put(np.broadcast_to(np.asarray(base.R),
                                       (n_probes,) + base.R.shape), shard)
    c = jax.device_put(np.broadcast_to(np.asarray(base.C),
                                       (n_probes,) + base.C.shape), shard)
    batch = api.BandedCTSF(grid, dr, r, c)

    def sweep():
        fac = api.concurrent_factorize(batch, mesh=mesh, axis="data")
        return fac, api.concurrent_logdet(fac)

    fac, lds = _timed(f"4chip id={matrix_id} step=concurrent_factorize"
                      f"+logdet probes={n_probes}", sweep,
                      lambda o: (o[0].ctsf.arrays(), o[1]))
    _check(len(on_devices(fac.ctsf.Dr)) == len(devs),
           f"sharded factor lives on {len(on_devices(fac.ctsf.Dr))} "
           f"distinct devices, want {len(devs)}")
    t0 = time.perf_counter()
    for i, tau in enumerate(taus):
        mi = api.BandedCTSF(grid, jax.device_put(
            _scale_band(base.Dr, [tau], grid)[0], one),
                            jax.device_put(base.R, one),
                            jax.device_put(base.C, one))
        fi = api.factorize_window(mi)
        ldi = float(api.logdet(fi))
        _check(_rel(float(lds[i]), ldi) <= 1e-5,
               f"probe {i}: sharded logdet {float(lds[i])!r} matches one "
               f"chip {ldi!r}")
        for a, b, name in zip(fac.ctsf.arrays(), fi.ctsf.arrays(), ("Dr", "R", "C")):
            _close(jax.device_put(a[i], one), b,
                   f"probe {i} factor {name} sharded~one chip",
                   rtol=1e-6, atol=1e-6)
    print(f"smoke-timing 4chip one_chip_reference_s="
          f"{time.perf_counter() - t0!r}", flush=True)
    del fac, batch, dr, r, c, base, fi, mi

    # --- distributed factorization of one block-diagonal matrix -----------
    arrow = st.arrow
    A, st = make_arrowhead(n_diag + arrow, st.bandwidth, arrow, rho=0.0,
                           seed=SEED)
    grid = api.TileGrid(st, t=t)
    m = api.BandedCTSF.from_sparse(A, grid)
    pm = partition_banded(m, len(devs))
    out = _timed(f"4chip distributed_factorize n_diag={n_diag} "
                 f"parts={len(devs)}",
                 lambda: distributed_factorize(pm, mesh, axis="data"),
                 lambda o: (o.Dr, o.R, o.C))
    _check(len(on_devices(out.Dr)) == len(devs),
           f"distributed factor lives on {len(on_devices(out.Dr))} "
           f"distinct devices, want {len(devs)}")
    fd = assemble_factor(out, grid)
    f1 = api.factorize_window(api.BandedCTSF(
        grid, *(jax.device_put(x, one) for x in m.arrays())))
    for a, b, name in zip(fd.ctsf.arrays(), f1.ctsf.arrays(), ("Dr", "R", "C")):
        _close(jax.device_put(a, one), b,
               f"distributed factor {name} ~ one chip", **PARITY)
    ld_d, ld_1 = float(api.logdet(fd)), float(api.logdet(f1))
    _check(_rel(ld_d, ld_1) <= 1e-5,
           f"distributed logdet {ld_d!r} matches one chip {ld_1!r}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the four-chip phase")
    args = p.parse_args(argv)

    import jax
    from jax import monitoring
    from repro.runtime import compile_cache

    _require_tpu(args.chips)
    cache_dir = compile_cache.configure(ROOT)
    events = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            events["misses"] += 1

    monitoring.register_event_listener(on_event)
    print(f"chip_smoke: devices={jax.devices()} compile_cache={cache_dir}",
          flush=True)
    print(f"chip_smoke: float64-oracle bounds {ORACLE_BOUNDS} (fixed on the "
          f"host from the float32 ref path); pallas~ref parity {PARITY}",
          flush=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase()
    else:
        for mid in MAIN_IDS:
            main_phase(mid)
        inla_phase()
        serving_phase()
    print(f"smoke-timing total_s={time.perf_counter() - t0!r} "
          f"compile_cache_hits={events['hits']} "
          f"compile_cache_misses={events['misses']}", flush=True)

    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
