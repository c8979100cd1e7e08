"""The pieces of the wide-band θ-sweep cell on the CPU: the sparse float64
reference, the ``theta_sweep_wide`` kind and its check, the structural
counts at Table II ID 19, the ``stream_hbm_gbps`` reader, and a rehearsal
of the cell at a tiny size on the program's ``ref`` path."""
import json
import os
import shutil
import sys
import time

import jax
import numpy as np
import pytest
import scipy.sparse as sp

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import counts, harness, tiles  # noqa: E402
from chipbench.reference import Reference  # noqa: E402
from chipbench.reference_sparse import (SparseReference,  # noqa: E402
                                        latent_block)

# n_diag 300: 8 time blocks of 40 rows, the last cut to 20
CUT = dict(n=310, bandwidth=40, arrow=10, rho=0.7, coupling=0.4,
           temporal_jitter=1e-3, spatial_tau=1.0, x_scale=0.5,
           schur_slack=1e-3)
THETA = np.array([1.4, 0.7, 1.2])


def _coupling(dep, seed=1):
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((dep.n_diag, dep.arrow)) * 0.5
         / np.sqrt(dep.n_diag)).astype(np.float32)
    return x, float((x.astype(np.float64) ** 2).sum() / dep.schur_slack + 1)


def test_sparse_reference_matches_the_spectral_one():
    dep = tiles.Deployment(**dict(CUT, n=330, bandwidth=20))
    assert dep.nt * dep.ns == dep.n_diag
    x, c = _coupling(dep)
    y = np.random.default_rng(2).standard_normal(dep.n)
    spectral = Reference(dep, x, c)
    _, _, want = spectral.probe(THETA, spectral.spectral(y[:dep.n_diag]),
                                y[dep.n_diag:])
    got = SparseReference(dep, x, c).solve(THETA, y)
    np.testing.assert_allclose(got, want, rtol=1e-10, atol=1e-10)


def test_sparse_reference_matches_dense_on_a_cut_block():
    """Against the repository generator's Kronecker sum, cut to n_diag
    rows and solved densely; and its latent block is the one the
    benchmark forms on the device (``tiles.latent_entry``)."""
    from repro.data.gmrf import ar1_precision, lattice_precision
    dep = tiles.Deployment(**CUT)
    nd = dep.n_diag
    assert nd % dep.ns
    x, c = _coupling(dep)
    kt = sp.kron(ar1_precision(dep.nt, dep.rho), sp.eye(dep.ns))
    ks = sp.kron(sp.eye(dep.nt), lattice_precision(dep.ns, dep.coupling))
    k = (THETA[0] * kt + THETA[1] * ks).toarray()[:nd, :nd]
    np.testing.assert_allclose(latent_block(dep, THETA).toarray(), k,
                               rtol=1e-14, atol=1e-14)
    i, j = np.meshgrid(np.arange(nd), np.arange(nd), indexing="ij")
    formed = np.asarray(tiles.latent_entry(dep, i, j, np.float32(THETA[0]),
                                           np.float32(THETA[1])))
    np.testing.assert_allclose(formed, k, rtol=1e-6, atol=1e-7)
    q = np.block([[k, x], [x.T, THETA[2] * c * np.eye(dep.arrow)]])
    y = np.random.default_rng(2).standard_normal(dep.n)
    np.testing.assert_allclose(SparseReference(dep, x, c).solve(THETA, y),
                               np.linalg.solve(q, y), rtol=1e-10,
                               atol=1e-12)


def test_counts_at_table2_id19():
    cfg = harness.load_config("t2-id19")
    dep = tiles.Deployment.from_config(cfg)
    flops, nbytes = counts.cholesky(dep.n_diag, dep.bandwidth, dep.arrow)
    assert flops == pytest.approx(9.014e12, rel=5e-4)
    assert nbytes == pytest.approx(5.104e9, rel=5e-4)


def _summary(ops, modules):
    from chipbench.trace import Summary
    return Summary(ops, modules, [("bench.step", 0, 10_000)])


def test_stream_hbm_gbps_reads_tags_and_kernel_runs():
    read = harness.load_reader("stream_hbm_gbps")
    span = {"name": "factorize.window_batched", "parent": None,
            "tags": {"b": 2, "stream_bytes": 3e9}}
    other = {"name": "concurrent.solve", "parent": None, "tags": {}}
    trace = _summary(
        [("band_cholesky_stream_sweep_pallas.1", 100, 800),
         ("band_cholesky_stream_sweep_pallas.1", 5100, 800),
         ("band_forward_sweep_pallas.1", 2000, 100)],
        [("jit_batched_window(1)", 50, 1000),
         ("jit_batched_window(2)", 5050, 1000),
         ("jit__forward_impl(3)", 1900, 400)])
    ctx = {"spans": [span, other, dict(span)], "trace": trace}
    # two factorizations of two matrices each, over 2 µs of executables
    assert read(ctx) == pytest.approx(4 * 3e9 / 2e-6 / 1e9)
    assert read(dict(ctx, spans=[other])) is None
    assert read(dict(ctx, trace=_summary([], []))) is None


def _tree(tmp_path):
    """The benchmark's data and code files plus the cut configuration as a
    cell of the wide θ-sweep."""
    here = tmp_path / "chipbench"
    for sub in ("traffic", "kinds", "limits", "metrics", "configs"):
        shutil.copytree(os.path.join(BENCH_DIR, sub), here / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (here / "configs" / "cut.json").write_text(json.dumps(CUT))
    shutil.copy(here / "limits" / "theta_sweep_wide.t2-id19.json",
                here / "limits" / "theta_sweep_wide.cut.json")
    bench = harness.load_benchmark(ROOT)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "theta_sweep_wide.t2-id19" in m.get("workloads", []):
            m["workloads"].append("theta_sweep_wide.cut")
    cell = {"name": "theta_sweep_wide.cut", "config": "cut",
            "traffic": "theta_sweep_wide", "chips": 1}
    return str(here), bench, cell


@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal(tmp_path, trace):
    here, bench, cell = _tree(tmp_path)
    res = harness.run_cell(bench, cell, 12345678901, 0.3, trace,
                           time.perf_counter(), jax.devices("cpu")[:1],
                           here=here, log=lambda *a: None)
    assert res["correct"], res
    assert set(res["readings"]) == {"x_rel", "xa_rel", "quad_rel"}
    assert all(np.isfinite(v) for v in res["readings"].values())
    # no device plane and no streamed sweep on the CPU: nothing to read
    assert set(res["metrics"]) == (set() if trace
                                   else {"setup_s", "probe_rate"})


def test_compare_flags_a_planted_error(tmp_path):
    here, _, _ = _tree(tmp_path)
    dep = tiles.Deployment(**CUT)
    traffic = harness.load_traffic("theta_sweep_wide", here)
    wl = harness.load_kind(traffic["kind"], here)(dep, traffic, 7)
    wl.setup()
    for s in range(2):
        wl.step(s)
    ref_data = wl.reference_data()
    limits = harness.load_limits("theta_sweep_wide.t2-id19", here)
    _, attempted, compared, failed = wl.compare(ref_data, limits, 32)
    p = len(wl.offsets)
    assert (attempted, compared, failed) == (2 * p, 2 * p, 0)
    s, th, ld, x = wl.answers[1]
    x = np.array(x)
    x[0, wl.grid.padded_index(np.array([dep.n_diag // 2]))] *= 1.0 + 1e-3
    wl.answers[1] = (s, th, ld, x)
    worst, _, _, failed = wl.compare(ref_data, limits, 32)
    assert failed == 1 and worst["x_rel"] > limits["x_rel"]
