"""Infrastructure satellites: kernel-backend env validation, the bounded
batching caches, and the benchmark harness's --check-only gate."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.core.options import SolverOptions

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# REPRO_KERNEL_IMPL validation (ops.default_impl)
# ---------------------------------------------------------------------------

def test_default_impl_env_override(monkeypatch):
    for valid in ("ref", "pallas"):
        monkeypatch.setenv("REPRO_KERNEL_IMPL", valid)
        assert ops.default_impl() == valid
    monkeypatch.delenv("REPRO_KERNEL_IMPL")
    assert ops.default_impl() in ("ref", "pallas")


def test_default_impl_rejects_invalid_env(monkeypatch):
    """An invalid REPRO_KERNEL_IMPL must fail loudly, not silently fall
    back to the backend default (the old behavior hid typos like
    'palas')."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "palas")
    with pytest.raises(ValueError, match="REPRO_KERNEL_IMPL"):
        ops.default_impl()
    # the error propagates through a dispatching primitive too
    with pytest.raises(ValueError, match="REPRO_KERNEL_IMPL"):
        ops.potrf(jnp.eye(8))


# ---------------------------------------------------------------------------
# Bounded traced-callable caches (core/batching.py)
# ---------------------------------------------------------------------------

def test_lru_cache_bounds_and_recency():
    from repro.core.batching import LRUCache
    c = LRUCache(maxsize=2)
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1          # refresh "a" -> "b" is now LRU
    c.put("c", 3)                   # evicts "b"
    assert "b" not in c and c.get("b") is None
    assert c.get("a") == 1 and c.get("c") == 3
    assert len(c) == 2
    with pytest.raises(ValueError):
        LRUCache(maxsize=0)


def test_batched_window_cache_is_bounded():
    """The serving caches must not grow without limit across distinct
    grids; eviction only drops the Python wrapper, correctness is
    unaffected on re-entry."""
    from repro.core import cholesky, selinv
    assert cholesky._BATCHED_WINDOW_CACHE.maxsize <= 64
    assert selinv._BATCHED_SELINV_CACHE.maxsize <= 64


def test_bucketed_batched_call_pads_and_strips():
    from repro.core.batching import bucketed_batched_call, next_pow2
    assert [next_pow2(b) for b in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]
    seen = {}

    def fn(x):
        seen["n"] = x.shape[0]
        return (x * 2,)

    x = jnp.arange(6, dtype=jnp.float32)[:, None]
    (out,) = bucketed_batched_call(fn, (x,), bucket=True)
    assert seen["n"] == 8 and out.shape[0] == 6
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 2)


def test_lru_eviction_follows_recency_order():
    """Eviction must walk least-recently-*used* order, not insertion
    order: a get() refresh moves an old key behind newer inserts."""
    from repro.core.batching import LRUCache
    c = LRUCache(maxsize=3)
    for k in "abc":
        c.put(k, k)
    assert c.keys() == ["a", "b", "c"]
    c.get("a")                      # refresh: now b is LRU
    c.put("d", "d")                 # evicts b
    assert c.keys() == ["c", "a", "d"]
    c.put("c", "C")                 # overwrite refreshes too
    c.put("e", "e")                 # evicts a (refreshed before c, d)
    assert c.keys() == ["d", "c", "e"]


def test_bucketed_batched_call_exact_pow2_boundary():
    """A batch already sitting on a pow2 boundary must dispatch unpadded
    (no silent 2x blow-up) and return all rows."""
    from repro.core.batching import bucketed_batched_call
    seen = {}

    def fn(x):
        seen["n"] = x.shape[0]
        return (x + 1,)

    for b in (1, 2, 8):
        x = jnp.zeros((b, 3), jnp.float32)
        (out,) = bucketed_batched_call(fn, (x,), bucket=True)
        assert seen["n"] == b and out.shape[0] == b
    # bucket=False never pads either
    x = jnp.zeros((5, 3), jnp.float32)
    (out,) = bucketed_batched_call(fn, (x,), bucket=False)
    assert seen["n"] == 5 and out.shape[0] == 5


def test_canonical_rung_cache_key_distinguishes_use_start():
    """Grids canonicalizing to the same rung share one policy-path cache
    entry, but that entry must be distinct from the plain (no-policy)
    entry for the canonical grid itself — colliding them would replay a
    static-zero trace for embedded inputs (or vice versa)."""
    from repro.core import (BandedCTSF, GridBucketPolicy, TileGrid,
                            factorize_window_batched)
    from repro.core import cholesky as core_cholesky
    from repro.data import make_arrowhead
    pol = GridBucketPolicy()
    A, s = make_arrowhead(96, 10, 5, rho=0.6, seed=0)
    g = TileGrid(s, t=8)
    m = BandedCTSF.from_sparse(A, g)
    cgrid = pol.canonicalize(g)
    cache = core_cholesky._BATCHED_WINDOW_CACHE
    before = set(cache.keys())
    factorize_window_batched([m], tree_chunks=5, options=SolverOptions(impl="ref", policy=pol))
    new = set(cache.keys()) - before
    assert len(new) == 1
    (key,) = new
    assert key[0] == cgrid          # keyed on the canonical grid
    assert key[-1] is True          # ... with the traced-start variant
    # a same-rung grid with a different true shape reuses that entry
    A2, s2 = make_arrowhead(90, 9, 3, rho=0.6, seed=1)
    m2 = BandedCTSF.from_sparse(A2, TileGrid(s2, t=8))
    factorize_window_batched([m2], tree_chunks=5, options=SolverOptions(impl="ref", policy=pol))
    assert set(cache.keys()) - before == new


# ---------------------------------------------------------------------------
# benchmarks/run.py --check-only (validates committed BENCH_*.json)
# ---------------------------------------------------------------------------

def _run_check_only(cwd):
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--check-only"],
        cwd=cwd, capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": os.path.join(_ROOT, "src")})


@pytest.mark.slow
def test_check_only_passes_on_committed_records():
    """The committed BENCH_*.json artifacts must satisfy their own embedded
    thresholds — the fast CI gate against landing a regressed record."""
    res = _run_check_only(_ROOT)
    assert res.returncode == 0, res.stdout + res.stderr


def test_check_only_validation_logic(tmp_path):
    """--check-only flags threshold regressions and missing metrics, and
    never gates on interpret-mode diagnostics."""
    sys.path.insert(0, _ROOT)
    try:
        from benchmarks.run import _record_failures
    finally:
        sys.path.remove(_ROOT)
    ok = {"x_speedup": 5.0, "thresholds": {"x_speedup_min": 3.0}, "pass": True}
    assert _record_failures(ok) == []
    bad = {"x_speedup": 2.0, "thresholds": {"x_speedup_min": 3.0}}
    assert any("x_speedup" in r for r in _record_failures(bad))
    missing = {"thresholds": {"x_speedup_min": 3.0}}
    assert any("missing" in r for r in _record_failures(missing))
    # interpret-mode-only timings are excluded from gating even if a
    # threshold (erroneously) names them
    diag = {"interpret_diagnostics": {"x_speedup": 0.5, "interpret_mode": True},
            "thresholds": {"x_speedup_min": 3.0}}
    assert _record_failures(diag) == []
    failed = {"pass": False}
    assert any("pass=false" in r for r in _record_failures(failed))


def test_check_only_fails_on_missing_registered_record(tmp_path, capsys):
    """Every suite registered in run.py RECORD_SUITES must have a
    committed BENCH_<suite>.json: deleting a record (instead of fixing a
    regression) must fail --check-only, not silently pass."""
    sys.path.insert(0, _ROOT)
    try:
        from benchmarks.run import RECORD_SUITES, check_records
    finally:
        sys.path.remove(_ROOT)
    assert "bucketing" in RECORD_SUITES
    # all registered records present and passing -> clean
    for suite in RECORD_SUITES:
        (tmp_path / f"BENCH_{suite}.json").write_text(
            json.dumps({"pass": True}))
    assert check_records(root=str(tmp_path)) == 0
    # dropping one registered record -> exactly that failure
    (tmp_path / f"BENCH_{RECORD_SUITES[0]}.json").unlink()
    assert check_records(root=str(tmp_path)) == 1
    out = capsys.readouterr().out
    assert f"BENCH_{RECORD_SUITES[0]}.json" in out and "no committed" in out


def test_compile_cache_location(tmp_path, monkeypatch):
    """The entry scripts keep JAX's compile cache where
    JAX_COMPILATION_CACHE_DIR says and set nothing themselves; unset, the
    cache sits at a fixed <root>/.jax_cache, the same on every run."""
    import jax
    from repro.runtime import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        assert compile_cache.configure(str(tmp_path)) == str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(str(tmp_path), ".jax_cache")
        assert compile_cache.configure(str(tmp_path)) == want
        assert compile_cache.configure(str(tmp_path)) == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
