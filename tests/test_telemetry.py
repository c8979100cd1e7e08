"""Telemetry subsystem (runtime/telemetry.py): registry units, disabled-mode
no-op + overhead guard, static kernel reports (launch/FLOP parity with the
numbers gated in BENCH_cholesky.json), exporter round-trips, instrumented
cache stats, and an end-to-end mixed-grid replay snapshot."""
import json
import os
import re
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import (BandedCTSF, GridBucketPolicy, TileGrid,
                        factorize_window, factorize_window_batched,
                        selinv_batched, solve_many)
from repro.core.batching import LRUCache
from repro.data import make_arrowhead
from repro.kernels import ops
from repro.kernels.ring import band_row_to_col
from repro.runtime import telemetry
from repro.runtime.telemetry import (Telemetry, count_pallas_launches,
                                     kernel_report, sweep_cost)
from repro.core.options import SolverOptions

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_registry():
    """Every test starts from (and leaves behind) a disabled, empty default
    registry — telemetry is process-global state."""
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _problem(n=96, bw=8, ar=4, t=8, seed=0):
    A, struct = make_arrowhead(n, bw, ar, rho=0.6, seed=seed)
    grid = TileGrid(struct, t=t)
    return grid, BandedCTSF.from_sparse(A, grid)


# ---------------------------------------------------------------------------
# Registry units
# ---------------------------------------------------------------------------

def test_counters_gauges_and_labels():
    reg = Telemetry(enabled=True)
    reg.inc("a")
    reg.inc("a", 2.5)
    reg.inc("a", 1, tag="x")
    reg.gauge("g", 7.0)
    reg.gauge("g", 3.0)            # last write wins
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 3.5
    assert snap["counters"]["a{tag=x}"] == 1.0
    assert snap["gauges"]["g"] == 3.0


def test_histogram_quantiles_nearest_rank():
    reg = Telemetry(enabled=True)
    for v in range(1, 101):
        reg.observe("h", float(v))
    s = reg.snapshot()["histograms"]["h"]
    assert s["count"] == 100 and s["sum"] == 5050.0
    assert s["min"] == 1.0 and s["max"] == 100.0
    assert s["p50"] == 50.0
    assert s["p90"] == 90.0
    assert s["p99"] == 99.0


def test_histogram_sample_cap_keeps_exact_count():
    reg = Telemetry(enabled=True, max_samples=16)
    for v in range(100):
        reg.observe("h", float(v))
    s = reg.snapshot()["histograms"]["h"]
    assert s["count"] == 100 and s["max"] == 99.0
    assert s["samples_dropped"] == 100 - 16


def test_span_nesting_parents_and_timing():
    reg = Telemetry(enabled=True)
    with reg.span("outer", who="t"):
        with reg.span("mid"):
            with reg.span("leaf"):
                time.sleep(0.002)
    spans = {s["name"]: s for s in reg.snapshot()["spans"]}
    assert spans["leaf"]["parent"] == spans["mid"]["id"]
    assert spans["mid"]["parent"] == spans["outer"]["id"]
    assert spans["outer"]["parent"] is None
    assert spans["outer"]["tags"] == {"who": "t"}
    # durations nest: outer covers mid covers leaf, and leaf saw the sleep
    assert spans["outer"]["dur_us"] >= spans["mid"]["dur_us"] \
        >= spans["leaf"]["dur_us"] >= 1500


def test_span_tag_after_open():
    reg = Telemetry(enabled=True)
    with reg.span("s") as sp:
        sp.tag(rung="r1", k=4)
    (rec,) = reg.snapshot()["spans"]
    assert rec["tags"] == {"rung": "r1", "k": 4}


def test_counter_thread_hammer():
    reg = Telemetry(enabled=True)
    threads, per = 8, 2000

    def work(i):
        for _ in range(per):
            reg.inc("hammer")
            reg.observe("lat", float(i))
            with reg.span("w"):
                pass

    ts = [threading.Thread(target=work, args=(i,)) for i in range(threads)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    snap = reg.snapshot()
    assert snap["counters"]["hammer"] == threads * per
    assert snap["histograms"]["lat"]["count"] == threads * per
    assert len(snap["spans"]) == threads * per
    # top-level spans on each thread: no cross-thread parent leakage
    assert all(s["parent"] is None for s in snap["spans"])


def test_reset_clears_everything():
    reg = Telemetry(enabled=True)
    reg.inc("a")
    with reg.span("s"):
        pass
    reg.reset()
    snap = reg.snapshot()
    assert snap["counters"] == {} and snap["spans"] == []
    assert reg.enabled()               # reset does not flip the flag


def test_tracer_recording_fails_loudly():
    """jit-safety contract: recording a traced value must raise at the
    call site (never silently bury a host sync in traced code)."""
    reg = Telemetry(enabled=True)

    @jax.jit
    def f(x):
        reg.inc("bad", x)
        return x

    with pytest.raises(Exception):
        f(np.float32(1.0))


# ---------------------------------------------------------------------------
# Disabled mode: no-op behavior + overhead guard
# ---------------------------------------------------------------------------

def test_disabled_mode_records_nothing():
    assert not telemetry.enabled()
    telemetry.inc("c")
    telemetry.observe("h", 1.0)
    telemetry.gauge("g", 1.0)
    with telemetry.span("s", k=1) as sp:
        sp.tag(more="tags")
    snap = telemetry.snapshot()
    assert snap["counters"] == {} and snap["gauges"] == {}
    assert snap["histograms"] == {} and snap["spans"] == []
    # the disabled path builds nothing, profiler annotation included
    assert telemetry.span("s") is telemetry._NOOP_SPAN


def test_capture_restores_previous_state():
    assert not telemetry.enabled()
    with telemetry.capture() as reg:
        assert telemetry.enabled()
        reg.inc("inside")
    assert not telemetry.enabled()
    assert telemetry.snapshot()["counters"]["inside"] == 1.0


def test_disabled_overhead_on_cached_solve_many_under_5pct():
    """Tier-1 guard: the disabled-mode cost of the telemetry surface a
    fully instrumented request crosses must stay under 5% of one cached
    ``solve_many`` dispatch.  Measured as per-op cost in a tight loop
    (deterministic) rather than an A/B wall-clock diff (bimodal in CI)."""
    grid, m = _problem()
    f = factorize_window(m, options=SolverOptions(impl="ref"))
    rng = np.random.default_rng(0)
    B = jax.numpy.asarray(
        rng.standard_normal((grid.padded_n, 4)).astype(np.float32))
    jax.block_until_ready(solve_many(f, B, options=SolverOptions(impl="ref")))  # warm the caches

    reps = 30
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(solve_many(f, B, options=SolverOptions(impl="ref")))
        times.append(time.perf_counter() - t0)
    dispatch = float(np.median(times))

    assert not telemetry.enabled()
    N = 5000
    t0 = time.perf_counter()
    for _ in range(N):
        # one request-worth of the disabled surface: a span with tags, a
        # post-open rung tag, a counter and a histogram observation
        with telemetry.span("solve.solve_many", k=4) as sp:
            sp.tag(grid=telemetry.rung_tag(grid))
        telemetry.inc("cache.hit", cache="batched_window")
        telemetry.observe("lat", 1.0)
    per_request = (time.perf_counter() - t0) / N
    # x3 headroom over the real per-call op count of the instrumented path
    assert 3 * per_request < 0.05 * dispatch, (
        f"disabled telemetry {per_request*1e6:.2f}us/request vs dispatch "
        f"{dispatch*1e6:.1f}us")


# ---------------------------------------------------------------------------
# Static kernel reports
# ---------------------------------------------------------------------------

def _bench_problem():
    """The exact quick problem bench_cholesky.py gates on."""
    n, bw, ar, t = 1024, 32, 16, 16
    A, struct = make_arrowhead(n, bw, ar, rho=0.6, seed=0)
    grid = TileGrid(struct, t=t)
    return grid, BandedCTSF.from_sparse(A, grid)


def test_kernel_report_one_launch_per_fused_sweep():
    """The three fused sweeps each trace to exactly one pallas_call — the
    launch counts gated in BENCH_cholesky.json, reproduced from library
    code (count_pallas_launches now lives in runtime/telemetry.py)."""
    grid, bm = _bench_problem()
    t, nat = grid.t, grid.n_arrow_tiles
    Ac = band_row_to_col(bm.Dr)

    rep_f = kernel_report(
        lambda a, r: ops.band_cholesky_sweep(a, r, nchunks=8, impl="pallas"),
        Ac, bm.R, grid=grid, sweep="cholesky")
    assert rep_f.pallas_launches == 1

    k = 4
    bd = jax.ShapeDtypeStruct((grid.n_diag_tiles, t, k), np.float32)
    rep_s = kernel_report(
        lambda d, r, b: ops.band_forward_sweep(d, r, b, impl="pallas"),
        bm.Dr, bm.R, bd, grid=grid, sweep="forward", k=k)
    assert rep_s.pallas_launches == 1

    sc = jax.ShapeDtypeStruct((nat, nat, t, t), np.float32)
    rep_i = kernel_report(
        lambda l, r, s: ops.selinv_sweep(l, r, s, impl="pallas"),
        Ac, bm.R, sc, grid=grid, sweep="selinv")
    assert rep_i.pallas_launches == 1

    # roofline terms populated and consistent with the hardware model
    for rep in (rep_f, rep_s, rep_i):
        assert rep.flops > 0 and rep.bytes_moved > 0
        assert rep.intensity == pytest.approx(rep.flops / rep.bytes_moved)
        assert rep.t_compute_s == pytest.approx(
            rep.flops / telemetry.PEAK_FLOPS)
        assert rep.bound in ("compute", "memory")


def test_kernel_report_matches_committed_bench_record():
    """Launch counts and FLOP/byte estimates reproduce the committed
    BENCH_cholesky.json from library code — the bench and the library can
    no longer drift (the bench imports the same implementation)."""
    path = os.path.join(_ROOT, "BENCH_cholesky.json")
    with open(path) as f:
        rec = json.load(f)
    assert rec["quick"], "parity test assumes the quick-problem record"
    grid, bm = _bench_problem()
    Ac = band_row_to_col(bm.Dr)
    rep = kernel_report(
        lambda a, r: ops.band_cholesky_sweep(a, r, nchunks=8, impl="pallas"),
        Ac, bm.R, grid=grid, sweep="cholesky")
    assert rep.pallas_launches == rec["fused_factorize_launches"] == 1
    kr = rec["kernel_report"]["cholesky"]
    assert rep.flops == pytest.approx(kr["flops"])
    assert rep.bytes_moved == pytest.approx(kr["bytes_moved"])
    cost = sweep_cost(grid, "selinv")
    assert cost["flops"] == pytest.approx(rec["kernel_report"]["selinv"]["flops"])


def test_sweep_cost_model_properties():
    grid, _ = _problem()
    chol = sweep_cost(grid, "cholesky")
    fwd = sweep_cost(grid, "forward", k=8)
    bwd = sweep_cost(grid, "backward", k=8)
    slv = sweep_cost(grid, "solve", k=8)
    sel = sweep_cost(grid, "selinv")
    # solve = forward + backward by construction
    assert slv["flops"] == fwd["flops"] + bwd["flops"]
    assert slv["bytes"] == fwd["bytes"] + bwd["bytes"]
    # factorization and selinv are O(t^3) per tile, solves O(t^2 k):
    # at k << t the panel sweeps are far cheaper
    assert chol["flops"] > fwd["flops"]
    assert sel["flops"] > fwd["flops"]
    with pytest.raises(ValueError):
        sweep_cost(grid, "nope")


def test_count_pallas_launches_multiplies_scan_bodies():
    """The pre-fusion per-panel path dispatches one launch per scanned
    panel — the counter must charge scan bodies by trip count (this is
    what makes the 'reduction' gate meaningful)."""
    grid, bm = _problem()
    Ac = band_row_to_col(bm.Dr)
    fused = count_pallas_launches(jax.make_jaxpr(
        lambda a, r: ops.band_cholesky_sweep(a, r, impl="pallas"))(Ac, bm.R))
    ref = count_pallas_launches(jax.make_jaxpr(
        lambda a, r: ops.band_cholesky_sweep(a, r, impl="ref"))(Ac, bm.R))
    assert fused == 1
    assert ref == 0          # the ref scan dispatches no pallas kernels


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

_PROM_LINE = re.compile(
    r"^(# TYPE \w+ (counter|gauge|summary)|"
    r"\w+(\{[\w]+=\"[^\"]*\"(,[\w]+=\"[^\"]*\")*\})? -?[\d.e+-]+(inf|nan)?)$")


def test_prometheus_text_parses():
    reg = Telemetry(enabled=True)
    reg.inc("cache.hit", 3, cache="batched_window")
    reg.gauge("queue_depth", 2)
    for v in (1.0, 2.0, 3.0):
        reg.observe("lat_seconds", v, path="solve")
    text = reg.to_prometheus_text()
    lines = text.strip().split("\n")
    for line in lines:
        assert _PROM_LINE.match(line), f"unparseable line: {line!r}"
    # sanitized + prefixed names, summary quantiles present
    assert 'repro_cache_hit{cache="batched_window"} 3' in lines
    assert any(l.startswith("repro_lat_seconds{") and 'quantile="0.99"' in l
               for l in lines)
    assert 'repro_lat_seconds_count{path="solve"} 3' in lines


def test_chrome_trace_round_trip_span_tree():
    reg = Telemetry(enabled=True)
    with reg.span("outer"):
        with reg.span("inner", rung="r"):
            pass
        with reg.span("inner2"):
            pass
    trace = json.loads(json.dumps(reg.to_chrome_trace()))
    evs = trace["traceEvents"]
    assert trace["displayTimeUnit"] == "ms"
    assert all(e["ph"] == "X" for e in evs)
    by_name = {e["name"]: e for e in evs}
    outer_id = by_name["outer"]["args"]["span_id"]
    assert by_name["inner"]["args"]["parent_id"] == outer_id
    assert by_name["inner2"]["args"]["parent_id"] == outer_id
    assert by_name["outer"]["args"]["parent_id"] is None
    assert by_name["inner"]["args"]["rung"] == "r"
    # timestamps are microseconds and children nest inside the parent
    o, i = by_name["outer"], by_name["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-3


# ---------------------------------------------------------------------------
# Instrumented caches
# ---------------------------------------------------------------------------

def test_lru_cache_stats_and_duplicate_trace():
    c = LRUCache(maxsize=2, name="unit_cache")
    assert c.get("a") is None                       # miss
    c.put("a", 1)
    assert c.get("a") == 1                          # hit
    c.put("a", 2)                                   # concurrent-miss double
    c.put("b", 1)
    c.put("c", 1)                                   # evicts "a"
    st = c.stats()
    assert st["hits"] == 1 and st["misses"] == 1
    assert st["duplicate_traces"] == 1
    assert st["evictions"] == 1
    assert st["size"] == 2 and st["maxsize"] == 2


def test_lru_cache_emits_telemetry_when_named():
    telemetry.enable()
    c = LRUCache(maxsize=8, name="emitting")
    c.get("k")
    c.put("k", 1)
    c.get("k")
    c.put("k", 2)
    v = c.get_or_create("k2", lambda: 42)
    assert v == 42
    snap = telemetry.snapshot()
    assert snap["counters"]["cache.miss{cache=emitting}"] == 2.0
    assert snap["counters"]["cache.hit{cache=emitting}"] == 1.0
    assert snap["counters"]["cache.duplicate_trace{cache=emitting}"] == 1.0
    assert snap["histograms"]["cache.trace_seconds{cache=emitting}"][
        "count"] == 1


def test_anonymous_cache_stays_silent():
    telemetry.enable()
    c = LRUCache(maxsize=2)
    c.get("a")
    c.put("a", 1)
    assert not any(k.startswith("cache.")
                   for k in telemetry.snapshot()["counters"])
    assert c.stats()["misses"] == 1                 # local stats still work


# ---------------------------------------------------------------------------
# End-to-end: mixed-grid replay snapshot (the ISSUE acceptance scenario)
# ---------------------------------------------------------------------------

def test_mixed_grid_replay_snapshot_and_trace():
    # earlier suite tests warm the module-level compile caches with the
    # same canonical-grid keys; start cold so the miss counters below are
    # deterministic under any test ordering
    from repro.core import cholesky as _chol_mod
    from repro.core import selinv as _selinv_mod
    _chol_mod._BATCHED_WINDOW_CACHE.clear()
    _selinv_mod._BATCHED_SELINV_CACHE.clear()
    telemetry.enable()
    pol = GridBucketPolicy()
    rng = np.random.default_rng(0)
    for (n, bw, ar), seed in [((96, 8, 4), 0), ((120, 14, 6), 1),
                              ((96, 8, 4), 2)]:
        A, s = make_arrowhead(n, bw, ar, rho=0.6, seed=seed)
        m = BandedCTSF.from_sparse(A, TileGrid(s, t=8))
        fb = factorize_window_batched([m, m], options=SolverOptions(impl="ref", policy=pol))
        f = factorize_window(m, options=SolverOptions(impl="ref", policy=pol))
        B = jax.numpy.asarray(rng.standard_normal(
            (m.grid.padded_n, 3)).astype(np.float32))
        jax.block_until_ready(solve_many(f, B, options=SolverOptions(impl="ref")))
        selinv_batched(fb, options=SolverOptions(impl="ref"))
    snap = telemetry.snapshot()
    counters = snap["counters"]
    # cache hit/miss counts: same-rung repeats hit, each rung misses once
    assert counters.get("cache.miss{cache=batched_window}", 0) >= 1
    assert counters.get("cache.hit{cache=batched_window}", 0) >= 1
    assert counters.get("cache.miss{cache=batched_selinv}", 0) >= 1
    # rung-hit histogram over the canonical rungs seen
    rung_hits = {k: v for k, v in counters.items()
                 if k.startswith("gridpolicy.rung_hit")}
    assert rung_hits and sum(rung_hits.values()) >= 6
    assert "gridpolicy.padded_flop_overhead" in snap["histograms"]
    # nested spans with grid/rung/batch-shape tags
    spans = snap["spans"]
    names = {s["name"] for s in spans}
    assert {"factorize.window_batched", "factorize.window",
            "solve.solve_many", "selinv.batched"} <= names
    fwb = next(s for s in spans if s["name"] == "factorize.window_batched")
    assert fwb["tags"]["b"] == 2 and "rung" in fwb["tags"]
    sm = next(s for s in spans if s["name"] == "solve.solve_many")
    assert sm["tags"]["k"] == 3 and "grid" in sm["tags"]
    # chrome trace is valid trace-event JSON over the same spans
    trace = json.loads(json.dumps(telemetry.to_chrome_trace()))
    assert len(trace["traceEvents"]) == len(spans)
    ids = {e["args"]["span_id"] for e in trace["traceEvents"]}
    assert all(e["args"]["parent_id"] in ids | {None}
               for e in trace["traceEvents"])


def _theta_step(grid, m):
    """One step of INLA's mode search as the benchmark runs it: a batched
    factorization, its log-determinants and its conditional means."""
    from repro import api
    batch = BandedCTSF(grid, *(np.stack([a, a]) for a in (m.Dr, m.R, m.C)))
    y = jax.numpy.ones(grid.padded_n, np.float32)

    def step():
        fac = api.factorize_window_batched(batch)
        jax.block_until_ready((api.concurrent_logdet(fac),
                               api.concurrent_solve(fac, y)))
    return step


def _marginals_step(grid, m):
    """One step of INLA's posterior marginals from a factor made in
    set-up: a selected inversion and its diagonal."""
    from repro import api
    f = factorize_window(m)

    def step():
        jax.block_until_ready(api.selected_inverse(f).diagonal())
    return step


@pytest.mark.parametrize("make,tree", [
    (_theta_step, [("factorize.window_batched", ["factorize.enqueue"]),
                   ("concurrent.logdet", []),
                   ("concurrent.solve", ["solve.enqueue"])]),
    (_marginals_step, [("selinv.selected_inverse", ["selinv.enqueue"]),
                       ("selinv.diagonal", [])]),
], ids=["theta", "marginals"])
def test_step_emits_its_entry_point_spans(make, tree):
    """Each entry point a benchmark step calls opens one outermost span,
    with its call into the compiled executable as a child."""
    step = make(*_problem())
    step()                                          # warm the caches
    telemetry.enable()
    step()
    spans = telemetry.snapshot()["spans"]
    top = sorted((s for s in spans if s["parent"] is None),
                 key=lambda s: s["ts_us"])
    assert [s["name"] for s in top] == [name for name, _ in tree]
    for s, (_, children) in zip(top, tree):
        assert [c["name"] for c in spans if c["parent"] == s["id"]] \
            == children


def test_robustness_ladder_counters():
    telemetry.enable()
    grid, m = _problem(seed=3)
    # clean input: one attempt, all ok — counted off the existing readback
    factorize_window(m, options=SolverOptions(impl="ref", regularize=True))
    snap = telemetry.snapshot()
    assert snap["counters"]["robustness.attempts"] >= 1.0
    assert snap["counters"]["robustness.status{outcome=ok}"] >= 1.0
    # indefinite input: ladder path counts recovered elements
    telemetry.reset()
    Dr = m.Dr.at[..., 0, 0, 0, 0].set(-50.0)       # break a diagonal
    bad = BandedCTSF(grid, Dr, m.R, m.C)
    f = factorize_window(bad, options=SolverOptions(impl="ref", regularize=True))
    assert f.info is not None
    snap = telemetry.snapshot()
    assert snap["counters"]["robustness.attempts"] >= 2.0
    assert "robustness.status{outcome=recovered}" in snap["counters"]
