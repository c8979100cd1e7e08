"""The program's spans on the profiler's clock: found in a CPU trace, mapped
from the telemetry snapshot onto the trace's clock, and read by the
program_idle.* and enqueue_ms.* readers on a small trace built here."""
import os
import sys
import tempfile

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench import counts, program, trace  # noqa: E402
from chipbench.harness import load_reader  # noqa: E402
from chipbench.tests.test_trace import MS, small_trace  # noqa: E402

# the program's spans on the trace's clock, inside small_trace()'s calls:
# bench.factorize [0, 1), bench.readback [1, 10), bench.form [12, 13) ms
PROGRAM = [("factorize.window_batched", int(0.2 * MS), int(0.6 * MS)),
           ("factorize.enqueue", int(0.5 * MS), int(0.2 * MS)),
           ("concurrent.solve", int(7.5 * MS), int(2.0 * MS)),
           ("solve.enqueue", int(9.2 * MS), int(0.2 * MS)),
           ("factorize.window_batched", int(12.3 * MS), int(0.5 * MS))]
PARENT = [None, 0, None, 2, None]
OFFSET_NS = 987_654_321          # the trace's clock less the registry's


def snapshot_spans(events=PROGRAM, parents=PARENT):
    """The events as the telemetry snapshot records them, on its own
    clock (microseconds from its epoch)."""
    return [{"name": n, "id": i, "parent": parents[i],
             "ts_us": (s - OFFSET_NS) / 1e3, "dur_us": d / 1e3,
             "tid": 1, "tags": {}}
            for i, (n, s, d) in enumerate(events)]


def test_snapshot_spans_map_onto_the_trace_clock():
    s = small_trace()
    got = program.on_trace_clock(s, snapshot_spans())
    # bounded 0.2 ms from each side by bench.factorize: the middle is exact
    assert got == PROGRAM
    # order of the snapshot does not matter
    assert sorted(program.on_trace_clock(
        s, snapshot_spans()[::-1])) == sorted(PROGRAM)
    # a program with only the factorization's span (an older one) maps too
    assert program.on_trace_clock(
        s, snapshot_spans(PROGRAM[:1], [None]))[0][0] == PROGRAM[0][0]
    # nothing to pin the clocks with
    assert program.on_trace_clock(trace.Summary(s.ops, s.modules, []),
                                  snapshot_spans()) == []
    assert program.on_trace_clock(s, []) == []


def test_repeating_steps_map_to_their_own_calls():
    """Steps repeat, so shifting every span by one call still puts each
    span's middle inside some call annotation; only the right offset also
    keeps every span inside its own.  Call names sort against time."""
    US = MS // 1000
    host, program_ = [], []
    for k in range(3):
        t = k * 6 * MS
        host += [(trace.STEP, t, 5 * MS), ("bench.selinv", t, 500 * US),
                 ("bench.diagonal", t + 500 * US, 4100 * US)]
        program_ += [("selinv.selected_inverse", t + 40 * US, 430 * US),
                     ("selinv.diagonal", t + 501 * US, 4098 * US)]
    s = trace.Summary([("op", 0, 1)], [], host)
    got = program.on_trace_clock(s, snapshot_spans(program_, [None] * 6))
    assert got == program_


def test_program_gaps_by_hand():
    # idle [0,1) [7,8) [9,13) [17,20) ms, cut at the program's edges
    g = program.gaps(small_trace(), PROGRAM)
    assert g == pytest.approx({
        "factorize.window_batched": 0.0003 + 0.0001 + 0.0005,
        "factorize.enqueue": 0.0002,
        "concurrent.solve": 0.0005 + 0.0002 + 0.0001,
        "solve.enqueue": 0.0002,
        program.OUTSIDE: 0.0002 + 0.0002 + 0.0005 + 0.0028 + 0.0002 + 0.003})
    assert sum(g.values()) == pytest.approx(0.009)       # all the idle
    assert program.gaps(small_trace(), []) == pytest.approx(
        {program.OUTSIDE: 0.009})


def _ctx(summary, spans):
    return {"trace": summary, "spans": spans, "steps": 2,
            "units_per_step": 8, "device_kind": "TPU v5 lite",
            "dep": type("D", (), {"n_diag": 100_000, "bandwidth": 1000,
                                  "arrow": 200})()}


def _existing(ctx):
    s = ctx["trace"]
    return (trace.idle_percent(ctx), trace.span_ms_per_step(ctx),
            counts.roofline_share(ctx, counts.cholesky, "band_cholesky"),
            s.gaps(), s.breakdown(), s.busy_s, s.window_s)


@pytest.mark.parametrize("traffic", ["theta", "marginals"])
def test_new_readers_by_hand_and_existing_readers_unchanged(traffic):
    before = _existing(_ctx(small_trace(), snapshot_spans()))
    ctx = _ctx(small_trace(), snapshot_spans())
    idle = load_reader(f"program_idle.{traffic}")(ctx)
    assert idle == pytest.approx(100 * 0.0021 / 0.020)
    # factorize.enqueue 0.2 ms + solve.enqueue 0.2 ms over 2 steps
    assert load_reader(f"enqueue_ms.{traffic}")(ctx) == pytest.approx(0.2)
    assert _existing(ctx) == before
    # without device ops neither reads anything
    bare = _ctx(trace.Summary([], [], small_trace().host), snapshot_spans())
    assert load_reader(f"program_idle.{traffic}")(bare) is None
    assert load_reader(f"enqueue_ms.{traffic}")(bare) is None
    # without program spans (a program that opens none) likewise
    none = _ctx(small_trace(), [])
    assert load_reader(f"program_idle.{traffic}")(none) is None
    assert load_reader(f"enqueue_ms.{traffic}")(none) is None


def _theta_step(api, grid, m, y):
    fac = api.factorize_window_batched(
        api.BandedCTSF(grid, *(np.stack([a, a]) for a in (m.Dr, m.R, m.C))))
    ld = api.concurrent_logdet(fac)
    x = api.concurrent_solve(fac, y)
    return np.asarray(ld), np.asarray(x)


def test_program_spans_reach_the_profiler_trace():
    """Each span of a small θ step is on the trace's host plane with its
    name, its nesting and its duration (10 % or 50 µs)."""
    import jax
    from repro import api
    from repro.data import make_arrowhead
    from repro.runtime import telemetry

    A, struct = make_arrowhead(96, 8, 4, rho=0.6, seed=0)
    grid = api.TileGrid(struct, t=8)
    m = api.BandedCTSF.from_sparse(A, grid)
    y = jax.numpy.ones(grid.padded_n, np.float32)
    _theta_step(api, grid, m, y)                       # compile outside
    with tempfile.TemporaryDirectory() as tmp:
        telemetry.enable()
        telemetry.reset()
        jax.profiler.start_trace(tmp)
        try:
            _theta_step(api, grid, m, y)
        finally:
            jax.profiler.stop_trace()
            spans = telemetry.snapshot()["spans"]
            telemetry.disable()
            telemetry.reset()
        events = program.load(tmp, {s["name"] for s in spans})
    assert {s["name"] for s in spans if s["parent"] is None} == {
        "factorize.window_batched", "concurrent.logdet", "concurrent.solve"}
    assert sorted(s["name"] for s in spans) == sorted(e[0] for e in events)
    # pair each span with its event: same name, same order of opening
    spans = sorted(spans, key=lambda s: s["ts_us"])
    events = sorted(events, key=lambda e: e[1])
    assert [s["name"] for s in spans] == [e[0] for e in events]
    ev = {s["id"]: e for s, e in zip(spans, events)}
    for s, (_, start, dur) in zip(spans, events):
        assert abs(dur / 1e3 - s["dur_us"]) <= max(0.1 * s["dur_us"], 50)
        if s["parent"] is not None:
            _, p0, pd = ev[s["parent"]]
            assert p0 <= start and start + dur <= p0 + pd


def test_attribute_rehearsal(tmp_path):
    """The diagnostic window at a tiny size on the CPU: every annotated
    span lies inside its step and matches the snapshot, and each bench.*
    call holds the entry points the step calls there."""
    import jax
    from chipbench import attribute
    from chipbench.tests.test_harness import tiny_tree
    here, _ = tiny_tree(tmp_path)
    cell = {"name": "theta_sweep.tiny", "config": "tiny",
            "traffic": "theta_sweep", "chips": 1}
    res = attribute.attribute(cell, 12345678901, 0.3, jax.devices("cpu")[0],
                              here=here)
    assert res["steps"] >= 1
    assert res["annotated_spans"] == res["snapshot_spans"] > 0
    assert res["annotated_outside_their_step"] == 0
    calls = res["bench_call_ms_per_step"]
    assert set(calls["bench.factorize"][1]) == {"factorize.window_batched"}
    assert set(calls["bench.logdet"][1]) == {"concurrent.logdet"}
    assert set(calls["bench.solve"][1]) == {"concurrent.solve"}
    for call, (total, inside) in calls.items():
        assert sum(inside.values()) <= total
    spans = res["span_ms_per_step"]
    assert spans["factorize.enqueue"] <= spans["factorize.window_batched"]
    assert spans["solve.enqueue"] <= spans["concurrent.solve"]
    # no device plane on the CPU: the whole window is idle, so the share
    # held by program spans is their cover, which the snapshot mapped
    # onto the trace's clock reproduces
    pct = res["program_idle_pct"]
    assert pct["mapped"] == pytest.approx(pct["annotated"], abs=2.0)
