"""Trace reduction on a small trace built here."""
import pytest

from chipbench import counts
from chipbench.trace import STEP, Summary, module_name

MS = 1_000_000


def small_trace():
    # two steps on the host, 0-10 ms and 12-20 ms
    host = [(STEP, 0, 10 * MS), (STEP, 12 * MS, 8 * MS),
            ("bench.factorize", 0, 1 * MS), ("bench.readback", 1 * MS, 9 * MS),
            ("bench.form", 12 * MS, 1 * MS)]
    # device: one factorization executable per step, plus a small one
    modules = [("jit_fact(11)", 1 * MS, 6 * MS),
               ("jit_fact(12)", 13 * MS, 4 * MS),
               ("jit_small(3)", 8 * MS, 1 * MS)]
    ops = [("band_cholesky_kernel", 1 * MS, 5 * MS),
           ("fusion.1", 6 * MS, 1 * MS),
           ("fusion.2", 8 * MS, 1 * MS),
           ("band_cholesky_kernel", 13 * MS, 4 * MS),
           ("late", 30 * MS, 5 * MS)]          # outside the window
    return Summary(ops, modules, host)


def test_busy_idle_and_window():
    s = small_trace()
    assert s.window_s == pytest.approx(0.020)
    # union of [1,7), [8,9), [13,17) inside [0, 20)
    assert s.busy_s == pytest.approx(0.011)
    assert s.idle_share() == pytest.approx(1 - 11 / 20)


def test_executables_and_kernel_runs():
    s = small_trace()
    assert module_name("jit__lambda_(1234)") == "jit__lambda_"
    ex = s.executables()
    assert ex["jit_fact"][0] == 2
    assert ex["jit_fact"][1] == pytest.approx(0.010)
    runs = s.runs_with_op("band_cholesky")
    assert [r[0] for r in runs] == ["jit_fact", "jit_fact"]
    assert sum(r[1] for r in runs) == pytest.approx(0.010)


def test_gaps_are_named_by_the_innermost_host_annotation():
    g = small_trace().gaps()
    # idle [0,1) [7,8) [9,13) [17,20): [0,1) under bench.factorize,
    # [7,8) and [9,10) under bench.readback, [10,12) between the steps,
    # [12,13) under bench.form, [17,20) under the step alone
    assert g == pytest.approx({"bench.factorize": 0.001,
                               "bench.readback": 0.002,
                               "outside bench.*": 0.002,
                               "bench.form": 0.001, STEP: 0.003})
    b = small_trace().breakdown()
    assert b["device_ops"][0] == ["band_cholesky_kernel", pytest.approx(0.009)]
    assert len(b["idle_gaps"]) <= 10


def test_roofline_share_and_silence():
    s = small_trace()
    ctx = {"trace": s, "units_per_step": 8, "device_kind": "TPU v5 lite",
           "dep": type("D", (), {"n_diag": 100_000, "bandwidth": 1000,
                                 "arrow": 200})()}
    share = counts.roofline_share(ctx, counts.cholesky, "band_cholesky")
    f, b = counts.cholesky(100_000, 1000, 200)
    least = counts.least_seconds(f, b, counts.peaks("TPU v5 lite"))
    assert share == pytest.approx(100 * least * 2 * 8 / 0.010)
    # no such kernel in the trace: the reader finds nothing to read
    assert counts.roofline_share(ctx, counts.selinv, "selinv_sweep") is None
    assert Summary([], [], []).idle_share() is None
