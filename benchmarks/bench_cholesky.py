"""Fused factorization path: one-launch Pallas sweeps vs the scan paths.

Two comparisons on the same banded-arrowhead problem:

* ``factorize_window(options=SolverOptions(impl="pallas"))`` — the whole band + arrow
  factorization as **one** ``kernels.band_cholesky`` launch (VMEM panel
  ring, in-kernel potrf/trsm, corner Schur accumulated on the fly) — vs
  ``impl="ref"``, the ring-buffer ``lax.scan`` dispatching per-panel ops.
* ``selected_inverse(options=SolverOptions(impl="pallas"))`` — the whole Takahashi recurrence as
  one ``kernels.selinv_sweep`` launch — vs the per-column scan.

Gating is on **counted kernel launches**, not wall time: the fused sweeps
must trace to exactly one ``pallas_call`` each (counted by jaxpr
traversal), versus the 3·ndt (potrf + trsm + band-update) / 2·ndt
(solve_panel + selinv_step) per-panel launches the pre-fusion paths
dispatched.  Launch counts are backend-independent, so this gate holds on
CPU CI; wall-clock timings on non-TPU hosts run the kernels in interpret
mode and are recorded under ``interpret_diagnostics`` only (run.py
excludes that block from gating), becoming top-level gated metrics on
real TPU hardware.

Emits a ``BENCH_cholesky.json`` trajectory point at the repo root.
"""
from __future__ import annotations

import json
import os
import time

import jax

from repro.core import (BandedCTSF, TileGrid, factorize_window,
                        selected_inverse)
from repro.kernels import ops
from repro.kernels.ring import band_row_to_col
# single library implementation of the launch counter + static cost model
# (ISSUE 7: the bench imports it, it no longer defines its own copy)
from repro.runtime.telemetry import count_pallas_launches, kernel_report
from repro.core.options import SolverOptions

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _time(fn, reps=2):
    fn()  # warmup / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run(quick: bool = True):
    from repro.data import make_arrowhead

    n, bw, ar, t = (1024, 32, 16, 16) if quick else (4096, 64, 32, 32)
    A, struct = make_arrowhead(n, bw, ar, rho=0.6, seed=0)
    grid = TileGrid(struct, t=t)
    bm = BandedCTSF.from_sparse(A, grid)
    ndt = grid.n_diag_tiles
    backend = jax.default_backend()
    interpret = backend != "tpu"

    # --- launch counts + static costs (backend-independent, the CI gate) ---
    Ac = band_row_to_col(bm.Dr)
    fact_report = kernel_report(
        lambda a, r: ops.band_cholesky_sweep(a, r, nchunks=8, impl="pallas"),
        Ac, bm.R, grid=grid, sweep="cholesky")
    fused_fact_launches = fact_report.pallas_launches
    f0 = factorize_window(bm, options=SolverOptions(impl="ref"))
    ctsf = f0.ctsf
    nat = grid.n_arrow_tiles
    sc_shape = jax.ShapeDtypeStruct((nat, nat, t, t), ctsf.C.dtype)
    selinv_report = kernel_report(
        lambda l, r, s: ops.selinv_sweep(l, r, s, impl="pallas"),
        band_row_to_col(ctsf.Dr), ctsf.R, sc_shape, grid=grid,
        sweep="selinv")
    fused_selinv_launches = selinv_report.pallas_launches
    # the pre-fusion per-panel dispatch counts (one potrf + trsm +
    # band-update launch per band panel; one solve_panel + selinv_step per
    # selinv column)
    scan_fact_launches = 3 * ndt
    scan_selinv_launches = 2 * ndt
    fact_reduction = scan_fact_launches / max(fused_fact_launches, 1)
    selinv_reduction = scan_selinv_launches / max(fused_selinv_launches, 1)

    rows = [("cholesky_fused_launches", float(fused_fact_launches),
             f"scan_equiv={scan_fact_launches};reduction={fact_reduction:.0f}x"),
            ("selinv_fused_launches", float(fused_selinv_launches),
             f"scan_equiv={scan_selinv_launches};reduction={selinv_reduction:.0f}x")]

    # --- partitioned sweep: one 2D launch over all ND partitions ----------
    # a block-separable problem (the post-adaptive-ND shape): the whole
    # multi-partition factorization must still be ONE counted launch, its
    # sequential grid axis must shrink from ndt to the largest partition
    # (+ the separator handled densely after the tree combine), and the
    # partition decomposition must be bit-identical to the fused oracle.
    from repro.core import detect_partition_plan
    from repro.data import block_separable_arrowhead
    n_parts = 4
    Ab, structb, bounds = block_separable_arrowhead(
        n, bw, ar, t, n_parts=n_parts, rho=0.6, seed=0)
    gridb = TileGrid(structb, t=t)
    mb = BandedCTSF.from_sparse(Ab, gridb)
    plan = detect_partition_plan(Ab, structb, t)
    assert plan.boundaries == bounds and plan.n_partitions == n_parts
    Acb = band_row_to_col(mb.Dr)
    part_report = kernel_report(
        lambda a, r: ops.band_cholesky_partitioned_sweep(
            a, r, plan.boundaries, impl="pallas"),
        Acb, mb.R)
    part_launches = part_report.pallas_launches
    seq_depth = plan.max_tiles                  # length of the 2D grid's
    seq_bound = plan.max_tiles + plan.sep_tiles  # sequential axis
    depth_reduction = gridb.n_diag_tiles / max(seq_depth, 1)
    # bit-identity vs the fused oracle, within one backend (CPU CI = ref)
    p_f, r_f, _, _ = ops.band_cholesky_sweep(Acb, mb.R, nchunks=1,
                                             impl="ref")
    p_p, r_p, _, _ = ops.band_cholesky_partitioned_sweep(
        Acb, mb.R, plan.boundaries, impl="ref")
    import numpy as _np
    part_bit_identical = (
        _np.asarray(p_f).tobytes() == _np.asarray(p_p).tobytes()
        and _np.asarray(r_f).tobytes() == _np.asarray(r_p).tobytes())
    # a trivial (single-partition) plan must reproduce the plan-less fused
    # factorization bit for bit, corner included
    from repro.core.ordering import PartitionPlan
    triv = PartitionPlan.trivial(gridb.n_diag_tiles)
    f_triv = factorize_window(
        mb, options=SolverOptions(impl="ref", partition_plan=triv))
    f_none = factorize_window(mb, options=SolverOptions(impl="ref"))
    trivial_bit_identical = all(
        _np.asarray(a).tobytes() == _np.asarray(b).tobytes()
        for a, b in zip(f_triv.ctsf.arrays(), f_none.ctsf.arrays()))
    rows.append(("partitioned_launches", float(part_launches),
                 f"partitions={n_parts};seq_depth={seq_depth}"
                 f"(bound={seq_bound});depth_reduction="
                 f"{depth_reduction:.1f}x"))
    rows.append(("partitioned_bit_identical", float(part_bit_identical),
                 f"trivial_plan_bit_identical={trivial_bit_identical}"))

    # --- timings: fused vs scan (interpret-mode diagnostics off-TPU) -------
    def fact_fused():
        jax.block_until_ready(factorize_window(bm, options=SolverOptions(impl="pallas")).ctsf.Dr)

    def fact_scan():
        jax.block_until_ready(factorize_window(bm, options=SolverOptions(impl="ref")).ctsf.Dr)

    t_ff = _time(fact_fused)
    t_fs = _time(fact_scan)

    def si_fused():
        jax.block_until_ready(selected_inverse(f0, options=SolverOptions(impl="pallas")).Dr)

    def si_scan():
        jax.block_until_ready(selected_inverse(f0, options=SolverOptions(impl="ref")).Dr)

    t_sf = _time(si_fused)
    t_ss = _time(si_scan)
    tag = "[interpret-diagnostic]" if interpret else ""
    rows.append((f"factorize_fused{tag}", t_ff * 1e6,
                 f"scan_us={t_fs*1e6:.0f};backend={backend}"))
    rows.append((f"selinv_fused{tag}", t_sf * 1e6,
                 f"scan_us={t_ss*1e6:.0f};backend={backend}"))

    record = {
        "bench": "cholesky",
        "quick": quick,
        "problem": {"n": n, "bandwidth": bw, "arrow": ar, "t": t,
                    "ndt": ndt, "band_tiles": grid.band_tiles,
                    "arrow_tiles": nat},
        "fused_factorize_launches": fused_fact_launches,
        "scan_factorize_launch_equiv": scan_fact_launches,
        "factorize_launch_reduction": fact_reduction,
        "fused_selinv_launches": fused_selinv_launches,
        "scan_selinv_launch_equiv": scan_selinv_launches,
        "selinv_launch_reduction": selinv_reduction,
        # static per-sweep cost estimates from telemetry.kernel_report
        # (flops / bytes-moved / arithmetic intensity under the shared
        # roofline hardware model) — informational, never gated
        "kernel_report": {
            "cholesky": {"flops": fact_report.flops,
                         "bytes_moved": fact_report.bytes_moved,
                         "intensity": fact_report.intensity,
                         "bound": fact_report.bound},
            "selinv": {"flops": selinv_report.flops,
                       "bytes_moved": selinv_report.bytes_moved,
                       "intensity": selinv_report.intensity,
                       "bound": selinv_report.bound},
        },
        # partitioned-sweep gates (ISSUE 10): the multi-partition
        # factorization is one counted launch, its sequential depth is
        # bounded by the largest partition + the separator, and both the
        # partition decomposition and the trivial plan are bit-identical
        # to the fused path
        "partitioned_problem": {"n_parts": n_parts,
                                "boundaries": list(plan.boundaries),
                                "sep_tiles": plan.sep_tiles,
                                "ndt": gridb.n_diag_tiles,
                                "seq_depth": seq_depth,
                                "seq_depth_bound": seq_bound,
                                "depth_reduction": depth_reduction},
        "partitioned_launches": part_launches,
        "partitioned_single_launch": float(part_launches == 1),
        "partitioned_depth_within_bound": float(seq_depth <= seq_bound
                                                and seq_depth
                                                < gridb.n_diag_tiles),
        "partitioned_bit_identical": float(part_bit_identical),
        "trivial_plan_bit_identical": float(trivial_bit_identical),
        "backend": backend,
        # interpret-mode timings never gate; launch counts do.  On TPU the
        # speedups graduate to top-level gated metrics.
        "thresholds": {"factorize_launch_reduction_min": 8.0,
                       "selinv_launch_reduction_min": 8.0,
                       "partitioned_single_launch_min": 1.0,
                       "partitioned_depth_within_bound_min": 1.0,
                       "partitioned_bit_identical_min": 1.0,
                       "trivial_plan_bit_identical_min": 1.0},
    }
    timing = {
        "factorize_fused_us": t_ff * 1e6,
        "factorize_scan_us": t_fs * 1e6,
        "factorize_fused_speedup": t_fs / t_ff,
        "selinv_fused_us": t_sf * 1e6,
        "selinv_scan_us": t_ss * 1e6,
        "selinv_fused_speedup": t_ss / t_sf,
    }
    passing = (fused_fact_launches == 1 and fused_selinv_launches == 1
               and fact_reduction >= 8.0 and selinv_reduction >= 8.0
               and part_launches == 1
               and seq_depth <= seq_bound and seq_depth < gridb.n_diag_tiles
               and part_bit_identical and trivial_bit_identical)
    if interpret:
        record["interpret_diagnostics"] = {**timing, "interpret_mode": True}
    else:
        record.update(timing)
        record["thresholds"].update({"factorize_fused_speedup_min": 1.2,
                                     "selinv_fused_speedup_min": 1.2})
        passing = passing and timing["factorize_fused_speedup"] >= 1.2 \
            and timing["selinv_fused_speedup"] >= 1.2
    record["pass"] = bool(passing)
    with open(os.path.join(_ROOT, "BENCH_cholesky.json"), "w") as f:
        json.dump(record, f, indent=2)
    return rows


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for row in run(quick=True):
        print(f"{row[0]},{row[1]:.1f},{row[2]}")
