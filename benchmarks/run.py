"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--full`` uses paper-scale
matrices (long on CPU); the default is structure-preserving scaled versions.

  Table I     -> bench_accumulation   (sequential GEMM/SYRK chains vs tree)
  Fig. 10     -> bench_libraries      (sTiles vs dense/sparse baselines)
  Fig. 11     -> bench_scalability    (DAG width/depth + speedup bounds)
  Fig. 12     -> bench_tree_reduction (tree on/off, matrices 2 & 14)
  Fig. 13     -> bench_libraries (dense crossover column)
  Table III   -> bench_tile_size      (+ accelerator tile-size terms)
  App. A      -> bench_concurrent     (concurrent factorizations, precond)
  Serving     -> bench_solve          (multi-RHS sweeps, batched factorize;
                                       writes BENCH_solve.json)
  Selinv      -> bench_selinv         (Takahashi recurrence vs dense-panel
                                       marginals vs np.linalg.inv; writes
                                       BENCH_selinv.json)
  Fused fact. -> bench_cholesky       (one-launch factorization/selinv vs
                                       scan: launch counts + timings;
                                       writes BENCH_cholesky.json)
  §Roofline   -> roofline             (from dry-run artifacts)

  Bucketing   -> bench_bucketing     (canonical-grid policy: compile
                                      counts for a mixed-grid stream;
                                      writes BENCH_bucketing.json)

  Rung server -> bench_serving       (continuous-batching front-end:
                                      seeded Poisson replay, throughput +
                                      latency percentiles, compile-per-rung
                                      and bit-exact-replay gates; writes
                                      BENCH_serving.json)

  Chaos       -> bench_chaos         (rung server under seeded faults +
                                      burst overload: conservation, closed
                                      status taxonomy, breaker isolation,
                                      bit-exact chaos replay; writes
                                      BENCH_chaos.json)

``--check-only`` validates every committed ``BENCH_*.json`` against its
embedded thresholds without re-running anything — the fast CI gate
against landing a record that fails its own pass criteria.  Suites
listed in ``RECORD_SUITES`` *must* have a committed record: a deleted
(or never-committed) ``BENCH_<suite>.json`` fails the check, so a
regression cannot slip in by dropping its record.  Timings recorded
under a record's ``interpret_diagnostics`` block (Pallas interpret-mode
numbers on non-TPU hosts) are never gated, in check-only or full runs;
fused-kernel records gate on counted launches instead.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
import traceback

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# suites that emit a BENCH_<name>.json trajectory point; --check-only
# requires each of these records to exist at the repo root (and pass its
# own thresholds), so deleting a record cannot silently pass CI
RECORD_SUITES = ("solve", "selinv", "cholesky", "bucketing", "robustness",
                 "serving", "chaos")


def _record_failures(record: dict) -> list:
    """Spell out which criteria a BENCH_*.json record misses.

    Threshold keys follow the ``<metric>_min`` convention (e.g.
    ``solve_many_speedup_min`` gates ``solve_many_speedup``).  A
    thresholded metric must exist at the record's top level — except
    metrics listed under ``interpret_diagnostics``, which are
    interpret-mode-only timings and are consistently excluded from
    gating.  ``pass: false`` fails regardless."""
    diag = record.get("interpret_diagnostics") or {}
    out = []
    for name, lo in (record.get("thresholds") or {}).items():
        metric = name[: -len("_min")] if name.endswith("_min") else name
        if metric in diag:
            continue
        val = record.get(metric)
        if val is None:
            out.append(f"{metric} missing (gated by threshold {name})")
        elif isinstance(val, (int, float)) and val < lo:
            out.append(f"{metric}={val:.3g} (min {lo:.3g})")
    if record.get("pass") is False:
        out.append("record has pass=false")
    return out


def _gated_metrics_cell(record: dict) -> str:
    """Compact ``metric=value(min lo)`` listing of a record's gated
    metrics for the check-only summary table (interpret-mode diagnostics
    excluded, like gating itself)."""
    diag = record.get("interpret_diagnostics") or {}
    cells = []
    for name, lo in sorted((record.get("thresholds") or {}).items()):
        metric = name[: -len("_min")] if name.endswith("_min") else name
        if metric in diag:
            continue
        val = record.get(metric)
        shown = f"{val:.3g}" if isinstance(val, (int, float)) else "?"
        cells.append(f"{metric}={shown}(min {lo:.3g})")
    return " ".join(cells) if cells else "-"


def check_records(root: str = _ROOT) -> int:
    """Validate all committed BENCH_*.json against their embedded
    thresholds; returns the number of failing records (printing a
    one-line-per-suite summary table, then every regressed suite).
    Every suite in ``RECORD_SUITES`` must have a committed record — a
    registered suite with no BENCH_<suite>.json fails."""
    bad = 0
    failing = []   # (suite/record name, reasons)
    paths = sorted(glob.glob(os.path.join(root, "BENCH_*.json")))
    for suite in RECORD_SUITES:
        expected = os.path.join(root, f"BENCH_{suite}.json")
        if expected not in paths:
            reason = (f"suite {suite!r} is registered in benchmarks/run.py "
                      "but has no committed record")
            print(f"FAIL: BENCH_{suite}.json — {reason}")
            failing.append((suite, [reason]))
            bad += 1
    if not paths:
        print("no BENCH_*.json records found", file=sys.stderr)
        return bad or 1
    rows = []
    for path in paths:
        with open(path) as f:
            record = json.load(f)
        name = os.path.basename(path)[len("BENCH_"): -len(".json")]
        reasons = _record_failures(record)
        rows.append((name, _gated_metrics_cell(record),
                     "FAIL" if reasons else "pass"))
        if reasons:
            failing.append((name, reasons))
            bad += 1
    widths = [max(len(r[i]) for r in rows + [("suite", "gated metrics",
                                              "status")]) for i in range(3)]
    fmt = "  ".join("{:<%d}" % w for w in widths)
    print(fmt.format("suite", "gated metrics", "status"))
    for row in rows:
        print(fmt.format(*row))
    if failing:
        print(f"\n{len(failing)} suite(s) failing:")
        for name, reasons in failing:
            for r in reasons:
                print(f"  {name}: {r}")
    else:
        print("\nall records pass")
    return bad


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--full", action="store_true")
    p.add_argument("--only", default=None)
    p.add_argument("--check-only", action="store_true",
                   help="validate committed BENCH_*.json thresholds "
                        "without re-running any benchmark")
    p.add_argument("--telemetry", default=None, metavar="PATH",
                   help="enable the runtime telemetry registry for the run "
                        "and dump a Chrome trace-event JSON (spans + a "
                        "'metrics' snapshot; open in Perfetto) to PATH")
    args = p.parse_args()
    quick = not args.full

    if args.check_only:
        raise SystemExit(1 if check_records() else 0)

    from repro.runtime import compile_cache, telemetry
    compile_cache.configure(_ROOT)
    if args.telemetry:
        telemetry.enable()

    from . import (bench_accumulation, bench_bucketing, bench_chaos,
                   bench_cholesky, bench_concurrent, bench_libraries,
                   bench_robustness, bench_scalability, bench_selinv,
                   bench_serving, bench_solve, bench_tile_size,
                   bench_tree_reduction, roofline)
    suites = {
        "accumulation": bench_accumulation,
        "libraries": bench_libraries,
        "scalability": bench_scalability,
        "tree_reduction": bench_tree_reduction,
        "tile_size": bench_tile_size,
        "concurrent": bench_concurrent,
        "solve": bench_solve,
        "selinv": bench_selinv,
        "cholesky": bench_cholesky,
        "bucketing": bench_bucketing,
        "robustness": bench_robustness,
        "serving": bench_serving,
        "chaos": bench_chaos,
        "roofline": roofline,
    }
    failures = []  # (suite, [reasons...])
    print("name,us_per_call,derived")
    for name, mod in suites.items():
        if args.only and args.only != name:
            continue
        t_start = time.time()
        try:
            with telemetry.span(f"bench.{name}", quick=quick):
                for row in mod.run(quick=quick):
                    print(f"{row[0]},{row[1]:.1f},{row[2]}")
                    sys.stdout.flush()
        except Exception as e:
            failures.append((name, [f"crashed: {type(e).__name__}: {e}"]))
            print(f"{name},ERROR,", flush=True)
            traceback.print_exc()
            continue
        # suites that emit a BENCH_<name>.json trajectory point also gate on
        # its `pass` flag — a speedup-threshold regression fails the run (and
        # therefore the CI benchmark step), not just the artifact.
        record_path = os.path.join(_ROOT, f"BENCH_{name}.json")
        if os.path.exists(record_path):
            if os.path.getmtime(record_path) >= t_start:
                print(f"# wrote {record_path}", flush=True)
            with open(record_path) as f:
                record = json.load(f)
            reasons = _record_failures(record)
            if reasons:
                failures.append((name, reasons))
                print(f"{name},THRESHOLD_FAIL,{';'.join(reasons)}",
                      flush=True)
    if args.telemetry:
        telemetry.write_trace(args.telemetry)
        print(f"# wrote telemetry trace {args.telemetry}", flush=True)
    if failures:
        print("\nFAILED benchmark suites:", file=sys.stderr)
        for name, reasons in failures:
            for r in reasons:
                print(f"  {name}: {r}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
