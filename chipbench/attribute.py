"""One traced window of a cell, its device idle put down to the program's
own spans as the profiler recorded them.

    python3 -m chipbench.attribute --workload <cell> --seed <n> \\
        --seconds <s> [--out <file.json>]

From the root of a checkout, on the chip the cell asks for.  It runs the
cell's set-up and a window of whole steps as ``chipbench.run --trace 1``
does, without the reference check, and keeps the trace long enough to
read the program's annotations (``program.load``), which the harness does
not keep.  It prints, and writes to ``--out`` as JSON: the idle seconds by
innermost program span read from those annotations and from the snapshot
mapped onto the trace's clock (``program.on_trace_clock``, what the
``program_idle.*`` readers see); the host milliseconds per step of each
span, and of each ``bench.*`` call with the outermost spans inside it;
how many program spans lie outside their step; and the stats the trace
gives the device's longest ops (their op metadata, where present).
"""
import argparse
import collections
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import harness, program, trace  # noqa: E402


def _per_step(events, steps):
    out = collections.Counter()
    for name, _, d in events:
        out[name] += d / 1e6 / steps
    return dict(sorted(out.items()))


def _op_stats(directory, device, names):
    """The stats of the first event of each op in ``names`` on the
    device's op line."""
    import glob
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != f"/device:TPU:{device.id}":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                name = trace.op_name(e.name)
                if name in names and name not in out:
                    out[name] = [str(x) for x in e.stats]
    return out


def _outermost(events):
    """The events no other event encloses."""
    return [e for e in events
            if not any(o is not e and o[1] <= e[1]
                       and e[1] + e[2] <= o[1] + o[2]
                       and (o[2] > e[2] or o[1] < e[1]) for o in events)]


def attribute(cell: dict, seed: int, seconds: float, device,
              here: str = harness.HERE) -> dict:
    """One traced window of ``cell`` read against the program's own
    annotations."""
    import jax
    from chipbench import tiles
    traffic = harness.load_traffic(cell["traffic"], here)
    dep = tiles.Deployment.from_config(harness.load_config(cell["config"],
                                                           here))
    wl = harness.load_kind(traffic["kind"], here)(dep, traffic, seed)
    wl.setup()
    jax.effects_barrier()
    steps = 0
    with harness._profiled(True) as prof:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with jax.profiler.TraceAnnotation(trace.STEP):
                wl.step(steps)
            steps += 1
    try:
        summary = trace.load(prof["dir"], device)
        spans = prof["spans"]
        annotated = program.load(prof["dir"], {s["name"] for s in spans})
        top_ops = [k for k, _ in summary.breakdown()["device_ops"]]
        stats = _op_stats(prof["dir"], device, top_ops)
    finally:
        shutil.rmtree(prof["dir"], ignore_errors=True)

    mapped = program.on_trace_clock(summary, spans)
    step_ivs = [(s, s + d) for n, s, d in summary.host if n == trace.STEP]
    calls = collections.defaultdict(lambda: [0.0, collections.Counter()])
    tops = _outermost(annotated)
    for name, s, d in summary.host:
        if name != trace.STEP:
            rec = calls[name]
            rec[0] += d / 1e6 / steps
            for pn, ps, pd in tops:
                if s <= ps and ps + pd <= s + d:
                    rec[1][pn] += pd / 1e6 / steps

    def idle_pct(events):
        g = program.gaps(summary, events)
        held = sum(v for k, v in g.items() if k != program.OUTSIDE)
        return g, (100.0 * held / summary.window_s
                   if summary.window_s else None)

    by_annotated, pct_annotated = idle_pct(annotated)
    by_mapped, pct_mapped = idle_pct(mapped)
    return {
        "cell": cell["name"], "seed": seed, "steps": steps,
        "window_s": summary.window_s, "busy_s": summary.busy_s,
        "snapshot_spans": len(spans), "annotated_spans": len(annotated),
        "annotated_outside_their_step": sum(
            not any(a <= s and s + d <= b for a, b in step_ivs)
            for _, s, d in annotated),
        "program_idle_pct": {"annotated": pct_annotated,
                             "mapped": pct_mapped},
        "idle_by_annotated_span": by_annotated,
        "idle_by_mapped_span": by_mapped,
        "idle_by_bench_call": summary.gaps(),
        "span_ms_per_step": _per_step(annotated, steps),
        "bench_call_ms_per_step": {k: [v[0], dict(v[1])]
                                   for k, v in sorted(calls.items())},
        "executables": summary.executables(),
        "device_ops": summary.breakdown()["device_ops"],
        "op_stats": stats,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out")
    args = p.parse_args(argv)

    bench = harness.load_benchmark(ROOT)
    cell = harness.find_cell(bench, args.workload)
    harness.configure_jax(ROOT)
    devices = harness.require_chips(cell["chips"])
    result = attribute(cell, args.seed, args.seconds, devices[0])
    text = json.dumps(result, indent=1, default=repr)
    print(text, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
