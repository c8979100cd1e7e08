"""The program's own spans on the profiler's clock, and the device's idle
time put down to them.

While telemetry is enabled every span of the program
(``repro.runtime.telemetry``) also opens a profiler annotation of its
name, so a traced window holds them on the host plane beside the
benchmark's ``bench.*`` annotations.  :func:`load` reads them from an
``.xplane.pb``.  The harness hands a per-layer reader only the
``bench.*`` annotations and the telemetry snapshot (it keeps no other
host events and removes the trace before the readers run), so
:func:`on_trace_clock` maps the snapshot's spans onto the profiler's clock
instead: every outermost span runs inside one ``bench.*`` call
annotation, opened a few microseconds before it and closed a few after,
which pins the offset between the two clocks from both sides.

:func:`gaps` names each idle stretch of the window by the innermost
program span open at its middle, or ``outside program``.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import sys

from chipbench.trace import STEP

OUTSIDE = "outside program"


def load(directory: str, names) -> list:
    """The host-plane events under ``directory`` whose names are in
    ``names``: [(name, start_ns, duration_ns)] on the profiler's clock."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    names = set(names)
    return [(e.name, int(e.start_ns), int(e.duration_ns))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name in names]


def _holder(calls, starts, t):
    """The call annotation open at ``t``, or None."""
    i = bisect.bisect_right(starts, t) - 1
    return calls[i] if i >= 0 and t < calls[i][1] + calls[i][2] else None


def on_trace_clock(summary, spans: list) -> list:
    """The telemetry snapshot's ``spans`` as [(name, start_ns,
    duration_ns)] on the clock of ``summary``'s trace, or [] where no
    outermost span falls inside the ``bench.*`` call annotations.

    Each offset that opens the first outermost span with one of the first
    step's call annotations pairs every outermost span with the call
    annotation open at its middle.  Of those pairings, the one with the
    most pairs that some offset keeps every paired span inside its
    annotation wins (steps repeat, so a wrong offset can pair as many);
    the offset is the middle of those that do."""
    by_start = lambda e: e[1]
    calls = sorted((e for e in summary.host if e[0] != STEP), key=by_start)
    steps = sorted((e for e in summary.host if e[0] == STEP), key=by_start)
    top = sorted((s["ts_us"] * 1e3, (s["ts_us"] + s["dur_us"]) * 1e3)
                 for s in spans if s["parent"] is None)
    if not calls or not top:
        return []
    starts = [c[1] for c in calls]
    first_end = steps[0][1] + steps[0][2] if steps else calls[-1][1]
    best, off = (0, False), None
    for c in calls:
        if c[1] > first_end:
            break
        shift = c[1] - top[0][0]
        pairs = [(h, s, e) for s, e in top
                 if (h := _holder(calls, starts, (s + e) / 2 + shift))]
        if not pairs:
            continue
        lo = max(h[1] - s for h, s, _ in pairs)
        hi = min(h[1] + h[2] - e for h, _, e in pairs)
        if (len(pairs), lo <= hi) > best:
            best, off = (len(pairs), lo <= hi), (lo + hi) / 2
    if off is None:
        return []
    return [(s["name"], round(s["ts_us"] * 1e3 + off),
             round(s["dur_us"] * 1e3)) for s in spans]


def _innermost(program, t: float) -> str:
    open_ = [(s, -d, name) for name, s, d in program if s <= t < s + d]
    return max(open_)[2] if open_ else OUTSIDE


def gaps(summary, program: list) -> dict:
    """{innermost program span open: idle seconds} over the window's idle
    stretches, each stretch cut where a program span opens or closes."""
    bounds = ([summary.t0] + [x for iv in summary._busy for x in iv]
              + [summary.t1])
    edges = sorted({x for _, s, d in program for x in (s, s + d)})
    out = collections.Counter()
    for s, e in zip(bounds[::2], bounds[1::2]):
        inner = edges[bisect.bisect_right(edges, s):
                      bisect.bisect_left(edges, e)]
        cuts = [s] + inner + [e]
        for a, b in zip(cuts, cuts[1:]):
            if b > a:
                out[_innermost(program, (a + b) / 2)] += (b - a) / 1e9
    return dict(out)


def idle_percent(ctx: dict):
    """Percent of the traced window in which no device op runs while a
    program span is open on the host; None without program spans or
    device ops.  Logs the idle time by program span."""
    summary = ctx["trace"]
    program = on_trace_clock(summary, ctx["spans"])
    if not program or summary.idle_share() is None:
        return None
    by_span = gaps(summary, program)
    print(f"chipbench: idle by program span {by_span!r}", file=sys.stderr)
    held = sum(v for k, v in by_span.items() if k != OUTSIDE)
    return 100.0 * held / summary.window_s


def enqueue_ms_per_step(ctx: dict):
    """Host milliseconds per step inside the program's ``*.enqueue``
    spans, its calls into compiled executables; None without them, or
    where the trace holds no device ops (the calls then enqueue nothing
    on a device)."""
    spans = [s for s in ctx["spans"] if s["name"].endswith(".enqueue")]
    if not spans or ctx["trace"].idle_share() is None:
        return None
    return sum(s["dur_us"] for s in spans) / 1e3 / ctx["steps"]
