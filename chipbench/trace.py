"""Reduction of a profiler trace to the numbers the per-layer readers use.

The profiler writes an ``.xplane.pb``; :func:`load` reads the device
plane's op and module events and the host's ``bench.*`` annotations
(``workload.py`` puts one around each call into the program and around
each step) and hands them to :class:`Summary`, which works on plain
``(name, start_ns, duration_ns)`` events.

* busy: the union of the device-op intervals inside the traced window,
  which runs from the first step's start to the last step's end;
* per-executable device time: module events summed by module name;
* idle gaps: the stretches of the window no device op covers, each named
  by the innermost ``bench.*`` annotation open on the host at its middle.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

STEP = "bench.step"


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events, lo, hi):
    return [(max(s, lo), min(s + d, hi)) for _, s, d in events
            if s < hi and s + d > lo]


def op_name(name: str) -> str:
    """``%band_cholesky_sweep_pallas.1 = (f32[...]) custom-call(...)`` ->
    ``band_cholesky_sweep_pallas.1``: the HLO instruction's own name."""
    return name.split(" = ", 1)[0].lstrip("%")


def module_name(name: str) -> str:
    """``jit_foo(123)`` -> ``jit_foo``: the executable without its run id."""
    return re.sub(r"\(\d+\)$", "", name)


@dataclasses.dataclass
class Summary:
    ops: list          # device ops: (name, start_ns, duration_ns)
    modules: list      # executables run on the device, same form
    host: list         # bench.* annotations on the host, same form

    def __post_init__(self):
        steps = [e for e in self.host if e[0] == STEP]
        if steps:
            self.t0 = min(s for _, s, _ in steps)
            self.t1 = max(s + d for _, s, d in steps)
        else:
            evs = self.ops + self.modules
            self.t0 = min((s for _, s, _ in evs), default=0)
            self.t1 = max((s + d for _, s, d in evs), default=0)
        self._busy = _union(_clip(self.ops, self.t0, self.t1))

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def busy_s(self) -> float:
        return sum(e - s for s, e in self._busy) / 1e9

    def idle_share(self):
        """Idle share of the traced window, or None without device ops."""
        if not self._busy or self.t1 <= self.t0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def executables(self) -> dict:
        """{module name: (runs, device seconds)} inside the window."""
        out = collections.defaultdict(lambda: [0, 0.0])
        for name, s, d in self.modules:
            if self.t0 <= s < self.t1:
                rec = out[module_name(name)]
                rec[0] += 1
                rec[1] += d / 1e9
        return {k: tuple(v) for k, v in out.items()}

    def runs_with_op(self, pattern: str) -> list:
        """Executable runs inside the window during which a device op
        whose name matches ``pattern`` started: [(module, seconds)]."""
        rx = re.compile(pattern)
        hits = sorted(s for name, s, _ in self.ops if rx.search(name))
        out = []
        for name, s, d in self.modules:
            if self.t0 <= s < self.t1:
                i = bisect.bisect_left(hits, s)
                if i < len(hits) and hits[i] < s + d:
                    out.append((module_name(name), d / 1e9))
        return out

    def ops_by_name(self) -> dict:
        out = collections.Counter()
        for name, s, d in self.ops:
            if self.t0 <= s < self.t1:
                out[name] += d / 1e9
        return dict(out)

    def gaps(self) -> dict:
        """{host label: idle seconds} over the window's idle stretches,
        each stretch cut where a host annotation opens or closes."""
        bounds = [self.t0] + [x for iv in self._busy for x in iv] + [self.t1]
        edges = sorted({x for _, s, d in self.host for x in (s, s + d)})
        out = collections.Counter()
        for s, e in zip(bounds[::2], bounds[1::2]):
            cuts = [s] + [x for x in edges if s < x < e] + [e]
            for a, b in zip(cuts, cuts[1:]):
                if b > a:
                    out[self._label((a + b) / 2)] += (b - a) / 1e9
        return dict(out)

    def _label(self, t: float) -> str:
        """The innermost annotation open at ``t``: the last opened, the
        shortest among those opened together."""
        open_ = [(s, -d, name) for name, s, d in self.host if s <= t < s + d]
        return max(open_)[2] if open_ else "outside bench.*"

    def breakdown(self) -> dict:
        top = lambda d: sorted(([k, v] for k, v in d.items()),
                               key=lambda kv: -kv[1])[:10]
        return {"device_ops": top(self.ops_by_name()),
                "idle_gaps": top(self.gaps())}


def idle_percent(ctx: dict):
    """Percent of the traced window in which no operation ran on the
    device; None without device ops."""
    idle = ctx["trace"].idle_share()
    return None if idle is None else 100.0 * idle


def span_ms_per_step(ctx: dict):
    """Host milliseconds per step inside the program's own outermost
    telemetry spans (its entry points' dispatch, not the device's work);
    None without spans."""
    spans = [s for s in ctx["spans"] if s["parent"] is None]
    if not spans:
        return None
    return sum(s["dur_us"] for s in spans) / 1e3 / ctx["steps"]


def load(directory: str, device) -> Summary:
    """The Summary of the trace under ``directory`` for ``device``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops, modules, host = [], [], []
    want = f"/device:TPU:{device.id}"
    for plane in data.planes:
        if plane.name == want:
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is ops:
                    ops.extend((op_name(e.name), int(e.start_ns),
                                int(e.duration_ns)) for e in line.events)
                elif dest is not None:
                    dest.extend((e.name, int(e.start_ns), int(e.duration_ns))
                                for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if e.name.startswith("bench."))
    return Summary(ops, modules, host)
