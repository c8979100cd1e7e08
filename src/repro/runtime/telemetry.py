"""Process-wide telemetry for the factorize/solve/selinv serving stack.

The paper's performance story rests on *seeing* the execution: sTiles
analyzes its static scheduler with per-task execution traces and balances
tile size against algorithmic intensity with per-kernel flop/byte counts
(§III-B, Table III).  This module is that layer for the serving stack —
two halves:

**Dynamic half** — a process-wide, thread-safe registry of

* **counters** (monotonic, e.g. cache hits per compile cache),
* **gauges** (last-write-wins point-in-time values),
* **histograms** (count/sum/min/max plus p50/p90/p99 over a bounded
  sample reservoir), and
* **nestable wall-clock spans** (per-thread stacks; every finished span
  records its parent, so exporters can rebuild the call tree).  While
  telemetry is enabled each span also opens a
  ``jax.profiler.TraceAnnotation`` of its name, so under a running
  profiler it lands on the trace's host plane, on the same clock as the
  device's ops; its tags stay in the registry.

Recording happens at *dispatch* level only — the Python host code around
``jax.jit`` boundaries — never inside traced computations, following the
PR 6 status-word pattern: anything that must be observed from inside a
traced sweep is carried out as a regular array output (the breakdown
status word of ``kernels.ops.band_cholesky_sweep``) and recorded here
after the host reads it back.  ``inc``/``observe`` coerce their value
with ``float(...)``, so accidentally passing a tracer fails loudly at the
call site instead of silently burying a host sync in a jitted function.

Telemetry is **disabled by default** (enable with :func:`enable`, the
``REPRO_TELEMETRY=1`` environment variable, or the :func:`capture`
context manager).  Every recording function bails on one flag check when
disabled, and :func:`span` returns a shared no-op context manager — the
tier-1 guard test asserts the disabled-mode cost of a fully instrumented
``solve_many`` dispatch stays under 5%.

**Serving resilience metrics** — the rung server's failure domains
(``launch/rung_server.py``) report through this registry so chaos runs
and production traces read identically.  Alongside the baseline serving
metrics (``serving.requests``, ``serving.flush {reason=}``,
``serving.batch_size``, ``serving.queue_wait``, ``serving.queue_depth
{rung=}``, ``serving.completed {outcome=ok|recovered|failed|shed}``,
``serving.request_seconds`` and the ``serving.dispatch`` /
``serving.finalize`` spans), the resilience layer emits counters
``serving.shed {detail=}`` (one per explicitly shed request, labeled
with the shed reason), ``serving.overload_reject {scope=rung|global}``
(typed admission rejections), ``serving.retry {rung=}`` /
``serving.bisect {rung=}`` / ``serving.quarantine {rung=}`` (the
recovery ladder), ``serving.dispatch_failure {kind=, rung=}``,
``serving.breaker_transition {state=, rung=}``, ``serving.straggler
{rung=}`` and ``serving.degradation_step {direction=up|down}``; gauges
``serving.degradation_level`` and ``serving.straggler_seconds {rung=}``;
and the per-batch device-time histogram ``serving.device_seconds
{rung=}`` that feeds the straggler monitor.

**Static half** — :func:`kernel_report` inspects a function *without
running it*: it traces to a jaxpr, counts ``pallas_call`` launch sites
(:func:`count_pallas_launches`, the gate behind ``BENCH_cholesky.json``),
and — given a :class:`~repro.core.structure.TileGrid` — attaches the
analytic per-sweep FLOP / bytes-moved estimates of :func:`sweep_cost`
plus the roofline terms of the hardware model shared with
``benchmarks/roofline.py`` (:data:`PEAK_FLOPS` / :data:`HBM_BW` live
here as the single source of truth).  Launch/intensity regressions are
therefore checkable from unit tests, not just benchmark runs.

Exporters:

* :func:`snapshot` — plain nested dict (counters, gauges, histogram
  summaries, finished spans);
* :func:`to_prometheus_text` — Prometheus text exposition (counters,
  gauges, histograms as summaries with quantile labels);
* :func:`to_chrome_trace` — spans as Chrome trace-event JSON ("X"
  complete events), viewable in Perfetto / ``chrome://tracing``; wired
  into ``benchmarks/run.py --telemetry <path>``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import json
import os
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

__all__ = [
    "Telemetry", "KernelReport", "get_registry", "enable", "disable",
    "enabled", "reset", "inc", "gauge", "observe", "span", "capture",
    "hist_summary", "snapshot", "to_prometheus_text", "to_chrome_trace",
    "rung_tag",
    "count_pallas_launches", "sweep_cost", "kernel_report",
    "PEAK_FLOPS", "HBM_BW", "ICI_BW",
]

# Hardware model (TPU v5e) — the roofline terms' denominators.  Single
# source of truth shared with benchmarks/roofline.py (which imports these
# rather than re-declaring them).
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # HBM bytes/s per chip
ICI_BW = 50e9                # bytes/s per ICI link (1 link, conservative)


def rung_tag(grid) -> str:
    """Canonical label for a tile grid — the rung/grid tag spans and the
    rung-hit counters share, so traces and metrics join on one string."""
    return (f"ndt{grid.n_diag_tiles}.bt{grid.band_tiles}."
            f"nat{grid.n_arrow_tiles}.t{grid.t}")


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

class _NoopSpan:
    """Shared do-nothing span returned while telemetry is disabled."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def tag(self, **tags):
        return self


_NOOP_SPAN = _NoopSpan()


class _Span:
    """A live span: context manager that pushes onto the per-thread stack
    on entry (capturing its parent) and records itself on exit.  It holds
    a profiler annotation of its name open for as long as it is open."""
    __slots__ = ("_reg", "name", "tags", "id", "parent", "t0", "_ann")

    def __init__(self, reg: "Telemetry", name: str, tags: Dict[str, Any]):
        self._reg = reg
        self.name = name
        self.tags = tags
        self.id = None
        self.parent = None
        self.t0 = None
        self._ann = TraceAnnotation(name)

    def tag(self, **tags) -> "_Span":
        """Attach tags discovered mid-span (e.g. the canonical rung after
        policy resolution)."""
        self.tags.update(tags)
        return self

    def __enter__(self) -> "_Span":
        stack = self._reg._span_stack()
        self.parent = stack[-1].id if stack else None
        self.id = next(self._reg._ids)
        stack.append(self)
        self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        stack = self._reg._span_stack()
        if stack and stack[-1] is self:
            stack.pop()
        self._reg._finish_span(self, t1)
        return False


# ---------------------------------------------------------------------------
# Histograms
# ---------------------------------------------------------------------------

class _Hist:
    """Count/sum/min/max plus a bounded sample reservoir for quantiles.

    Samples beyond ``cap`` are counted (in ``count``/``sum``/extrema) but
    not stored; quantiles then describe the first ``cap`` observations and
    the summary carries ``samples_dropped`` so readers know."""
    __slots__ = ("count", "total", "vmin", "vmax", "samples", "dropped",
                 "cap")

    def __init__(self, cap: int):
        self.count = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = float("-inf")
        self.samples: List[float] = []
        self.dropped = 0
        self.cap = cap

    def add(self, v: float):
        self.count += 1
        self.total += v
        self.vmin = v if v < self.vmin else self.vmin
        self.vmax = v if v > self.vmax else self.vmax
        if len(self.samples) < self.cap:
            self.samples.append(v)
        else:
            self.dropped += 1

    def quantile(self, q: float) -> float:
        """Nearest-rank quantile over the stored samples: the value at
        rank ``ceil(q * n)`` (1-based), so p50 of [1..100] is 50 and p99
        is 99 — exact and deterministic for test-sized data."""
        if not self.samples:
            return float("nan")
        s = sorted(self.samples)
        idx = max(int(-(-q * len(s) // 1)) - 1, 0)      # ceil(q*n) - 1
        return s[min(idx, len(s) - 1)]

    def summary(self) -> Dict[str, float]:
        out = {"count": self.count, "sum": self.total,
               "min": self.vmin if self.count else float("nan"),
               "max": self.vmax if self.count else float("nan"),
               "mean": self.total / self.count if self.count else float("nan"),
               "p50": self.quantile(0.50),
               "p90": self.quantile(0.90),
               "p99": self.quantile(0.99)}
        if self.dropped:
            out["samples_dropped"] = self.dropped
        return out


def _labels_key(labels: Dict[str, Any]) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


def _render_key(name: str, labels: Tuple[Tuple[str, str], ...]) -> str:
    if not labels:
        return name
    return name + "{" + ",".join(f"{k}={v}" for k, v in labels) + "}"


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

class Telemetry:
    """Thread-safe metric + span registry.

    One instance (:func:`get_registry`) backs the module-level functions;
    independent instances are constructible for tests.  All mutation is
    guarded by one lock held only for the bookkeeping (never across user
    code or JAX dispatch); span stacks are per-thread so concurrent
    serving threads nest independently.
    """

    def __init__(self, enabled: bool = False, max_spans: int = 100_000,
                 max_samples: int = 8192):
        if max_spans <= 0 or max_samples <= 0:
            raise ValueError("max_spans and max_samples must be positive")
        self._enabled = bool(enabled)
        self.max_spans = max_spans
        self.max_samples = max_samples
        self._lock = threading.Lock()
        self._counters: Dict[Tuple[str, tuple], float] = {}
        self._gauges: Dict[Tuple[str, tuple], float] = {}
        self._hists: Dict[Tuple[str, tuple], _Hist] = {}
        self._spans: List[Dict[str, Any]] = []
        self._spans_dropped = 0
        self._local = threading.local()
        self._ids = itertools.count(1)
        self._epoch = time.perf_counter_ns()

    # -- lifecycle ----------------------------------------------------------

    def enable(self):
        self._enabled = True

    def disable(self):
        self._enabled = False

    def enabled(self) -> bool:
        return self._enabled

    def reset(self):
        """Drop all recorded metrics and finished spans (the enabled flag
        and the span-id counter are untouched; live spans finish into the
        cleared buffers)."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()
            self._spans.clear()
            self._spans_dropped = 0
            self._epoch = time.perf_counter_ns()

    # -- recording ----------------------------------------------------------

    def inc(self, name: str, value: float = 1.0, **labels):
        if not self._enabled:
            return
        v = float(value)            # tracers fail loudly here (jit-safety)
        key = (name, _labels_key(labels))
        with self._lock:
            self._counters[key] = self._counters.get(key, 0.0) + v

    def gauge(self, name: str, value: float, **labels):
        if not self._enabled:
            return
        v = float(value)
        key = (name, _labels_key(labels))
        with self._lock:
            self._gauges[key] = v

    def observe(self, name: str, value: float, **labels):
        if not self._enabled:
            return
        v = float(value)
        key = (name, _labels_key(labels))
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = _Hist(self.max_samples)
            h.add(v)

    def span(self, name: str, **tags):
        """Open a nestable wall-clock span (use as a context manager).
        Returns the shared no-op span while disabled."""
        if not self._enabled:
            return _NOOP_SPAN
        return _Span(self, name, tags)

    def hist_summary(self, name: str, **labels) -> Optional[Dict[str, float]]:
        """Summary (count/sum/min/max/mean/p50/p90/p99) of one histogram
        by exact name + labels, or None if never observed — the typed
        accessor ``benchmarks/bench_serving.py`` reads request-latency
        percentiles through, instead of string-matching rendered
        ``snapshot()`` keys."""
        key = (name, _labels_key(labels))
        with self._lock:
            h = self._hists.get(key)
            return h.summary() if h is not None else None

    def _span_stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _finish_span(self, span: _Span, t1: int):
        rec = {"name": span.name, "id": span.id, "parent": span.parent,
               "ts_us": (span.t0 - self._epoch) / 1e3,
               "dur_us": (t1 - span.t0) / 1e3,
               "tid": threading.get_ident(), "tags": dict(span.tags)}
        with self._lock:
            if len(self._spans) < self.max_spans:
                self._spans.append(rec)
            else:
                self._spans_dropped += 1

    # -- exporters ----------------------------------------------------------

    def snapshot(self, include_spans: bool = True) -> Dict[str, Any]:
        """Plain-dict view of everything recorded so far: ``counters`` and
        ``gauges`` keyed ``name{label=value,...}``, ``histograms`` mapped
        to their summaries (count/sum/min/max/mean/p50/p90/p99), and (by
        default) the finished ``spans`` with parent ids intact."""
        with self._lock:
            out: Dict[str, Any] = {
                "enabled": self._enabled,
                "counters": {_render_key(*k): v
                             for k, v in sorted(self._counters.items())},
                "gauges": {_render_key(*k): v
                           for k, v in sorted(self._gauges.items())},
                "histograms": {_render_key(*k): h.summary()
                               for k, h in sorted(self._hists.items())},
            }
            if include_spans:
                out["spans"] = [dict(s, tags=dict(s["tags"]))
                                for s in self._spans]
                out["spans_dropped"] = self._spans_dropped
        return out

    def to_prometheus_text(self) -> str:
        """Prometheus text exposition: counters and gauges verbatim,
        histograms as summaries (``quantile`` labels + ``_sum``/``_count``
        series).  Metric names are prefixed ``repro_`` and sanitized."""
        lines: List[str] = []
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            hists = {k: h.summary() for k, h in self._hists.items()}
        for kind, data in (("counter", counters), ("gauge", gauges)):
            seen = set()
            for (name, labels), v in sorted(data.items()):
                pname = _prom_name(name)
                if pname not in seen:
                    lines.append(f"# TYPE {pname} {kind}")
                    seen.add(pname)
                lines.append(f"{pname}{_prom_labels(labels)} {_prom_num(v)}")
        seen = set()
        for (name, labels), s in sorted(hists.items()):
            pname = _prom_name(name)
            if pname not in seen:
                lines.append(f"# TYPE {pname} summary")
                seen.add(pname)
            for q in ("0.5", "0.9", "0.99"):
                ql = labels + (("quantile", q),)
                val = s[{"0.5": "p50", "0.9": "p90", "0.99": "p99"}[q]]
                lines.append(f"{pname}{_prom_labels(ql)} {_prom_num(val)}")
            lines.append(f"{pname}_sum{_prom_labels(labels)} "
                         f"{_prom_num(s['sum'])}")
            lines.append(f"{pname}_count{_prom_labels(labels)} "
                         f"{_prom_num(s['count'])}")
        return "\n".join(lines) + ("\n" if lines else "")

    def to_chrome_trace(self) -> Dict[str, Any]:
        """Spans as Chrome trace-event JSON (``ph="X"`` complete events,
        microsecond timestamps) — ``json.dump`` the result and open it in
        Perfetto (ui.perfetto.dev) or ``chrome://tracing``.  Span/parent
        ids ride in ``args`` so the tree survives the export."""
        pid = os.getpid()
        with self._lock:
            spans = [dict(s, tags=dict(s["tags"])) for s in self._spans]
        events = [{
            "name": s["name"],
            "cat": s["name"].split(".", 1)[0],
            "ph": "X",
            "ts": s["ts_us"],
            "dur": s["dur_us"],
            "pid": pid,
            "tid": s["tid"],
            "args": {**s["tags"], "span_id": s["id"],
                     "parent_id": s["parent"]},
        } for s in spans]
        return {"traceEvents": events, "displayTimeUnit": "ms"}


def _prom_name(name: str) -> str:
    return "repro_" + re.sub(r"[^a-zA-Z0-9_]", "_", name)


def _prom_labels(labels: Tuple[Tuple[str, str], ...]) -> str:
    if not labels:
        return ""
    esc = lambda v: str(v).replace("\\", r"\\").replace('"', r"\"")
    body = ",".join(
        f'{re.sub(r"[^a-zA-Z0-9_]", "_", k)}="{esc(v)}"' for k, v in labels)
    return "{" + body + "}"


def _prom_num(v: float) -> str:
    f = float(v)
    return repr(int(f)) if f == int(f) and abs(f) < 1e15 else repr(f)


# ---------------------------------------------------------------------------
# Default registry + module-level API
# ---------------------------------------------------------------------------

_DEFAULT = Telemetry(
    enabled=os.environ.get("REPRO_TELEMETRY", "") not in ("", "0"))


def get_registry() -> Telemetry:
    return _DEFAULT


def enable():
    _DEFAULT.enable()


def disable():
    _DEFAULT.disable()


def enabled() -> bool:
    return _DEFAULT._enabled


def reset():
    _DEFAULT.reset()


def inc(name: str, value: float = 1.0, **labels):
    if _DEFAULT._enabled:
        _DEFAULT.inc(name, value, **labels)


def gauge(name: str, value: float, **labels):
    if _DEFAULT._enabled:
        _DEFAULT.gauge(name, value, **labels)


def observe(name: str, value: float, **labels):
    if _DEFAULT._enabled:
        _DEFAULT.observe(name, value, **labels)


def span(name: str, **tags):
    if not _DEFAULT._enabled:
        return _NOOP_SPAN
    return _Span(_DEFAULT, name, tags)


def hist_summary(name: str, **labels) -> Optional[Dict[str, float]]:
    return _DEFAULT.hist_summary(name, **labels)


def snapshot(include_spans: bool = True) -> Dict[str, Any]:
    return _DEFAULT.snapshot(include_spans=include_spans)


def to_prometheus_text() -> str:
    return _DEFAULT.to_prometheus_text()


def to_chrome_trace() -> Dict[str, Any]:
    return _DEFAULT.to_chrome_trace()


@contextlib.contextmanager
def capture():
    """Enable the default registry for the duration of a block, yielding
    it; the previous enabled state is restored on exit (recorded data is
    kept — call :func:`reset` to drop it)."""
    prev = _DEFAULT._enabled
    _DEFAULT.enable()
    try:
        yield _DEFAULT
    finally:
        _DEFAULT._enabled = prev


# ---------------------------------------------------------------------------
# Static kernel inspection: launch counting + analytic sweep costs
# ---------------------------------------------------------------------------

def count_pallas_launches(closed_jaxpr) -> int:
    """Count pallas_call sites in a (closed) jaxpr, descending into
    sub-jaxprs; scan/while bodies multiply by their trip count where it is
    statically known (``scan`` carries ``length``), so a per-panel kernel
    loop is charged once per panel.

    This is the library home of the counter that gates
    ``BENCH_cholesky.json`` (``benchmarks/bench_cholesky.py`` imports it
    from here): the fused sweeps must trace to exactly one launch each."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            total += 1
            continue
        mult = eqn.params.get("length", 1) \
            if eqn.primitive.name == "scan" else 1
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                total += mult * count_pallas_launches(v)
            elif isinstance(v, (list, tuple)):
                total += mult * sum(count_pallas_launches(b)
                                    for b in v if hasattr(b, "jaxpr"))
    return total


def sweep_cost(grid, sweep: str, k: int = 1,
               dtype_bytes: int = 4) -> Dict[str, float]:
    """Analytic FLOP / bytes-moved estimate of one banded-arrowhead sweep
    on ``grid`` — the tile-granular model the paper tunes tile size with
    (flops from tile-matmul counts, bytes from CTSF array traffic).

    Sweeps: ``"cholesky"`` (band+arrow factorization incl. the dense
    corner), ``"forward"`` / ``"backward"`` (one triangular band solve of
    a width-``k`` RHS panel), ``"solve"`` (forward + backward), and
    ``"selinv"`` (the blocked Takahashi recurrence).

    The FLOP side of the cholesky model is shared with
    ``core.gridpolicy.padded_flop_overhead`` (same tile-matmul counter),
    so the padding-overhead metric and these absolute estimates cannot
    drift apart.  Bytes assume each CTSF array crosses HBM once per read
    and once per write — the fused single-launch kernels' traffic, which
    is the floor the VMEM rings were built to hit.  Returns ``{"flops",
    "bytes", "intensity"}`` (intensity in flops/byte)."""
    t, ndt = grid.t, grid.n_diag_tiles
    bt, nat = grid.band_tiles, grid.n_arrow_tiles
    mm = 2.0 * t ** 3                    # one (t,t)@(t,t) tile matmul
    pmm = 2.0 * t * t * k                # one (t,t)@(t,k) panel matmul
    factor_bytes = float((ndt * (bt + 1) + ndt * nat + nat * nat)
                         * t * t * dtype_bytes)
    panel_bytes = float((ndt + nat) * t * k * dtype_bytes)
    corner_n = nat * t
    if sweep == "cholesky":
        from repro.core.gridpolicy import _sweep_tile_matmuls
        flops = _sweep_tile_matmuls(ndt, bt, nat) * mm \
            + corner_n ** 3 / 3.0        # dense corner Cholesky
        byts = 2.0 * factor_bytes        # read A tiles, write L tiles
    elif sweep in ("forward", "backward"):
        panel_ops = max(ndt, 0) * (bt + nat + 1) + nat * (nat + 1) / 2.0
        flops = panel_ops * pmm
        byts = factor_bytes + 2.0 * panel_bytes
    elif sweep == "solve":
        f = sweep_cost(grid, "forward", k, dtype_bytes)
        b = sweep_cost(grid, "backward", k, dtype_bytes)
        flops = f["flops"] + b["flops"]
        byts = f["bytes"] + b["bytes"]
    elif sweep == "selinv":
        # per column: (bt+1) band panels + nat arrow rows, each contracting
        # over the (bt + nat)-deep trailing ring, plus the diagonal seed
        tiles = max(ndt, 0) * ((bt + 1 + nat) * (bt + nat) + 1)
        flops = tiles * mm + float(corner_n) ** 3   # corner seed L^-1, L^-T L^-1
        byts = 2.0 * factor_bytes        # read L tiles, write Sigma tiles
    else:
        raise ValueError(f"unknown sweep {sweep!r} (want 'cholesky', "
                         "'forward', 'backward', 'solve' or 'selinv')")
    return {"flops": float(flops), "bytes": float(byts),
            "intensity": float(flops) / max(byts, 1.0)}


@dataclasses.dataclass(frozen=True)
class KernelReport:
    """Static inspection result of :func:`kernel_report`.

    ``pallas_launches`` is exact (jaxpr traversal); the cost fields are
    the analytic :func:`sweep_cost` estimates (``None`` without a grid),
    with ``t_compute_s`` / ``t_memory_s`` the roofline terms under the
    module's hardware model and ``bound`` naming the larger one."""
    pallas_launches: int
    sweep: Optional[str] = None
    flops: Optional[float] = None
    bytes_moved: Optional[float] = None
    intensity: Optional[float] = None
    t_compute_s: Optional[float] = None
    t_memory_s: Optional[float] = None
    bound: Optional[str] = None

    def asdict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def kernel_report(fn: Callable, *args, grid=None, sweep: Optional[str] = None,
                  k: int = 1, dtype_bytes: int = 4,
                  **make_jaxpr_kwargs) -> KernelReport:
    """Statically inspect ``fn(*args)`` without executing it: trace to a
    jaxpr, count ``pallas_call`` launch sites, and (when ``grid`` and
    ``sweep`` are given) attach the analytic per-sweep FLOP / bytes-moved
    estimates and roofline terms.

    ``args`` may be concrete arrays or ``jax.ShapeDtypeStruct``s — only
    shapes/dtypes matter.  Extra keyword arguments are forwarded to
    ``jax.make_jaxpr`` (e.g. ``static_argnums``).  This is how tests gate
    launch/intensity regressions without running a benchmark::

        rep = kernel_report(lambda a, r: ops.band_cholesky_sweep(
            a, r, impl="pallas"), Ac, R, grid=grid, sweep="cholesky")
        assert rep.pallas_launches == 1
    """
    import jax
    closed = jax.make_jaxpr(fn, **make_jaxpr_kwargs)(*args)
    launches = count_pallas_launches(closed)
    if grid is None or sweep is None:
        return KernelReport(pallas_launches=launches, sweep=sweep)
    cost = sweep_cost(grid, sweep, k=k, dtype_bytes=dtype_bytes)
    t_c = cost["flops"] / PEAK_FLOPS
    t_m = cost["bytes"] / HBM_BW
    return KernelReport(
        pallas_launches=launches, sweep=sweep, flops=cost["flops"],
        bytes_moved=cost["bytes"], intensity=cost["intensity"],
        t_compute_s=t_c, t_memory_s=t_m,
        bound="compute" if t_c >= t_m else "memory")


def write_trace(path: str, registry: Optional[Telemetry] = None):
    """Dump the registry's Chrome trace (plus a ``metrics`` key holding
    the span-free snapshot — Perfetto ignores unknown top-level keys) to
    ``path`` as JSON.  The ``benchmarks/run.py --telemetry`` exit hook."""
    reg = registry or _DEFAULT
    trace = reg.to_chrome_trace()
    trace["metrics"] = reg.snapshot(include_spans=False)
    with open(path, "w") as f:
        json.dump(trace, f, indent=1)
