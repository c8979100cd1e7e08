"""Runs one cell once: set-up, a measured window of whole steps, the
reference check, and (traced) the per-layer readers.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by its name in
BENCHMARK.json:

    configs/<config>.json    the deployment (tiles.Deployment's fields)
    traffic/<traffic>.json   the mix: its ``kind`` and parameters
    kinds/<kind>.py          the step code of a kind: a ``Workload`` class
                             (see workload.py)
    limits/<cell>.json       the limit of each number the check compares
    metrics/<metric>.py      a reader: ``read(ctx) -> float | None``
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def load_config(name: str, here: str = HERE) -> dict:
    return _json(os.path.join(here, "configs", f"{name}.json"))


def load_traffic(name: str, here: str = HERE) -> dict:
    return _json(os.path.join(here, "traffic", f"{name}.json"))


def load_limits(cell: str, here: str = HERE) -> dict:
    return _json(os.path.join(here, "limits", f"{cell}.json"))


def _load_module(here: str, sub: str, name: str):
    """The module ``<sub>/<name>.py`` under ``here``, loaded from its file."""
    path = os.path.join(here, sub, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{sub}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, here: str = HERE):
    """The ``read`` function of ``metrics/<name>.py``."""
    return _load_module(here, "metrics", name).read


def load_kind(kind: str, here: str = HERE):
    """The ``Workload`` class of ``kinds/<kind>.py``."""
    return _load_module(here, "kinds", kind).Workload


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")


def metrics_of(bench: dict, cell: str, group: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def configure_jax(root: str = ROOT) -> str:
    """Keep JAX's persistent compilation cache where the program's own
    entry scripts keep it (``<root>/.jax_cache``, or where
    ``JAX_COMPILATION_CACHE_DIR`` says), and cache every program, however
    quick to compile."""
    import jax
    from repro.runtime import compile_cache
    path = compile_cache.configure(root)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(count: int):
    """The devices of a TPU run; refuses anything else."""
    import jax
    impl = os.environ.get("REPRO_KERNEL_IMPL", "")
    if impl not in ("", "pallas"):
        raise SystemExit(f"chipbench: REPRO_KERNEL_IMPL={impl!r} forces a "
                         "kernel backend other than Pallas")
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: JAX finds no TPU (platform "
                         f"{devs[0].platform!r}); the benchmark runs only "
                         "on the chip")
    if len(devs) < count:
        raise SystemExit(f"chipbench: the cell needs {count} chips, JAX "
                         f"sees {len(devs)}")
    return devs[:count]


class CompileCounter:
    """Counts executables built or loaded from the cache, by JAX's own
    monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.count = 0

        def on_duration(event, *_a, **_k):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
        monitoring.register_event_duration_secs_listener(on_duration)


@contextlib.contextmanager
def _profiled(trace: bool):
    """The profiler's trace of the window, or nothing."""
    if not trace:
        yield None
        return
    import jax
    from repro.runtime import telemetry
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    telemetry.enable()
    telemetry.reset()
    box = {"dir": tmp}
    jax.profiler.start_trace(tmp)
    try:
        yield box
    finally:
        jax.profiler.stop_trace()
        box["spans"] = telemetry.snapshot()["spans"]
        telemetry.disable()


def run_cell(bench: dict, cell: dict, seed: int, seconds: float,
             trace: bool, t_start: float, devices, here: str = HERE,
             log=print) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    import jax
    from . import tiles, trace as tr

    t_enter = time.perf_counter()
    config = load_config(cell["config"], here)
    traffic = load_traffic(cell["traffic"], here)
    limits = load_limits(cell["name"], here)
    dep = tiles.Deployment.from_config(config)
    compiles = CompileCounter()
    wl = load_kind(traffic["kind"], here)(dep, traffic, seed)
    wl.setup()
    jax.effects_barrier()
    setup_s = time.perf_counter() - t_start
    log(f"chipbench: set-up {setup_s!r} s ({t_enter - t_start!r} s to "
        f"reach the chip, {time.perf_counter() - t_enter!r} s for the data, "
        f"programs and warm step), {compiles.count} executables built or "
        f"loaded")

    built = compiles.count
    units = steps = 0
    longest = (0.0, 0)
    with _profiled(trace) as prof:
        t0 = t_end = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation(tr.STEP):
                units += wl.step(steps)
            t_prev, t_end = t_end, time.perf_counter()
            longest = max(longest, (t_end - t_prev, steps))
            steps += 1
            if t_end - t0 >= seconds:
                break
    window_s = t_end - t0
    in_window = compiles.count - built
    stats = devices[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    log(f"chipbench: window {window_s!r} s, {steps} steps, {units} "
        f"{wl.unit}, {in_window} executables built or loaded inside the "
        f"window, longest step {longest[0]!r} s (step {longest[1]}), "
        f"memory peak {peak} bytes (limit {stats.get('bytes_limit')})")

    ref_data = wl.reference_data()
    wl.free()
    gc.collect()
    t_ref = time.perf_counter()
    worst, attempted, compared, failed = wl.compare(
        ref_data, limits, traffic["check_answers"])
    log(f"chipbench: reference compared {compared} of {attempted} answers "
        f"in {time.perf_counter() - t_ref!r} s")

    host = {"setup_s": setup_s, traffic["rate_metric"]: units / window_s}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": failed == 0 and compared > 0,
              "attempted": attempted, "failed": failed}
    if trace:
        summary = tr.load(prof["dir"], d0)
        shutil.rmtree(prof["dir"], ignore_errors=True)
        log(f"chipbench: executables (runs, device s) "
            f"{summary.executables()!r}")
        ctx = {"trace": summary, "spans": prof["spans"], "steps": steps,
               "units": units, "window_s": window_s, "dep": dep,
               "traffic": traffic, "cell": cell, "device_kind": d0.device_kind,
               "units_per_step": units / steps}
        metrics = {}
        for m in metrics_of(bench, cell["name"], "per_layer"):
            v = load_reader(m["name"], here)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result.update(metrics=metrics, device=device,
                      breakdown=summary.breakdown())
    else:
        metrics = {}
        for m in metrics_of(bench, cell["name"], "end_to_end"):
            metrics[m["name"]] = {"value": host[m["name"]], "unit": m["unit"]}
        result.update(metrics=metrics, device=device)
    for name in sorted(set(worst) - set(limits)):
        log(f"chipbench: not compared (no limit): {name} {worst[name]!r}")
    checks = {name: {"value": worst.get(name), "limit": lim}
              for name, lim in limits.items()}
    result["readings"] = worst
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return result
