"""CPU rehearsal of the harness at a tiny size on the program's ``ref``
path, the loaders, and the refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import harness  # noqa: E402

# n_diag = 16 time blocks of 20 rows: the reference's Kronecker form holds
TINY = dict(n=330, bandwidth=20, arrow=10, rho=0.7, coupling=0.4,
            temporal_jitter=1e-3, spatial_tau=1.0, x_scale=0.5,
            schur_slack=1e-3)


def tiny_tree(tmp_path, rho=0.7, theta=None, marginals=None):
    """A copy of the benchmark's data files plus a tiny configuration."""
    here = tmp_path / "chipbench"
    for sub in ("traffic", "kinds", "limits", "metrics", "configs"):
        shutil.copytree(os.path.join(BENCH_DIR, sub), here / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (here / "configs" / "tiny.json").write_text(
        json.dumps(dict(TINY, rho=rho)))
    for kind, extra in (("theta_sweep", theta), ("marginals", marginals)):
        tr = json.loads((here / "traffic" / f"{kind}.json").read_text())
        tr.update(extra or {})
        (here / "traffic" / f"{kind}.json").write_text(json.dumps(tr))
        lim = json.loads(
            (here / "limits" / f"{kind}.t2-id11.json").read_text())
        (here / "limits" / f"{kind}.tiny.json").write_text(json.dumps(lim))
    bench = harness.load_benchmark(ROOT)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [w.replace("t2-id10", "tiny").replace(
                "t2-id11", "tiny") for w in m["workloads"]]
    return str(here), bench


def run_tiny(here, bench, kind, trace=False, seed=12345678901):
    cell = {"name": f"{kind}.tiny", "config": "tiny", "traffic": kind,
            "chips": 1}
    return harness.run_cell(bench, cell, seed, 0.3, trace,
                            time.perf_counter(), jax.devices("cpu")[:1],
                            here=here, log=lambda *a: None)


@pytest.mark.parametrize("kind,rate", [("theta_sweep", "probe_rate"),
                                       ("marginals", "selinv_rate")])
@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal(tmp_path, kind, rate, trace):
    here, bench = tiny_tree(tmp_path)
    res = run_tiny(here, bench, kind, trace)
    assert res["correct"], res
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    for c in res["checks"].values():
        assert 0 <= c["value"] <= c["limit"]
    if trace:
        assert "breakdown" in res
        # no device plane on the CPU: only the program's spans are read
        assert set(res["metrics"]) == {"dispatch_ms." + kind.split("_")[0]}
    else:
        assert set(res["metrics"]) == {"setup_s", rate}


NEW_KIND = """
from chipbench.workload import Base, tally


class Workload(Base):
    unit = "calls"

    def setup(self):
        self._data()

    def step(self, s):
        self.answers.append(float(self.c))
        return 2

    def free(self):
        del self.R

    def compare(self, ref_data, limits, max_answers):
        got = [{"gap": abs(a - ref_data["c"])} for a in self.answers]
        return tally(got, limits, len(self.answers))
"""


def test_loaders_find_new_files_by_name(tmp_path):
    """A configuration, a traffic mix of a new kind, its limits and a
    metric reader, each a new file, run a cell with no edit elsewhere."""
    here = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "kinds", "limits", "metrics"):
        (here / sub).mkdir(parents=True)
    (here / "configs" / "new-cfg.json").write_text(json.dumps(TINY))
    mix = {"kind": "new_kind", "rate_metric": "call_rate", "check_answers": 9}
    (here / "traffic" / "new_mix.json").write_text(json.dumps(mix))
    (here / "kinds" / "new_kind.py").write_text(NEW_KIND)
    (here / "limits" / "new_mix.new-cfg.json").write_text('{"gap": 0}')
    (here / "metrics" / "new.metric.py").write_text(
        "def read(ctx):\n    return ctx['units_per_step'] * 21\n")
    assert harness.load_config("new-cfg", str(here)) == TINY
    assert harness.load_traffic("new_mix", str(here)) == mix
    assert harness.load_limits("new_mix.new-cfg", str(here)) == {"gap": 0}
    assert harness.load_reader("new.metric", str(here))(
        {"units_per_step": 2}) == 42
    bench = {"end_to_end": [{"name": "setup_s", "unit": "s"},
                            {"name": "call_rate", "unit": "calls/s"}],
             "per_layer": [{"name": "new.metric", "unit": "calls"}]}
    cell = {"name": "new_mix.new-cfg", "config": "new-cfg",
            "traffic": "new_mix", "chips": 1}
    for trace, names in ((False, {"setup_s", "call_rate"}),
                         (True, {"new.metric"})):
        res = harness.run_cell(bench, cell, 3, 0.05, trace,
                               time.perf_counter(), jax.devices("cpu")[:1],
                               here=str(here), log=lambda *a: None)
        assert res["correct"] and res["attempted"] > 0, res
        assert set(res["metrics"]) == names
        assert res["checks"] == {"gap": {"value": 0.0, "limit": 0}}


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "theta_sweep.t2-id10", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(p):
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_refuses_without_a_tpu():
    _no_result(_run(ROOT, {}))


def test_refuses_in_a_directory_of_the_benchmark_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(str(tmp_path), {"PYTHONPATH": ""}))
