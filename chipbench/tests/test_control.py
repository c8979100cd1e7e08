"""The control (the same matrices at float32 ``high`` precision) reads
worse than the program, and the check refuses runs with planted faults."""
import os
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench import control  # noqa: E402
from chipbench.tests.test_harness import run_tiny, tiny_tree  # noqa: E402


def test_high_tile_product_is_three_bf16_passes():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 64)).astype(np.float32)
    b = rng.standard_normal((64, 64)).astype(np.float32)
    exact = a.astype(np.float64) @ b
    got = np.asarray(control.tile_dot_high(a, b, trans_b=True))
    err_high = np.abs(got - a.astype(np.float64) @ b.T).max()
    err_f32 = np.abs(a @ b - exact).max()
    assert 10 * err_f32 < err_high < 1e-3


@pytest.mark.parametrize("kind,number", [("theta_sweep", "x_rel"),
                                         ("marginals", "var_rel")])
def test_control_reads_worse_than_program(tmp_path, monkeypatch, kind,
                                          number):
    """The Pallas kernels in interpret mode at a tiny size, as they are
    and with their tile products lowered to ``high``."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    here, bench = tiny_tree(tmp_path, theta={"probe_offsets": [[0, 0, 0],
                                                               [1, 0, 0]]},
                            marginals={"design_points": 2})
    jax.clear_caches()
    prog = run_tiny(here, bench, kind)
    assert prog["correct"]
    assert control.lower_precision(monkeypatch.setattr) == 3
    jax.clear_caches()
    ctrl = run_tiny(here, bench, kind)
    jax.clear_caches()
    print(kind, prog["readings"], ctrl["readings"])
    assert ctrl["readings"][number] > 3 * prog["readings"][number]


def _stale(fn):
    """Calls ``fn`` once and returns that first result ever after."""
    box = []

    def wrapped(*a, **k):
        if not box:
            box.append(fn(*a, **k))
        return box[0]
    return wrapped


def _half_batch(fn):
    """Factorizes the first half of the batch and repeats it."""
    def wrapped(batch, *a, **k):
        from repro import api
        h = batch.Dr.shape[0] // 2
        pick = lambda v: np.concatenate([v[:h], v[:h]])
        return fn(api.BandedCTSF(batch.grid, *(pick(v) for v in
                                              batch.arrays())), *a, **k)
    return wrapped


def _scaled_first(fn):
    def wrapped(*a, **k):
        out = fn(*a, **k)
        return out.at[0].multiply(1.001)
    return wrapped


def _selinv_altered(half):
    def patch(fn):
        def wrapped(*a, **k):
            s = fn(*a, **k)
            dr = s.Dr
            if half:            # the second half of the band left out
                dr = dr.at[dr.shape[0] // 2:].set(0.0)
            else:               # one variance altered where it is produced
                dr = dr.at[0, 0, 0, 0].multiply(1.001)
            return type(s)(s.grid, dr, s.R, s.C)
        return wrapped
    return patch


@pytest.mark.parametrize("kind,name,patch", [
    ("theta_sweep", "factorize_window_batched", _stale),
    ("theta_sweep", "factorize_window_batched", _half_batch),
    ("theta_sweep", "concurrent_solve", _scaled_first),
    ("marginals", "selected_inverse", _stale),
    ("marginals", "selected_inverse", _selinv_altered(False)),
    ("marginals", "selected_inverse", _selinv_altered(True)),
])
def test_planted_fault_is_not_correct(tmp_path, monkeypatch, kind, name,
                                      patch):
    from repro import api
    here, bench = tiny_tree(tmp_path)
    monkeypatch.setattr(api, name, patch(getattr(api, name)))
    res = run_tiny(here, bench, kind)
    assert not res["correct"] and res["failed"] > 0, res["checks"]
