"""API-surface contract tests: the ``repro.api`` facade and the unified
``SolverOptions`` knob object.

Two golden snapshots pin the public surface — ``repro.api.__all__`` and
the ``SolverOptions`` field set/defaults — so additions are deliberate
diffs and removals are loud failures.  Every entry point that takes
``options=`` refuses anything but a ``SolverOptions`` there, and the
options path is silent under ``-W error::DeprecationWarning``.
"""
import dataclasses
import warnings

import jax
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from repro import api
from repro.core import BandedCTSF, TileGrid
from repro.core.options import SolverOptions
from repro.launch.rung_server import RungExecutor
from repro.data import make_arrowhead

API_SNAPSHOT = [
    # matrix + grid types
    "ArrowheadStructure", "BandedCTSF", "TileGrid", "measure_arrowhead",
    # the one knob object + its ingredients
    "SolverOptions", "GridBucketPolicy", "PartitionPlan", "RegularizePolicy",
    # orderings / partition detection
    "adaptive_nd_ordering", "detect_partition_plan",
    "partition_plan_from_ordering",
    # factorization
    "CholeskyFactor", "FactorInfo", "factorize_window",
    "factorize_window_batched", "concurrent_factorize", "stack_ctsf",
    # solves
    "solve", "solve_many", "solve_many_batched", "forward_solve",
    "forward_solve_many", "backward_solve", "backward_solve_many",
    "concurrent_solve", "concurrent_quadratic_forms", "logdet",
    "concurrent_logdet", "sample_gmrf", "sample_gmrf_many",
    # selected inversion
    "SelectedInverse", "selected_inverse", "selinv_batched",
    "concurrent_selinv", "marginal_variances",
    # per-element status codes on FactorInfo
    "STATUS_OK", "STATUS_RECOVERED", "STATUS_FAILED", "STATUS_SHED",
    # serving
    "RungServer", "SimClock",
]

OPTIONS_FIELDS = {
    "policy": None,
    "regularize": None,
    "impl": None,
    "partition_plan": None,
    "method": None,
}


def _factor(opts=None):
    A, st = make_arrowhead(64, 6, 4, seed=0)
    m = BandedCTSF.from_sparse(A, TileGrid(st, 8))
    return api.factorize_window(
        m, options=opts or SolverOptions(impl="ref")), m


# ---------------------------------------------------------------------------
# golden snapshots
# ---------------------------------------------------------------------------

def test_api_all_snapshot():
    assert list(api.__all__) == API_SNAPSHOT


def test_api_names_resolve():
    for name in api.__all__:
        assert getattr(api, name) is not None


def test_solver_options_field_snapshot():
    fields = {f.name: f.default for f in dataclasses.fields(SolverOptions)}
    assert fields == OPTIONS_FIELDS


def test_solver_options_frozen_and_hashable():
    opts = SolverOptions(impl="ref")
    with pytest.raises(dataclasses.FrozenInstanceError):
        opts.impl = "pallas"
    assert hash(opts) == hash(SolverOptions(impl="ref"))
    assert opts != SolverOptions(impl="pallas")
    assert opts.replace(method="panels").method == "panels"
    assert opts.replace(method="panels") is not opts


def test_compile_key_drops_non_compile_fields():
    from repro.core.robustness import RegularizePolicy
    a = SolverOptions(impl="ref", regularize=RegularizePolicy(),
                      method="panels")
    b = SolverOptions(impl="ref")
    assert a.compile_key() == b.compile_key()
    assert a.compile_key() != SolverOptions(impl="pallas").compile_key()


# every entry point that takes options=, called with its data as usual
ENTRY_POINTS = {
    "factorize_window": lambda p, o: api.factorize_window(p["m"], options=o),
    "factorize_window_batched": lambda p, o: api.factorize_window_batched(
        [p["m"], p["m"]], options=o),
    "forward_solve_many": lambda p, o: api.forward_solve_many(
        p["f"], p["B"], options=o),
    "backward_solve_many": lambda p, o: api.backward_solve_many(
        p["f"], p["B"], options=o),
    "solve_many": lambda p, o: api.solve_many(p["f"], p["B"], options=o),
    "solve_many_batched": lambda p, o: api.solve_many_batched(
        p["fb"], p["Bb"], options=o),
    "forward_solve": lambda p, o: api.forward_solve(
        p["f"], p["B"][:, 0], options=o),
    "backward_solve": lambda p, o: api.backward_solve(
        p["f"], p["B"][:, 0], options=o),
    "solve": lambda p, o: api.solve(p["f"], p["B"][:, 0], options=o),
    "sample_gmrf": lambda p, o: api.sample_gmrf(
        p["f"], jax.random.PRNGKey(0), options=o),
    "sample_gmrf_many": lambda p, o: api.sample_gmrf_many(
        p["f"], jax.random.PRNGKey(0), 2, options=o),
    "marginal_variances": lambda p, o: api.marginal_variances(
        p["f"], np.arange(4), options=o),
    "selected_inverse": lambda p, o: api.selected_inverse(p["f"], options=o),
    "selinv_batched": lambda p, o: api.selinv_batched(p["fb"], options=o),
    "concurrent_factorize": lambda p, o: api.concurrent_factorize(
        api.stack_ctsf([p["m"], p["m"]]), options=o),
    "concurrent_solve": lambda p, o: api.concurrent_solve(
        p["fb"], p["B"], options=o),
    "concurrent_selinv": lambda p, o: api.concurrent_selinv(
        p["fb"], options=o),
    "concurrent_quadratic_forms": lambda p, o: api.concurrent_quadratic_forms(
        p["fb"], p["B"][:, 0], options=o),
    "RungExecutor": lambda p, o: RungExecutor(options=o),
    "RungServer": lambda p, o: api.RungServer(clock=api.SimClock(),
                                              options=o),
}


@pytest.fixture(scope="module")
def problem():
    opts = SolverOptions(impl="ref")
    f, m = _factor(opts)
    B = np.zeros((m.grid.padded_n, 2), np.float32)
    B[:3, :] = 1.0
    fb = api.concurrent_factorize(api.stack_ctsf([m, m]), options=opts)
    return {"f": f, "m": m, "B": B, "fb": fb, "Bb": B[None].repeat(2, 0)}


@pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
def test_entry_point_rejects_a_non_options_object(entry, problem):
    """A dict, a string or a bare kwarg spelling in ``options=`` is a
    ``TypeError`` at the entry point, not a silently ignored knob."""
    with pytest.raises(TypeError, match="must be a SolverOptions"):
        ENTRY_POINTS[entry](problem, {"impl": "ref"})


def test_rung_server_defaults_to_the_jitter_ladder():
    # with no options the server and its executor run the ladder; an
    # explicit options object is taken verbatim
    assert RungExecutor().options.regularize is True
    assert api.RungServer(clock=api.SimClock()).options.regularize is True
    explicit = api.RungServer(clock=api.SimClock(),
                              options=SolverOptions(impl="ref"))
    assert explicit.options.regularize is None


# ---------------------------------------------------------------------------
# the blessed options path is silent
# ---------------------------------------------------------------------------

def test_options_path_emits_no_deprecation_warnings():
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        opts = SolverOptions(impl="ref")
        f, m = _factor(opts)
        b = np.zeros((m.grid.padded_n, 2), np.float32)
        b[:3, :] = 1.0
        api.solve_many(f, b, options=opts)
        api.selected_inverse(f, options=opts)
        api.marginal_variances(f, np.arange(4), options=opts)
        api.marginal_variances(f, np.arange(4),
                               options=opts.replace(method="panels"))
        batch = api.stack_ctsf([m, m])
        fb = api.concurrent_factorize(batch, options=opts)
        api.selinv_batched(fb, options=opts)
        api.concurrent_selinv(fb, options=opts)
        api.solve_many_batched(fb, b[None].repeat(2, 0), options=opts)
