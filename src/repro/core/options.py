"""Unified solver options for every serving entry point.

:class:`SolverOptions` is the one knob object: a frozen, hashable
dataclass accepted as a single ``options=`` argument by
``factorize_window(_batched)``, the ``solve_many`` family,
``selected_inverse``/``selinv_batched``, the ``concurrent_*`` wrappers
and the rung server.  Per-call data — right-hand sides, ``start_tile``
prefixes, batch bucketing — stays out of it: options describe *how*,
arguments describe *what*.

Hashability is load-bearing, not cosmetic: the batching compile caches
key on :meth:`SolverOptions.compile_key` — the compile-relevant subset of
the options — so option-equal calls share traced callables no matter
which construction path produced the object.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

from .gridpolicy import GridBucketPolicy
from .ordering import PartitionPlan
from .robustness import RegularizePolicy

__all__ = ["SolverOptions", "check_options"]


@dataclasses.dataclass(frozen=True)
class SolverOptions:
    """How to factorize/solve — everything except the data itself.

    Fields:
      policy: a :class:`~repro.core.gridpolicy.GridBucketPolicy`
        canonical-grid bucketing policy, or None for exact-grid compiles.
      regularize: numerical fault tolerance — ``None``/``False`` off,
        ``True`` the default :class:`~repro.core.robustness.RegularizePolicy`,
        or an explicit policy (the escalating-jitter retry ladder).
      impl: kernel backend — ``"pallas"``, ``"ref"`` or None for the
        per-backend default (pallas on TPU, ref elsewhere).
      partition_plan: a :class:`~repro.core.ordering.PartitionPlan` of
        independent band partitions; with >1 partition the factorization
        runs the multi-partition fused sweep (2D Pallas grid, one parallel
        axis over partitions).
      method: marginal-variance path — None (= ``"selinv"``) or
        ``"panels"``.

    Frozen and hashable (all fields are immutables or frozen dataclasses),
    so an options object can key compile caches directly.
    """

    policy: Optional[GridBucketPolicy] = None
    regularize: Union[None, bool, RegularizePolicy] = None
    impl: Optional[str] = None
    partition_plan: Optional[PartitionPlan] = None
    method: Optional[str] = None

    def compile_key(self) -> "SolverOptions":
        """The compile-relevant subset, as a (hashable) options object.

        ``policy``, ``regularize`` and ``method`` never change what a
        traced sweep callable computes — the policy picks *which* grid is
        compiled (already part of every cache key), the ladder re-invokes
        the same callable, and ``method`` selects between entry points —
        so they are cleared here and option-equal calls share compile-
        cache entries across those axes."""
        return dataclasses.replace(self, policy=None, regularize=None,
                                   method=None)

    def replace(self, **changes) -> "SolverOptions":
        """`dataclasses.replace` as a method, for call-site brevity."""
        return dataclasses.replace(self, **changes)


def check_options(options: Optional[SolverOptions],
                  default: Optional[SolverOptions] = None) -> SolverOptions:
    """The options an entry point runs with: ``options`` itself, or
    ``default`` (a plain :class:`SolverOptions` unless given) when it is
    None.  Anything else raises ``TypeError`` at the entry point."""
    if options is None:
        return default if default is not None else SolverOptions()
    if not isinstance(options, SolverOptions):
        raise TypeError(
            f"options= must be a SolverOptions, got {type(options).__name__}")
    return options
