"""What every kind of traffic shares.  A traffic file
(``traffic/<mix>.json``) names its ``kind`` and the parameters; the kind's
step code is ``kinds/<kind>.py``, whose ``Workload`` class the harness
finds by that name.  Every kind is a closed loop with one step in flight
(INLA's optimiser waits for every result), and implements

    setup()            the run's data, warmed shapes (counted as set-up)
    step(s) -> int     one step of the window; returns its work units
    free()             drops the program's state before the reference runs
    compare(ref_data, limits, max_answers)
                       -> (worst of each number, attempted, compared, failed)

with ``unit`` the name of its work units.  Every call into the program
goes through ``repro.api`` at call time.
"""
from __future__ import annotations

import numpy as np

from . import tiles


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


def tally(got: list, limits: dict, attempted: int):
    """(worst of each number, answers attempted, compared, failed); only
    the numbers ``limits`` names are compared."""
    worst = {m: max(g[m] for g in got) for m in got[0]} if got else {}
    failed = sum(any(not g[m] <= lim for m, lim in limits.items())
                 for g in got)
    return worst, attempted, len(got), failed


class Base:
    """The deployment, the program's tile grid, the seeded coupling X (on
    the device) and the record of every answer."""

    def __init__(self, dep: tiles.Deployment, traffic: dict, seed: int):
        from repro import api
        self.api = api
        self.dep, self.traffic, self.seed = dep, traffic, seed
        self.grid = tiles.grid(dep)
        self.answers = []

    def _data(self):
        self.x, self.R, self.c = tiles.make_data(self.dep, self.grid,
                                                 self.seed)

    def reference_data(self) -> dict:
        """Host copies of the run's data, for the reference."""
        return {"x": np.asarray(self.x), "c": float(self.c)}
