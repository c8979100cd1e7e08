"""Useful work of the banded-arrowhead factorization and selected
inversion, counted from the matrix structure alone, and the least time
the chip's published peaks allow for it.

The structure is (n_diag, bandwidth, arrow).  Column j of the lower factor
holds m_j structural entries below its diagonal: min(bandwidth,
n_diag-1-j) band rows plus the ``arrow`` rows for a band column, and
``arrow-1-k`` for arrow column k.  No fill falls outside that pattern.
Counts do not depend on the tile size, on padding, on how many MXU passes
a product takes or on how the work is split between kernels.

* Cholesky, right-looking: per column one square root, m_j divisions and
  a rank-1 update of the m_j(m_j+1)/2 pattern entries below (a multiply
  and a subtraction each): (m_j + 1)² flops.
* Takahashi selected inversion on the same pattern, columns from last to
  first: per column m_j divisions (v = l_j / L_jj), the product of the
  known m_j×m_j block of Σ with v (2m_j² − m_j), and Σ_jj = 1/L_jj² − v·Σ_j
  (2 + 2m_j): 2m_j² + 2m_j + 2 flops.
* Bytes: each stored pattern entry read once and written once.
"""
from __future__ import annotations

import json
import os

import numpy as np

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def _below(n_diag: int, bandwidth: int, arrow: int) -> np.ndarray:
    """m_j for every column of the factor, as float64."""
    j = np.arange(n_diag, dtype=np.float64)
    band = np.minimum(float(bandwidth), n_diag - 1 - j) + arrow
    corner = arrow - 1 - np.arange(arrow, dtype=np.float64)
    return np.concatenate([band, corner])


def cholesky(n_diag: int, bandwidth: int, arrow: int,
             itemsize: int = 4) -> tuple:
    """(flops, bytes) of one factorization."""
    m = _below(n_diag, bandwidth, arrow)
    return float(((m + 1) ** 2).sum()), float(2 * itemsize * (m + 1).sum())


def selinv(n_diag: int, bandwidth: int, arrow: int,
           itemsize: int = 4) -> tuple:
    """(flops, bytes) of one selected inversion from a factor."""
    m = _below(n_diag, bandwidth, arrow)
    return (float((2 * m * m + 2 * m + 2).sum()),
            float(2 * itemsize * (m + 1).sum()))


def peaks(device_kind: str, path: str = _PEAKS) -> dict:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {path}")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def roofline_share(ctx: dict, count, kernel: str):
    """Percent of the roofline over the executable runs that launched the
    kernel matching ``kernel``: the least time for the useful work of the
    units those runs did (each run does one step's units), over their
    device time.  None where the trace holds no such run."""
    runs = ctx["trace"].runs_with_op(kernel)
    seconds = sum(d for _, d in runs)
    if not runs or seconds <= 0:
        return None
    dep = ctx["dep"]
    flops, nbytes = count(dep.n_diag, dep.bandwidth, dep.arrow)
    least = least_seconds(flops, nbytes, peaks(ctx["device_kind"]))
    return 100.0 * least * len(runs) * ctx["units_per_step"] / seconds
