"""Structural flop and byte counts, and the table of peaks."""
import json
import math

import numpy as np
import pytest

from chipbench import counts, tiles


def _direct(n_diag, bandwidth, arrow):
    """Count the flops of a literal right-looking Cholesky and Takahashi
    selected inversion of a dense matrix with the banded-arrowhead pattern,
    operation by operation over the pattern's entries."""
    n = n_diag + arrow
    pat = np.zeros((n, n), bool)
    for i in range(n):
        for j in range(i + 1):
            pat[i, j] = i >= n_diag or i - j <= bandwidth
    chol = sel = 0
    for j in range(n):
        below = [i for i in range(j + 1, n) if pat[i, j]]
        m = len(below)
        chol += 1 + m                       # sqrt, divisions
        for a, p in enumerate(below):       # rank-1 update of the pattern
            for q in below[:a + 1]:
                assert pat[p, q]            # no fill outside the pattern
                chol += 2
        sel += m                            # v = l_j / L_jj
        sel += m * m + m * (m - 1)          # Σ_SS · v
        sel += 2 + 2 * m                    # Σ_jj = 1/L_jj² - v·Σ_Sj
    entries = int(pat.sum())
    return chol, sel, entries


@pytest.mark.parametrize("n_diag,bandwidth,arrow", [
    (12, 3, 2), (9, 0, 3), (20, 5, 0), (15, 14, 4)])
def test_counts_match_a_direct_count(n_diag, bandwidth, arrow):
    chol, sel, entries = _direct(n_diag, bandwidth, arrow)
    f, b = counts.cholesky(n_diag, bandwidth, arrow)
    assert f == chol and b == 2 * 4 * entries
    f, b = counts.selinv(n_diag, bandwidth, arrow)
    assert f == sel and b == 2 * 4 * entries


@pytest.mark.parametrize("cfg", ["t2-id10", "t2-id11"])
def test_counts_do_not_change_with_the_tile_size(cfg):
    from chipbench import harness
    dep = tiles.Deployment.from_config(harness.load_config(cfg))
    seen = set()
    for t in (16, 32, 64, 128):
        # the stored tiles do change with t ...
        seen.add(tiles.tile_bytes(tiles.grid(dep, t)))
        # ... the counts take only the structure
        seen_counts = (counts.cholesky(dep.n_diag, dep.bandwidth, dep.arrow),
                       counts.selinv(dep.n_diag, dep.bandwidth, dep.arrow))
        if t == 16:
            first = seen_counts
        assert seen_counts == first
    assert len(seen) == 4


def test_least_time_of_table2_id10():
    f, b = counts.cholesky(100_000, 1000, 200)
    peak = counts.peaks("TPU v5 lite")
    assert math.isclose(f, 1.434e11, rel_tol=1e-3)
    # memory bound: about 0.96 GB at 819 GB/s
    assert counts.least_seconds(f, b, peak) == b / peak["hbm_bytes_per_s"]


def test_unknown_device_kind_raises(tmp_path):
    with pytest.raises(KeyError):
        counts.peaks("TPU v99")
    p = tmp_path / "peaks.json"
    p.write_text(json.dumps({"A": {"flops_per_s": 1, "hbm_bytes_per_s": 1,
                                   "source": "x"}}))
    assert counts.peaks("A", str(p))["flops_per_s"] == 1
    with pytest.raises(KeyError):
        counts.peaks("TPU v5 lite", str(p))


def test_every_peak_names_its_source():
    with open(counts._PEAKS) as f:
        for kind, row in json.load(f).items():
            assert row["source"] and row["flops_per_s"] > 0 \
                and row["hbm_bytes_per_s"] > 0, kind
