"""The HBM-streamed band Cholesky sweep (``kernels/band_cholesky.py``) in
Pallas interpret mode: agreement with the ring-scan oracle, the choice
between it and the fused ring sweep, and its path through ``repro.api``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import cholesky
from repro.core.batching import LRUCache
from repro.core.structure import ArrowheadStructure, TileGrid
from repro.data.gmrf import TABLE2
from repro.kernels import ops, ref, ring
from repro.kernels.band_cholesky import (band_cholesky_stream_sweep_pallas,
                                         stream_bytes, sweep_path)
from repro.kernels.ring import band_row_to_col
from repro.runtime import telemetry

NAMES = ("panels", "R_out", "schur", "status")


def _spd_ctsf(n, bw, ar, t, seed=0):
    from repro.data import make_arrowhead
    A, st = make_arrowhead(n, bw, ar, rho=0.6, seed=seed)
    grid = TileGrid(st, t=t)
    return api.BandedCTSF.from_sparse(A, grid), grid


def _assert_sweeps_agree(got, want):
    for g, w, name in zip(got, want, NAMES):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


# (n, bw, arrow, t, nchunks): band tiles 1 (no arrow), 3 (one arrow tile),
# 5 (two), 7 with ndt 8 (bt next to ndt) and 11 with ndt 12 (several
# window blocks a source column, the last moved back)
STREAM_GRIDS = [(160, 8, 0, 16, 1), (88, 24, 8, 8, 2), (96, 40, 16, 8, 3),
                (72, 60, 8, 8, 2), (104, 88, 8, 8, 4)]


@pytest.mark.parametrize("n,bw,ar,t,nchunks", STREAM_GRIDS)
def test_stream_sweep_matches_the_oracle(n, bw, ar, t, nchunks):
    bm, _ = _spd_ctsf(n, bw, ar, t)
    Ac = band_row_to_col(bm.Dr)
    _assert_sweeps_agree(
        band_cholesky_stream_sweep_pallas(Ac, bm.R, nchunks=nchunks,
                                          interpret=True),
        ref.band_cholesky_sweep_ref(Ac, bm.R, nchunks=nchunks))


@pytest.mark.parametrize("start_tile", [2, 9])
def test_stream_sweep_start_tile(start_tile):
    """A traced identity prefix: identity panels and zero arrow rows there,
    and no source column of the prefix enters a later column's update."""
    from repro.core import embed_ctsf
    bm, grid = _spd_ctsf(104, 88, 16, 8)
    cgrid = TileGrid.from_tile_counts(8, grid.n_diag_tiles + start_tile,
                                      grid.band_tiles, grid.n_arrow_tiles)
    emb = embed_ctsf(bm, cgrid)
    Ac = band_row_to_col(emb.Dr)
    st = jnp.asarray(start_tile, jnp.int32)
    got = band_cholesky_stream_sweep_pallas(Ac, emb.R, nchunks=3,
                                            start_tile=st, interpret=True)
    _assert_sweeps_agree(got, ref.band_cholesky_sweep_ref(
        Ac, emb.R, nchunks=3, start_tile=st))
    plain = ref.band_cholesky_sweep_ref(band_row_to_col(bm.Dr), bm.R)
    np.testing.assert_allclose(np.asarray(got[0])[start_tile:],
                               np.asarray(plain[0]), rtol=2e-4, atol=2e-4)


def test_stream_sweep_vmap():
    """A batch rides one launch: vmap folds into the kernel's batch axis,
    nested vmaps too."""
    mats = [_spd_ctsf(88, 24, 8, 8, seed=s)[0] for s in range(4)]
    Acb = jnp.stack([band_row_to_col(m.Dr) for m in mats]).reshape(
        (2, 2) + mats[0].Dr.shape)
    Rb = jnp.stack([m.R for m in mats]).reshape((2, 2) + mats[0].R.shape)
    sweep = lambda a, r: band_cholesky_stream_sweep_pallas(
        a, r, nchunks=2, interpret=True)
    got = jax.vmap(jax.vmap(sweep))(Acb, Rb)
    for i in range(4):
        want = ref.band_cholesky_sweep_ref(Acb[i // 2, i % 2],
                                           Rb[i // 2, i % 2], nchunks=2)
        _assert_sweeps_agree([g[i // 2, i % 2] for g in got], want)


def _table2_grid(matrix_id, t=128):
    n, bw, arrow = TABLE2[matrix_id]
    return TileGrid(ArrowheadStructure(n=n, bandwidth=bw, arrow=arrow), t=t)


@pytest.mark.parametrize("matrix_id,path", [(10, "fused"), (11, "fused"),
                                            (19, "stream")])
def test_sweep_path_at_table2_shapes(matrix_id, path):
    g = _table2_grid(matrix_id)
    assert sweep_path(g.t, g.band_tiles, g.n_arrow_tiles) == path


def test_stream_bytes_of_table2_id19():
    """The streamed sweep's DMA schedule at ID 19 (ndt 391, bt 118, one
    arrow tile): 460 MB of window a full column, 179.3 GB a matrix."""
    g = _table2_grid(19)
    assert (g.n_diag_tiles, g.band_tiles, g.n_arrow_tiles) == (391, 118, 1)
    assert stream_bytes(391, 118, 1, 128) == 179303677952
    # the schedule read a column at a time: 2 (bt+1+nat) tiles in and out,
    # and per source j the blocks covering tiles j..bt plus the arrow tile
    tile = 128 * 128 * 4
    full = sum(-(-(119 - j) // 8) * 8 + 1 for j in range(1, 119))
    assert stream_bytes(200, 118, 1, 128) - stream_bytes(199, 118, 1, 128) \
        == (2 * 120 + full) * tile


@pytest.fixture
def fresh_caches(monkeypatch):
    """Compiled factorizations keyed before a patched VMEM cap must not
    answer for it, nor outlive it."""
    monkeypatch.setattr(cholesky, "_BATCHED_WINDOW_CACHE",
                        LRUCache(maxsize=8, name="batched_window"))
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_auto_streams_a_band_over_the_cap(monkeypatch, fresh_caches):
    """With the VMEM cap below the ring's ask, ``auto`` takes the streamed
    sweep through ``repro.api`` (counted and tagged), and its factor,
    log-determinants and solves agree with the fused sweep's."""
    mats = [_spd_ctsf(104, 88, 8, 8, seed=s)[0] for s in range(2)]
    grid = mats[0].grid
    opts = api.SolverOptions(impl="pallas")
    y = jnp.asarray(np.random.default_rng(3).standard_normal(
        grid.padded_n), jnp.float32)

    def run():
        fac = api.factorize_window_batched(mats, options=opts)
        return (fac.ctsf, api.concurrent_logdet(fac),
                api.concurrent_solve(fac, y, options=opts))

    fused = run()
    launched = []
    real = ops.band_cholesky_stream_sweep_pallas
    monkeypatch.setattr(ops, "band_cholesky_stream_sweep_pallas",
                        lambda *a, **k: launched.append(1) or real(*a, **k))
    monkeypatch.setattr(ring, "VMEM_CAP_BYTES", 2 ** 20)
    jax.clear_caches()
    assert sweep_path(grid.t, grid.band_tiles, grid.n_arrow_tiles) \
        == "stream"
    telemetry.reset()
    with telemetry.capture():
        streamed = run()
        snap = telemetry.snapshot()
    telemetry.reset()
    assert launched
    assert snap["counters"]["cholesky.sweep{path=stream}"] == 1.0
    span, = [s for s in snap["spans"]
             if s["name"] == "factorize.window_batched"]
    assert span["tags"]["sweep"] == "stream"
    assert span["tags"]["tile_block"] == grid.t      # t = 8: unblocked
    assert span["tags"]["stream_bytes"] == stream_bytes(
        grid.n_diag_tiles, grid.band_tiles, grid.n_arrow_tiles, grid.t)
    for a, b in zip((fused[0].Dr, fused[0].R, fused[0].C, fused[1],
                     fused[2]),
                    (streamed[0].Dr, streamed[0].R, streamed[0].C,
                     streamed[1], streamed[2])):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-4)


def test_forced_fused_over_the_cap_raises(monkeypatch, fresh_caches):
    bm, _ = _spd_ctsf(104, 88, 8, 8)
    monkeypatch.setattr(ring, "VMEM_CAP_BYTES", 2 ** 20)
    with pytest.raises(ValueError, match="VMEM"):
        api.factorize_window(
            bm, options=api.SolverOptions(impl="pallas", sweep="fused"))
