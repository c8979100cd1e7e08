"""Table II matrices of sTiles (arXiv 2501.02483), built from their closed
form straight into the banded-arrowhead tile layout, on the device.

A configuration describes

    Q(θ) = [[θ_t·(Q_t(ρ) ⊗ I_ns) + θ_s·(I_nt ⊗ Q_s),  X      ],
            [Xᵀ,                                      θ_f·c·I ]]

with ``ns = bandwidth`` and ``nt = ceil(n_diag / ns)``, truncated to
``n_diag`` latent rows, as the repository's Table II generator makes it:
``Q_t`` the AR(1) precision (diagonal ``1`` at both ends and ``1 + ρ²``
inside, off-diagonal ``-ρ``) plus ``temporal_jitter·I``; ``Q_s`` the 1-D
lattice precision (off-diagonal ``-coupling``, diagonal the row's coupling
weight plus ``spatial_tau``); ``X`` the dense fixed-effect coupling drawn
as ``x_scale/sqrt(n_diag)`` standard normals; ``c = Σx²/schur_slack + 1``.
θ = (1, 1, 1) is the matrix the generator makes.

The latent block has three fixed diagonals (0, ±1, ±ns), so every tile
entry is a closed-form function of its row and column: no host matrix, no
scatter.  The layout is the program's ``BandedCTSF`` (``Dr[m, d]`` the
tile at block row ``m``, column ``m-d``; ``R[k, i]`` arrow tile row ``i``
over band tile column ``k``; ``C`` the corner), with identity on padding.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

# The tile size users of the program pick on a TPU today; the counts in
# counts.py do not depend on it.
TILE = 128


@dataclasses.dataclass(frozen=True)
class Deployment:
    """The sizes and coefficients of one configuration file."""

    n: int
    bandwidth: int
    arrow: int
    rho: float
    coupling: float
    temporal_jitter: float
    spatial_tau: float
    x_scale: float
    schur_slack: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Deployment":
        return cls(**{f.name: cfg[f.name] for f in dataclasses.fields(cls)})

    @property
    def n_diag(self) -> int:
        return self.n - self.arrow

    @property
    def ns(self) -> int:
        return max(1, self.bandwidth)

    @property
    def nt(self) -> int:
        return max(1, math.ceil(self.n_diag / self.ns))


def grid(dep: Deployment, t: int = TILE):
    """The program's ``TileGrid`` of a deployment at tile size ``t``."""
    from repro.core.structure import ArrowheadStructure, TileGrid
    return TileGrid(ArrowheadStructure(n=dep.n, bandwidth=dep.bandwidth,
                                       arrow=dep.arrow), t=t)


def tile_bytes(g, itemsize: int = 4) -> int:
    """Bytes of one matrix's Dr, R and C on the grid ``g``."""
    ndt, bt, nat = g.n_diag_tiles, g.band_tiles, g.n_arrow_tiles
    return itemsize * g.t * g.t * (ndt * (bt + 1) + ndt * nat + nat * nat)


def latent_entry(dep: Deployment, i, j, theta_t, theta_s, pad=1.0):
    """Entry (i, j) of the latent block θ_t·(Q_t⊗I) + θ_s·(I⊗Q_s), either
    triangle, as float32; rows and columns at or past ``n_diag`` read as
    ``pad`` times the identity (1 is the padding of the tile layout)."""
    nd, ns, nt = dep.n_diag, dep.ns, dep.nt
    lo, hi = jnp.minimum(i, j), jnp.maximum(i, j)
    off = hi - lo
    a, b = lo // ns, lo % ns
    f32 = jnp.float32
    qt = jnp.where((a == 0) | (a == nt - 1), f32(1.0),
                   f32(1.0 + dep.rho * dep.rho)) + f32(dep.temporal_jitter)
    qs = (f32(dep.coupling) * ((b > 0).astype(f32) + (b < ns - 1).astype(f32))
          + f32(dep.spatial_tau))
    v = jnp.where(off == 0, theta_t * qt + theta_s * qs, f32(0.0))
    v = v + jnp.where((off == 1) & (b < ns - 1),
                      -f32(dep.coupling) * theta_s, f32(0.0))
    v = v + jnp.where(off == ns, -f32(dep.rho) * theta_t, f32(0.0))
    return jnp.where(hi < nd, v, jnp.where(off == 0, f32(pad), f32(0.0)))


def band_tiles(dep: Deployment, g, theta, pad=1.0):
    """``Dr`` (ndt, bt+1, t, t) of Q(θ); ``theta`` is (θ_t, θ_s, θ_f)."""
    t = g.t
    m = jnp.arange(g.n_diag_tiles, dtype=jnp.int32)[:, None, None, None]
    d = jnp.arange(g.band_tiles + 1, dtype=jnp.int32)[None, :, None, None]
    r = jnp.arange(t, dtype=jnp.int32)[None, None, :, None]
    c = jnp.arange(t, dtype=jnp.int32)[None, None, None, :]
    i = m * t + r
    j = (m - d) * t + c
    v = latent_entry(dep, i, j, theta[0], theta[1], pad)
    return jnp.where(m - d >= 0, v, jnp.float32(0.0))


def arrow_tiles(g, x):
    """``R`` (ndt, nat, t, t) from the coupling X (n_diag, arrow)."""
    t = g.t
    ndt, nat = g.n_diag_tiles, g.n_arrow_tiles
    xp = jnp.zeros((ndt * t, nat * t), jnp.float32)
    xp = xp.at[:x.shape[0], :x.shape[1]].set(x)
    return xp.reshape(ndt, t, nat, t).transpose(0, 2, 3, 1)


def corner_tiles(dep: Deployment, g, theta_f, c):
    """``C`` (nat, nat, t, t) of θ_f·c·I, identity on padding."""
    t = g.t
    nat = g.n_arrow_tiles
    k = jnp.arange(nat * t)
    diag = jnp.where(k < dep.arrow, theta_f * c, jnp.float32(1.0))
    eye = jnp.eye(nat, dtype=jnp.float32)[:, :, None, None] \
        * jnp.eye(t, dtype=jnp.float32)
    return eye * diag.reshape(nat, 1, 1, t)


def seed_key(seed: int):
    """A JAX key for any non-negative seed, also past 32 bits."""
    seed = int(seed) % (1 << 64)
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def coupling_data(dep: Deployment, g, x):
    """(X, R, c) for a coupling X (n_diag, arrow) float32."""
    c = jnp.sum(x * x) / jnp.float32(dep.schur_slack) + jnp.float32(1.0)
    return x, arrow_tiles(g, x), c


def make_data(dep: Deployment, g, seed: int):
    """The seeded data of one run, made on the device in one call: the
    coupling X drawn from ``seed``, its arrow tiles R, and c."""
    def build(key):
        x = jax.random.normal(key, (dep.n_diag, dep.arrow), jnp.float32)
        return coupling_data(
            dep, g, x * jnp.float32(dep.x_scale / math.sqrt(dep.n_diag)))
    return jax.jit(build)(seed_key(seed))


def tiles(dep: Deployment, g, theta, R, c):
    """(Dr, R, C) of Q(θ) for one θ."""
    theta = jnp.asarray(theta, jnp.float32)
    return (band_tiles(dep, g, theta), R,
            corner_tiles(dep, g, theta[2], c))


class Former:
    """Forms Q(θ) on the device from two component tile sets made once,
    the temporal part T and the spatial part S of the band (zero on
    padding): Dr = θ_t·T + θ_s·S, identity on the padding rows, which all
    lie in the last tile row.  Entries are the closed form's, bit for bit.
    ``batch(thetas (P, 3), R, c)`` gives (Dr, R, C) with a leading probe
    axis; ``one(theta, R, c)`` one matrix."""

    def __init__(self, dep: Deployment, g):
        self.dep, self.g = dep, g
        self.parts = jax.jit(lambda: (
            band_tiles(dep, g, (1.0, 0.0), pad=0.0),
            band_tiles(dep, g, (0.0, 1.0), pad=0.0)))()
        self._form = jax.jit(self._batch)

    def batch(self, thetas, R, c):
        return self._form(thetas, R, c, *self.parts)

    def one(self, theta, R, c):
        return tuple(v[0] for v in self.batch(
            jnp.asarray(theta, jnp.float32)[None], R, c))

    def _batch(self, thetas, R, c, T, S):
        dep, g = self.dep, self.g
        tt = thetas[:, 0, None, None, None, None]
        ts = thetas[:, 1, None, None, None, None]
        rows = (g.n_diag_tiles - 1) * g.t + jnp.arange(g.t)
        pad_eye = jnp.diag((rows >= dep.n_diag).astype(jnp.float32))
        dr = (tt * T + ts * S).at[:, -1, 0].add(pad_eye)
        cc = jax.vmap(lambda th: corner_tiles(dep, g, th[2], c))(thetas)
        rr = jnp.broadcast_to(R, (thetas.shape[0],) + R.shape)
        return dr, rr, cc
