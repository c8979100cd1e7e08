"""GPipe-style pipeline parallelism over a mesh axis (`shard_map` + ppermute).

The model is split into S equal stages whose params are stacked on a leading
stage dim and sharded P(axis).  A microbatched forward sweeps the classic
GPipe wavefront: at tick t, stage s processes microbatch (t - s); hidden
states hop stage->stage over `ppermute` (on TPU: neighbour ICI links).  The
whole schedule is differentiable — `jax.grad` through the scan yields the
reverse wavefront, i.e. backward pipelining for free — so this composes with
the training step as an alternative to pure TP for deep models
(`RunConfig` knob; off by default, exercised in tests and the PP example).

Bubble fraction = (S-1)/(M+S-1), the standard GPipe trade; pick M >= 4·S.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_forward", "split_stages"]


def split_stages(stacked_layer_params: Any, n_stages: int) -> Any:
    """(L, ...) stacked layer params -> (S, L/S, ...) stage-stacked."""
    def r(x):
        L = x.shape[0]
        if L % n_stages != 0:
            raise ValueError(f"{L} layers not divisible by {n_stages} stages")
        return x.reshape((n_stages, L // n_stages) + x.shape[1:])
    return jax.tree.map(r, stacked_layer_params)


def pipeline_forward(stage_fn: Callable, stage_params: Any, x: jnp.ndarray,
                     mesh: Mesh, axis: str = "model",
                     n_microbatches: int = 8, remat: bool = True) -> jnp.ndarray:
    """Run ``y = stages(x)`` through the pipeline.

    stage_fn(stage_params_slice, h) -> h', applied by each stage to the
    hidden state; x: (B, ...) with B % n_microbatches == 0.
    """
    S = mesh.shape[axis]
    M = n_microbatches
    B = x.shape[0]
    if B % M != 0:
        raise ValueError(f"batch {B} % microbatches {M} != 0")
    mb = B // M
    xs = x.reshape((M, mb) + x.shape[1:])

    body = jax.checkpoint(stage_fn) if remat else stage_fn

    def local(params, xs_local):
        # params: (1, L/S, ...) this stage's slice; xs_local: (M, mb, ...)
        params = jax.tree.map(lambda p: p[0], params)
        s = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(xs_local[0])
        outs = jnp.zeros_like(xs_local)

        def tick(carry, t):
            buf, outs = carry
            mb_idx = t - s
            active = (mb_idx >= 0) & (mb_idx < M)
            x0 = jax.lax.dynamic_index_in_dim(
                xs_local, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            inp = jnp.where(s == 0, x0, buf)
            out = body(params, inp)
            out = jnp.where(active, out, jnp.zeros_like(out))
            # last stage records its finished microbatch
            outs = jax.lax.cond(
                active & (s == S - 1),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, jnp.clip(mb_idx, 0, M - 1), 0),
                lambda o: o, outs)
            # hop to the next stage (ring permute; stage S-1 -> 0 ignored)
            nxt = jax.lax.ppermute(out, axis,
                                   [(i, (i + 1) % S) for i in range(S)])
            return (nxt, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs),
                                      jnp.arange(M + S - 1))
        # only the last stage holds real outputs (zeros elsewhere): a psum
        # replicates them to every stage
        return jax.lax.psum(outs, axis)

    in_specs = (P(axis), P())
    out_specs = P()
    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    outs = jax.jit(fn)(stage_params, xs)
    return outs.reshape((B,) + x.shape[1:])
