"""Explicit data-parallel trainer with int8 error-feedback gradient
compression on the cross-pod axis.

The jit/GSPMD trainer (launch/train.py) lets XLA insert the gradient
all-reduce, which cannot be intercepted for wire compression.  This variant
makes the reduction explicit: params replicated across the ``pod`` axis,
batch sharded, per-pod gradients reduced by ``ef_compress_allreduce``
(int8 on the wire + error feedback).  Used when RunConfig.pod_grad_compression
is set and by the fault-tolerance/compression tests.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.optim.adamw import adamw_update, clip_by_global_norm
from repro.optim.compress import ef_compress_allreduce, ef_init

__all__ = ["make_compressed_dp_step"]


def make_compressed_dp_step(loss_fn: Callable, mesh: Mesh, axis: str = "data",
                            lr: float = 1e-3, weight_decay: float = 0.0,
                            grad_clip: float = 1.0, bits: int = 8):
    """loss_fn(params, batch) -> scalar.  Returns (step_fn, ef_init_fn).

    step_fn((params, opt_state, ef_state), batch) -> (state', metrics);
    params replicated, batch sharded on ``axis``.
    """

    def local_step(params, opt, ef, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        loss = jax.lax.pmean(loss, axis)
        grads, ef = ef_compress_allreduce(grads, ef, axis, bits=bits)
        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        new_params, new_opt = adamw_update(grads, opt, params, lr,
                                           weight_decay=weight_decay)
        return new_params, new_opt, ef, {"loss": loss, "grad_norm": gnorm}

    rep = P()
    shd = P(axis)

    def batch_specs(batch):
        return jax.tree.map(lambda _: shd, batch)

    def step(state, batch):
        params, opt, ef = state
        specs_b = batch_specs(batch)
        fn = jax.shard_map(local_step, mesh=mesh,
                           in_specs=(rep, rep, rep, specs_b),
                           out_specs=(rep, rep, rep, rep), check_vma=False)
        p, o, e, m = jax.jit(fn)(params, opt, ef, batch)
        return (p, o, e), m

    return step, ef_init
