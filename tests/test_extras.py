"""Additional coverage: INLA marginal variances, grad-accumulation
equivalence, flash-attention GQA sweep, bf16 kernels, MoE expert padding."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis",
                    reason="property tests need the hypothesis package")
from hypothesis import given, settings, strategies as st

from repro.core import (BandedCTSF, TileGrid, factorize_window,
                        marginal_variances)
from repro.data import make_arrowhead


def test_marginal_variances_match_dense_inverse():
    A, struct = make_arrowhead(160, 16, 16, rho=0.6, seed=0)
    g = TileGrid(struct, t=16)
    bm = BandedCTSF.from_sparse(A, g)
    f = factorize_window(bm)
    idx = jnp.asarray([0, 7, 63, 150, 159])
    got = np.asarray(marginal_variances(f, idx))
    inv = np.linalg.inv(bm.to_dense(lower_only=False))
    want = np.diag(inv)[np.asarray(idx)]
    np.testing.assert_allclose(got, want, rtol=2e-3)


def test_grad_accumulation_equivalent():
    """ga=4 must produce the same loss and (averaged) grads as ga=1."""
    from repro.configs import get
    from repro.configs.base import RunConfig
    from repro.launch.train import (TrainState, init_state, make_train_step,
                                    reduce_config)
    from repro.data.synthetic import token_batch
    cfg = reduce_config(get("qwen2-7b"), layers=2, d_model=64)
    key = jax.random.PRNGKey(0)
    batch = token_batch(0, 0, 8, 32, cfg.vocab)
    outs = {}
    for ga in (1, 4):
        run = RunConfig(remat="none", loss_chunk=32, grad_accum=ga,
                        compute_dtype="float32")
        state = init_state(key, cfg, run, max_seq=32)
        step = make_train_step(cfg, run, None, total_steps=10)
        new_state, metrics = jax.jit(step)(state, batch)
        outs[ga] = (float(metrics["loss"]), float(metrics["grad_norm"]),
                    jax.tree.leaves(new_state.params)[0])
    assert abs(outs[1][0] - outs[4][0]) < 1e-4          # loss equal
    assert abs(outs[1][1] - outs[4][1]) / outs[1][1] < 1e-3   # grad norm
    np.testing.assert_allclose(np.asarray(outs[1][2]),
                               np.asarray(outs[4][2]), atol=1e-5)


@given(st.sampled_from([(4, 1), (4, 2), (4, 4), (8, 2)]),
       st.sampled_from([16, 24, 48]),
       st.integers(0, 2 ** 16))
@settings(max_examples=10, deadline=None)
def test_flash_attention_gqa_sweep(heads_kv, seq, seed):
    """Flash vs naive for random GQA group configurations and odd lengths."""
    from repro.models.layers import chunked_attention
    H, KV = heads_kv
    D = 8
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((2, seq, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, seq, KV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, seq, KV, D)), jnp.float32)
    got = chunked_attention(q, k, v, causal=True, q_chunk=8, kv_chunk=8)
    # naive reference
    q5 = q.reshape(2, seq, KV, H // KV, D) * D ** -0.5
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q5, k).astype(jnp.float32)
    mask = jnp.tril(jnp.ones((seq, seq), bool))
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    want = jnp.einsum("bhgqk,bkhd->bqhgd", p, v).reshape(2, seq, H, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("t", [16, 32])
def test_kernels_bf16(rng, t):
    """Tile kernels accept bf16 inputs (f32 accumulation inside)."""
    from repro.kernels.gemm import gemm_pallas
    from repro.kernels import ref
    c = jnp.asarray(rng.standard_normal((t, t)), jnp.bfloat16)
    a = jnp.asarray(rng.standard_normal((t, t)), jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal((t, t)), jnp.bfloat16)
    got = gemm_pallas(c, a, b, interpret=True)
    want = ref.gemm_ref(c.astype(jnp.float32), a.astype(jnp.float32),
                        b.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=0.05, atol=0.05)


def test_moe_expert_padding_routes_only_real_experts():
    from repro.models.moe import moe_params, moe_apply
    key = jax.random.PRNGKey(0)
    p = moe_params(key, 16, 32, n_experts=5, pad_to=8)
    assert p["wi"].shape[0] == 8 and p["router"].shape[1] == 5
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 16))
    y = moe_apply(p, x, top_k=2, capacity_factor=4.0)
    assert np.isfinite(np.asarray(y)).all()
    # padded experts contribute nothing: zeroing them changes nothing
    p2 = dict(p)
    for w in ("wi", "wg", "wo"):
        p2[w] = p[w].at[5:].set(0.0)
    y2 = moe_apply(p2, x, top_k=2, capacity_factor=4.0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), atol=1e-6)

