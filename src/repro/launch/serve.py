"""Serving driver: batched prefill + autoregressive decode.

``python -m repro.launch.serve --arch qwen2-7b --prompt-len 64 --gen 32``
serves a reduced model on local devices; the full-config serve graphs are
exercised (lower+compile) by launch/dryrun.py on the production meshes.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import ModelConfig, RunConfig
from repro.models.registry import get_model
from repro.sharding.partition import make_rules
from .mesh import make_local_mesh
from .train import reduce_config

__all__ = ["Server", "main"]


def _pad_caches(caches, target_len: int):
    """Grow attention caches from prefill length to the serving window."""

    def pad(path, x):
        keys = [str(getattr(p, "key", getattr(p, "idx", ""))) for p in path]
        name = keys[-1]
        if name in ("k", "v") and x.ndim == 5 and x.shape[2] < target_len:
            padw = [(0, 0)] * 5
            padw[2] = (0, target_len - x.shape[2])
            return jnp.pad(x, padw)
        return x

    return jax.tree_util.tree_map_with_path(pad, caches)


class Server:
    """Minimal batched-request server: prefill once, decode greedily."""

    def __init__(self, cfg: ModelConfig, run: RunConfig, max_len: int = 512,
                 seed: int = 0):
        self.cfg, self.run, self.max_len = cfg, run, max_len
        self.api = get_model(cfg)
        self.mesh = make_local_mesh()
        self.rules = make_rules(self.mesh, cfg, run)
        self.params = self.api.init(jax.random.PRNGKey(seed), cfg, max_len)
        self._decode = jax.jit(
            lambda p, c, t, pos: self.api.decode_step(p, c, t, pos, cfg, run))
        self._prefill = jax.jit(
            lambda p, b: self.api.prefill(p, b, cfg, run))

    def generate(self, batch: Dict[str, np.ndarray], gen_len: int
                 ) -> Dict[str, Any]:
        t0 = time.perf_counter()
        logits, caches = self._prefill(self.params, batch)
        caches = _pad_caches(caches, self.max_len)
        prefill_t = time.perf_counter() - t0
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out = [tok]
        pos = batch["tokens"].shape[1]
        t0 = time.perf_counter()
        for i in range(gen_len - 1):
            logits, caches = self._decode(self.params, caches, tok,
                                          jnp.asarray(pos + i, jnp.int32))
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            out.append(tok)
        tokens = jnp.concatenate(out, axis=1)
        tokens.block_until_ready()
        decode_t = time.perf_counter() - t0
        b = tokens.shape[0]
        return {"tokens": np.asarray(tokens),
                "prefill_s": prefill_t, "decode_s": decode_t,
                "decode_tok_per_s": b * (gen_len - 1) / max(decode_t, 1e-9)}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="qwen2-7b", choices=configs.ARCH_IDS)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--gen", type=int, default=32)
    args = p.parse_args()
    cfg = reduce_config(configs.get(args.arch))
    run = RunConfig(remat="none", loss_chunk=128)
    server = Server(cfg, run, max_len=args.prompt_len + args.gen)
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, cfg.vocab,
                                    (args.batch, args.prompt_len)).astype(np.int32)}
    if cfg.family == "vlm":
        batch["image_embeds"] = np.zeros(
            (args.batch, cfg.n_image_tokens, cfg.d_model), np.float32)
    if cfg.family == "encdec":
        batch["frame_embeds"] = rng.standard_normal(
            (args.batch, cfg.encoder_seq, cfg.d_model)).astype(np.float32)
    out = server.generate(batch, args.gen)
    print(f"prefill {out['prefill_s']*1e3:.1f} ms; "
          f"decode {out['decode_tok_per_s']:.1f} tok/s; "
          f"sample: {out['tokens'][0][:16].tolist()}")


if __name__ == "__main__":
    main()
