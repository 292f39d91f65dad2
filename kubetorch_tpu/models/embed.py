"""Text-embedding inference: pooled hidden states, batched and jitted.

The reference's inference tutorial family includes an embedding service
(``python_client/kubetorch/docs/tutorials/inference/triton-embedding.md``
— Triton serving a pooled-encoder model); this is the native equivalent
on the framework's own flagship: one jitted forward over right-padded
prompts, masked mean / last-token / CLS pooling over the final hidden
states, optional L2 normalization. Works with the quantized (int8) tree
and under a device mesh like every other model entry point.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from kubetorch_tpu.models import llama
from kubetorch_tpu.models.configs import LlamaConfig
from kubetorch_tpu.parallel.sharding import ShardingRules

POOLINGS = ("mean", "last", "first")


def _embed_impl(params, tokens, lens, *, pooling, normalize, cfg, rules):
    B, P = tokens.shape
    # hidden_states already applies the final RMS norm
    x = llama.hidden_states(params, tokens, cfg, rules)      # [B, P, E]
    x = x.astype(jnp.float32)
    mask = (jnp.arange(P)[None, :] < lens[:, None])
    if pooling == "mean":
        denom = jnp.maximum(lens[:, None].astype(jnp.float32), 1.0)
        emb = jnp.sum(x * mask[:, :, None], axis=1) / denom
    elif pooling == "last":
        emb = jnp.take_along_axis(
            x, (lens - 1)[:, None, None], axis=1)[:, 0]
    else:                                                    # "first"
        emb = x[:, 0]
    if normalize:
        emb = emb / jnp.maximum(
            jnp.linalg.norm(emb, axis=-1, keepdims=True), 1e-9)
    return emb


class Embedder:
    """Batched embedding endpoint over the flagship decoder.

    >>> emb = Embedder(params, cfg, pooling="mean")
    >>> vecs = emb.embed([[1, 5, 9], [2, 7]])    # [2, E] float32, L2=1
    """

    def __init__(self, params: Dict[str, Any], cfg: LlamaConfig,
                 mesh=None, rules: Optional[ShardingRules] = None,
                 pooling: str = "mean", normalize: bool = True,
                 pad_id: int = 0):
        if pooling not in POOLINGS:
            raise ValueError(f"pooling must be one of {POOLINGS}, "
                             f"got {pooling!r}")
        self.params = params
        self.cfg = cfg
        self.mesh = mesh
        self.rules = rules or ShardingRules.default()
        self.pad_id = pad_id
        self._fn = jax.jit(partial(
            _embed_impl, pooling=pooling, normalize=normalize, cfg=cfg,
            rules=self.rules))

    def embed(self, prompts: Sequence[Sequence[int]],
              bucket: int = 16) -> np.ndarray:
        """[len(prompts), embed_dim] float32. Prompts right-pad to a
        power-of-two bucket so compile count stays O(log max_len)."""
        B = len(prompts)
        lens = np.array([len(p) for p in prompts], np.int32)
        if B == 0 or (lens <= 0).any():
            raise ValueError("empty prompt")
        P = bucket
        while P < lens.max():
            P *= 2
        if P > self.cfg.max_seq_len:
            raise ValueError(f"prompt length {lens.max()} exceeds "
                             f"max_seq_len {self.cfg.max_seq_len}")
        toks = np.full((B, P), self.pad_id, np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p
        ctx = (jax.set_mesh(self.mesh) if self.mesh is not None
               else contextlib.nullcontext())
        with ctx:
            out = self._fn(self.params, jnp.asarray(toks),
                           jnp.asarray(lens))
        return np.asarray(jax.device_get(out))
