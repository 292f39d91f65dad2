"""Autoregressive generation: jitted prefill + ``lax.scan`` decode loop.

TPU-first shape discipline: prompts are right-padded to a common length, the
KV cache is a preallocated static buffer (``llama.init_cache``), and the whole
``max_new_tokens`` loop is ONE jitted ``lax.scan`` with the cache donated —
no per-token Python dispatch, no dynamic shapes, one compile per
(batch, prompt_len, max_new_tokens) bucket.

Positions and masking with ragged prompts: sequence ``b`` has
``prompt_len[b]`` real tokens at slots ``[0, prompt_len[b])``; generated
tokens go at uniform slots ``Pmax + step`` with RoPE position
``prompt_len[b] + step``. Attention masks out each sequence's pad gap
``[prompt_len[b], Pmax)``.

The reference framework has no inference engine (it deploys e.g. vLLM as an
``App`` — reference ``examples/tutorials/vllm_inference/``); the TPU build
owns the compute path, so rollout generation (BASELINE #5 GRPO) is framework
code.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from kubetorch_tpu.models import llama
from kubetorch_tpu.models.configs import LlamaConfig
from kubetorch_tpu.parallel.sharding import ShardingRules


_TOP_P_CANDIDATES = 2048  # nucleus threshold search space (full sort is
                          # ~0.7 ms/step at V=32k on v5e; top_k of 2048 is
                          # cheaper and exact unless the nucleus is wider)


def filter_logits(logits: jax.Array, top_k: Optional[int] = None,
                  top_p: Optional[float] = None) -> jax.Array:
    """Apply top-k and/or nucleus (top-p) filtering to [B, V] logits."""
    if top_k is not None:
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        # threshold search over the top candidates only (lax.top_k returns
        # them sorted); probabilities still normalize over the FULL vocab,
        # so the cutoff matches full-sort semantics exactly whenever the
        # nucleus fits in the candidate set. If the true nucleus is wider
        # than _TOP_P_CANDIDATES (near-flat distribution at top_p→1), the
        # sample is truncated to the top candidates — narrower than exact
        # nucleus sampling. Accepted trade-off for the ~0.7 ms/step the
        # full 32k-vocab sort costs on v5e.
        c = min(_TOP_P_CANDIDATES, logits.shape[-1])
        cand = jax.lax.top_k(logits, c)[0]            # [B, c] descending
        logz = jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
        probs = jnp.exp(cand - logz)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative prob >= top_p (always
        # keep the argmax); threshold = logit of the last kept token.
        keep = cum - probs < top_p
        kth = jnp.min(jnp.where(keep, cand, jnp.inf), axis=-1, keepdims=True)
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return logits


def sample_tokens(rng: jax.Array, logits: jax.Array, temperature: float,
                  top_k: Optional[int], top_p: Optional[float]) -> jax.Array:
    """Sample [B] token ids from [B, V] logits (greedy iff temperature==0)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = filter_logits(logits / temperature, top_k, top_p)
    return jax.random.categorical(rng, logits)


class Generator:
    """Batched KV-cache text generation for the flagship Llama.

    >>> gen = Generator(params, cfg)
    >>> out = gen.generate([[1, 5, 9], [1, 7]], max_new_tokens=16,
    ...                    temperature=0.8, top_p=0.9, eos_id=2, seed=0)

    Works under a device mesh: pass ``mesh`` (and optionally ``rules``) and
    call inside or outside ``jax.set_mesh`` — params keep their shardings and XLA
    propagates them into the cache.
    """

    def __init__(self, params: Dict[str, Any], cfg: LlamaConfig,
                 mesh=None, rules: Optional[ShardingRules] = None,
                 pad_id: int = 0, kv_dtype: str = "bf16",
                 adapters=None, adapter_scale: Optional[float] = None):
        """``kv_dtype="int8"``: per-vector-quantized KV cache — halves
        the decode's cache stream and residency (the batch ceiling moves
        up accordingly); greedy outputs are near-identical to the bf16
        cache (argmax flips on near-ties only — pinned in tests).

        ``adapters``: multi-adapter serving — a stacked tree from
        ``models.lora.stack_adapters`` (``{name: {"a": [L,n,K,r],
        "b": [L,n,r,N]}}``); each request picks its adapter via
        ``generate(..., adapter_ids=[...])`` (index -1 = base model).
        ``adapter_scale`` defaults to LoraConfig's alpha/rank — pass the
        value used in training."""
        self.params = params
        self.cfg = cfg
        self.mesh = mesh
        self.rules = rules or ShardingRules.default()
        self.pad_id = pad_id
        self.kv_quantized = kv_dtype == "int8"
        self.adapters = adapters
        if adapters is not None and adapter_scale is None:
            raise ValueError(
                "adapters need adapter_scale (= LoraConfig.scale used "
                "in training)")
        self.adapter_scale = adapter_scale
        self.n_adapters = (next(iter(adapters.values()))["a"].shape[1]
                           if adapters is not None else 0)
        if adapters is not None:
            from kubetorch_tpu.models.lora import validate_adapter_targets

            # fail fast on fused/unfused target mismatch (a missing
            # target silently contributes a zero delta inside the model)
            validate_adapter_targets(adapters, params["layers"])
        self._prefill = jax.jit(
            partial(self._prefill_impl, cfg=cfg, rules=self.rules,
                    quantized=self.kv_quantized),
            static_argnames=("max_len", "quantized"))
        # note: no cache donation — the decode returns only tokens, so XLA
        # has no same-shaped output to alias the donated buffer to.
        self._decode = jax.jit(
            partial(self._decode_impl, cfg=cfg, rules=self.rules),
            static_argnames=("n_steps", "temperature", "top_k", "top_p",
                             "eos_id", "pad_id", "repetition_penalty"))

    # -------------------------------------------------------------- impl
    @staticmethod
    def _prefill_impl(params, tokens, prompt_lens, lora, *, max_len, cfg,
                      rules, quantized=False):
        B, P = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(P)[None, :], (B, P))
        # causal over the prompt region; pad queries produce unused rows.
        m = jnp.arange(max_len)[None, None, :]
        t = jnp.arange(P)[None, :, None]
        mask = (m <= t) & (m < prompt_lens[:, None, None])
        cache = llama.init_cache(cfg, B, max_len, quantized=quantized)
        # next-token logits at each sequence's last real token only — the
        # full [B, P, V] logits would be GBs of HBM at 128k vocab.
        logits, cache = llama.forward_cached(
            params, tokens, positions, cache, 0, mask, cfg, rules,
            unembed_positions=prompt_lens - 1, lora=lora)
        return logits[:, 0], cache

    @staticmethod
    def _decode_impl(params, cache, first_logits, prompt_lens, rng, win0,
                     lora, *,
                     n_steps, temperature, top_k, top_p, eos_id, pad_id,
                     repetition_penalty, cfg, rules):
        B = first_logits.shape[0]
        M = cache["k"].shape[2]
        Pmax = M - n_steps
        slot_idx = jnp.arange(M)[None, :]

        def step(carry, i):
            cache, logits, done, rng, win = carry
            if repetition_penalty != 1.0:
                # HF semantics over the rolling last-W window (−1 = empty)
                idx = jnp.maximum(win, 0)
                gathered = jnp.take_along_axis(logits, idx, axis=1)
                adjusted = jnp.where(gathered > 0,
                                     gathered / repetition_penalty,
                                     gathered * repetition_penalty)
                # empty slots (−1) scatter out of range and drop — see
                # rolling.py _decode_impl for the duplicate-index hazard
                sidx = jnp.where(win >= 0, win, logits.shape[-1])
                logits = logits.at[jnp.arange(B)[:, None], sidx].set(
                    adjusted, mode="drop")
            rng, key = jax.random.split(rng)
            tok = sample_tokens(key, logits, temperature, top_k, top_p)
            tok = jnp.where(done, pad_id, tok)
            if eos_id is not None:
                done = done | (tok == eos_id)
            win = jnp.concatenate([win[:, 1:], tok[:, None]], axis=1)
            write_at = Pmax + i
            positions = (prompt_lens + i)[:, None]
            # attend: real prompt slots + generated slots up to write_at
            mask = ((slot_idx < prompt_lens[:, None])
                    | ((slot_idx >= Pmax) & (slot_idx <= write_at)))[:, None, :]
            logits, cache = llama.forward_cached(
                params, tok[:, None], positions, cache, write_at, mask,
                cfg, rules, lora=lora)
            return (cache, logits[:, 0], done, rng, win), tok

        done0 = jnp.zeros((B,), bool)
        (_, _, done, _, _), toks = jax.lax.scan(
            step, (cache, first_logits, done0, rng, win0),
            jnp.arange(n_steps))
        return toks.T, done  # [B, n_steps]

    # -------------------------------------------------------------- api
    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        max_new_tokens: int = 128,
        temperature: float = 0.7,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        eos_id: Optional[int] = None,
        seed: int = 0,
        repetition_penalty: float = 1.0,
        stop: Optional[Sequence[Sequence[int]]] = None,
        adapter_ids: Optional[Sequence[int]] = None,
    ) -> List[List[int]]:
        """Generate continuations; returns per-prompt token lists
        (truncated at ``eos_id`` if given, which is included).

        ``adapter_ids`` (multi-adapter serving): per-prompt index into
        the stacked adapter tree; -1 serves the bare base model.
        ``repetition_penalty`` (HF semantics, last-64-token window; seeded
        from the prompt tail) runs inside the scan. ``stop`` sequences trim
        post-hoc — the static scan still runs ``max_new_tokens`` steps, so
        prefer :class:`~kubetorch_tpu.models.rolling.RollingGenerator` when
        stop sequences usually fire early."""
        B = len(prompts)
        lens = np.array([len(p) for p in prompts], np.int32)
        if (lens <= 0).any():
            raise ValueError("empty prompt")
        Pmax = int(lens.max())
        toks = np.full((B, Pmax), self.pad_id, np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p
        max_len = Pmax + max_new_tokens
        if max_len > self.cfg.max_seq_len:
            raise ValueError(
                f"prompt+generation {max_len} exceeds max_seq_len "
                f"{self.cfg.max_seq_len}")

        import contextlib

        ctx = (jax.set_mesh(self.mesh) if self.mesh is not None
               else contextlib.nullcontext())
        W = 64
        win0 = np.full((B, W), -1, np.int32)
        if repetition_penalty != 1.0:
            for i, p in enumerate(prompts):
                tail = list(p)[-W:]
                win0[i, -len(tail):] = tail
        lora = None
        if self.adapters is not None:
            ids = [-1] * B if adapter_ids is None else list(adapter_ids)
            if len(ids) != B:
                raise ValueError(
                    f"adapter_ids has {len(ids)} entries for {B} prompts")
            slots = np.full(B, -1, np.int32)
            for i, a in enumerate(ids):
                if not -1 <= a < self.n_adapters:
                    raise ValueError(
                        f"adapter id {a} out of range "
                        f"({self.n_adapters} adapters; -1 = base)")
                slots[i] = a
            lora = {"adapters": self.adapters,
                    "slots": jnp.asarray(slots),
                    "scale": float(self.adapter_scale)}
        elif adapter_ids is not None:
            raise ValueError("adapter_ids passed but Generator has no "
                             "adapters")
        with ctx:
            first_logits, cache = self._prefill(
                self.params, jnp.asarray(toks), jnp.asarray(lens), lora,
                max_len=max_len)
            out, done = self._decode(
                self.params, cache, first_logits, jnp.asarray(lens),
                jax.random.key(seed), jnp.asarray(win0), lora,
                n_steps=max_new_tokens,
                temperature=float(temperature), top_k=top_k, top_p=top_p,
                eos_id=eos_id, pad_id=self.pad_id,
                repetition_penalty=float(repetition_penalty))
        out = np.asarray(jax.device_get(out))
        stop_seqs = [list(s) for s in (stop or []) if s]
        results: List[List[int]] = []
        for row in out:
            seq = row.tolist()
            if eos_id is not None and eos_id in seq:
                seq = seq[:seq.index(eos_id) + 1]
            for sseq in stop_seqs:
                n = len(sseq)
                for end in range(n, len(seq) + 1):
                    if seq[end - n:end] == sseq:
                        seq = seq[:end]
                        break
            results.append(seq)
        return results
