"""Speculative greedy decoding with prompt-lookup (n-gram) drafts.

The reference serves LLMs by deploying vLLM as an ``App``
(``examples/tutorials/vllm_inference/deepseek_llama_70b.py``); vLLM's
n-gram speculator is part of what it delegates to. This is the TPU-native
equivalent, built on the framework's own cache machinery: draft tokens are
proposed model-free by matching the last *n* tokens of the context against
earlier occurrences (prompt-lookup decoding), then verified in ONE cached
forward of ``K`` tokens — accepted prefixes advance the sequence several
tokens per model pass, and greedy output is **token-identical** to plain
greedy decoding by construction (a draft is only kept where it equals the
model's own argmax).

Where it wins: decode is weight-stream-bound at small batch (the 8B int8
step reads ~9 GB of weights whether it decodes 1 or K tokens), so every
accepted draft is nearly free — repetitive/extractive workloads (code
editing, RAG quoting, summarization) see multi-token acceptance. Random
text degrades gracefully to ~1 token per pass (one extra unembed of K
positions is the only overhead).

TPU-first mechanics:

- contiguous per-sequence cache layout (slot == true position), purely
  causal masks;
- the verify forward runs in the cache's CHUNK mode
  (``llama._block_cached_chunk``): the K fed tokens land at uniform
  columns of a small per-round chunk cache and attention merges the
  read-only grid with the chunk under one softmax — per-sequence grid
  scatters would rewrite whole cache layers per K-token pass and were
  measured to erase the entire speculation win on device;
- only the ACCEPTED prefix merges into the grid, once per round, with
  the same row loop of slice updates rolling decode uses
  (``ops/grid_write.py``: each row's K-column window, nothing else of
  the grid); rejected drafts are simply never merged, so there is no
  rollback;
- the whole generate loop is one jitted ``lax.while_loop`` — draft
  matching, the K-token verify forward, acceptance-prefix math, the
  merge, and the output scatter all run on device with static shapes.

Sampling (temperature > 0) uses speculative **rejection sampling**,
which is exact for the deterministic n-gram draft: the draft
distribution is a point mass, so draft ``d`` is accepted with
probability ``p(d)`` under the (temperature/top-k/top-p filtered)
target distribution, and on rejection the next token is sampled from
the residual ``p`` with ``d``'s mass removed and renormalized — the
emitted sequence is distributed exactly as non-speculative sampling
(pinned by a Monte-Carlo distribution test). ``repetition_penalty`` is
not supported here (use the static ``Generator``/``RollingGenerator``).
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from kubetorch_tpu.lookahead import LookaheadState  # noqa: F401
#   (re-exported: the per-row adaptive-lookahead state machine lives in
#   kubetorch_tpu/lookahead.py — stdlib-only so the jax-free serving
#   engine can import it — but spec callers reach it from here)
from kubetorch_tpu.models import llama
from kubetorch_tpu.models.configs import LlamaConfig
from kubetorch_tpu.parallel.sharding import ShardingRules


def _ngram_draft(cext: jax.Array, clen: jax.Array, nt: jax.Array,
                 *, n: int, k: int) -> jax.Array:
    """Prompt-lookup proposal: [B, k-1] draft tokens.

    ``cext`` [B, L]: context with ``nt`` already placed at slot ``clen``
    (conceptual length clen+1). Finds the LATEST earlier position whose
    n-gram equals the context's last n tokens and proposes the tokens that
    followed it. No match → repeats ``nt`` (rejected after one round,
    degrading to plain greedy).
    """
    B, L = cext.shape
    pos = jnp.arange(L)[None, :]
    # end positions e of candidate n-grams (e indexes cext; the suffix
    # n-gram ends at clen). Candidates must end before the suffix does.
    match = pos < clen[:, None]
    for j in range(n):
        # candidate token at e-j vs suffix token at clen-j
        cand = jnp.take_along_axis(
            cext, jnp.broadcast_to(jnp.maximum(pos - j, 0), (B, L)), axis=1)
        suff = jnp.take_along_axis(
            cext, jnp.maximum(clen[:, None] - j, 0), axis=1)
        match = match & (cand == suff) & (pos - j >= 0)
    best_e = jnp.max(jnp.where(match, pos, -1), axis=1)          # [B]
    off = jnp.arange(1, k)[None, :]                              # [B, k-1]
    idx = jnp.clip(best_e[:, None] + off, 0, L - 1)
    drafts = jnp.take_along_axis(cext, idx, axis=1)
    # beyond the known context, or no match at all: fall back to nt
    valid = (best_e[:, None] >= 0) & (best_e[:, None] + off <= clen[:, None])
    return jnp.where(valid, drafts, nt[:, None])


def rejection_accept(probs, feed, key, *, k, kk=None):
    """Speculative rejection acceptance for a point-mass draft: [B]
    accepted-draft count (0..k-1). Draft ``feed[:, i+1]`` is accepted at
    position ``i`` with probability ``p_i(draft)`` under ``probs``
    [B, k, V]; acceptance stops at the first reject (cumprod). Shared by
    the static generator and the rolling engine's sampled spec path —
    the math must never diverge between them.

    ``kk`` [B] (optional): per-row lookahead inside a width-``k``
    dispatch — positions past ``kk − 1`` drafts are forced-rejected, so
    a row behaves exactly as if it had been dispatched at its own
    ``kk`` (the acceptance test never reads its masked positions'
    draws). The adaptive rolling engine runs rows at different ``k`` in
    ONE chunk-mode forward this way."""
    B = feed.shape[0]
    if k <= 1:
        return jnp.zeros((B,), jnp.int32)
    p_draft = jnp.take_along_axis(
        probs[:, :-1], feed[:, 1:, None], axis=2)[..., 0]    # [B, k-1]
    u = jax.random.uniform(key, (B, k - 1))
    ok = u < p_draft
    if kk is not None:
        ok = ok & (jnp.arange(k - 1)[None, :] < (kk[:, None] - 1))
    return jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)


def residual_next(probs, feed, acc, key, *, k, kk=None):
    """Exact next-token draw at the acceptance break: the residual
    distribution (the rejected draft's mass removed, renormalized) on a
    rejection, the full break-position distribution on a full accept —
    together with :func:`rejection_accept` this makes the emitted
    stream distributed exactly as non-speculative sampling.

    ``kk`` [B] (optional): per-row lookahead inside a width-``k``
    dispatch. ``acc == kk − 1`` is that row's FULL accept — its next
    token draws from the unmodified break distribution (the draft at
    the truncation boundary was never tested, so removing its mass
    would be wrong), exactly as a ``k = kk`` dispatch would."""
    V = probs.shape[-1]
    j = jnp.clip(acc, 0, k - 1)
    p_j = jnp.take_along_axis(probs, j[:, None, None], axis=1)[:, 0]
    if k > 1:
        rejected = (acc < (k - 1) if kk is None
                    else acc < (kk - 1))
        d_rej = jnp.take_along_axis(
            feed, jnp.clip(acc + 1, 0, k - 1)[:, None], axis=1)[:, 0]
        removed = jnp.where(
            rejected[:, None],
            jnp.arange(V)[None, :] == d_rej[:, None], False)
        resid = jnp.where(removed, 0.0, p_j)
        total = jnp.sum(resid, axis=-1, keepdims=True)
        # p(d)≈1 rejected has ~zero residual mass (measure-zero); fall
        # back to p_j rather than divide by ~0
        p_next = jnp.where(total > 1e-9, resid / total, p_j)
    else:
        p_next = p_j
    return jax.random.categorical(
        key, jnp.log(p_next + 1e-30)).astype(jnp.int32)


class SpeculativeGenerator:
    """Greedy generation with n-gram speculative verification.

    >>> gen = SpeculativeGenerator(params, cfg, k=8, ngram=3)
    >>> outs = gen.generate(prompts, max_new_tokens=128, eos_id=2)

    ``k`` tokens are verified per model pass (1 carried token + k-1
    drafts); ``k=1`` disables speculation (plain decode in the same
    layout — the equivalence tests pin ``k>1`` output to it token for
    token). ``temperature>0`` switches to exact speculative rejection
    sampling (module docstring). ``kv_dtype="int8"`` runs the quantized
    grid (serving density): the verify forward reads the int8 grid + a
    bf16 chunk and only the accepted prefix quantizes into the grid at
    the merge — same machinery as the int8 rolling engine.
    """

    def __init__(self, params: Dict[str, Any], cfg: LlamaConfig,
                 mesh=None, rules: Optional[ShardingRules] = None,
                 pad_id: int = 0, k: int = 8, ngram: int = 3,
                 kv_dtype: str = "bf16"):
        if k < 1:
            raise ValueError("k must be >= 1")
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype must be 'bf16' or 'int8', "
                             f"got {kv_dtype!r}")
        self.kv_quantized = kv_dtype == "int8"
        self.params = params
        self.cfg = cfg
        self.mesh = mesh
        self.rules = rules or ShardingRules.default()
        self.pad_id = pad_id
        self.k = int(k)
        self.ngram = int(ngram)
        self._prefill = jax.jit(
            partial(self._prefill_impl, cfg=cfg, rules=self.rules,
                    quantized=self.kv_quantized),
            static_argnames=("max_len", "quantized"))
        self._decode = jax.jit(
            partial(self._decode_impl, cfg=cfg, rules=self.rules),
            static_argnames=("max_new", "k", "ngram", "eos_id", "pad_id",
                             "temperature", "top_k", "top_p"))

    # -------------------------------------------------------------- impl
    @staticmethod
    def _prefill_impl(params, tokens, prompt_lens, *, max_len, cfg, rules,
                      quantized=False):
        B, P = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(P)[None, :], (B, P))
        m = jnp.arange(max_len)[None, None, :]
        t = jnp.arange(P)[None, :, None]
        mask = (m <= t) & (m < prompt_lens[:, None, None])
        cache = llama.init_cache(cfg, B, max_len, quantized=quantized)
        logits, cache = llama.forward_cached(
            params, tokens, positions, cache, 0, mask, cfg, rules,
            unembed_positions=prompt_lens - 1)
        return logits[:, 0], cache

    @staticmethod
    def _decode_impl(params, cache, first_logits, prompt_lens, ctx0, rng, *,
                     max_new, k, ngram, eos_id, pad_id, temperature,
                     top_k, top_p, cfg, rules):
        from kubetorch_tpu.models.generate import (
            filter_logits,
            sample_tokens,
        )

        B = first_logits.shape[0]
        M = cache["k"].shape[2]
        L = ctx0.shape[1]
        nL = cache["k"].shape[0]
        sampled = temperature > 0.0

        def _probs(lg):
            # [*, V] filtered target distribution — same tempering/filter
            # order as generate.sample_tokens, so spec sampling draws from
            # the identical per-position distribution. filter_logits is
            # [rows, V]-shaped; flatten any leading dims.
            shp = lg.shape
            flat = filter_logits(lg.reshape(-1, shp[-1]) / temperature,
                                 top_k, top_p)
            return jax.nn.softmax(flat, axis=-1).reshape(shp)

        if sampled:
            rng, key0 = jax.random.split(rng)
            nt0 = sample_tokens(key0, first_logits, temperature,
                                top_k, top_p).astype(jnp.int32)
        else:
            nt0 = jnp.argmax(first_logits, axis=-1).astype(jnp.int32)
        out0 = jnp.full((B, max_new), pad_id, jnp.int32)
        bidx = jnp.arange(B)[:, None]
        cdt = jnp.bfloat16 if "ks" in cache else cache["k"].dtype
        chunk0 = {
            "k": jnp.zeros((nL, B, k) + cache["k"].shape[3:], cdt),
            "v": jnp.zeros((nL, B, k) + cache["v"].shape[3:], cdt)}

        def cond(state):
            _, _, _, _, _, _, _, done, rounds, _ = state
            # done already folds in the token budget (see body's tail)
            return (rounds < max_new) & jnp.any(~done)

        def body(state):
            (cache, chunk, ctx, clen, nt, out, out_len, done, rounds,
             rng) = state
            # --- draft k-1 tokens from the context (+ nt at slot clen)
            cext = ctx.at[bidx, clen[:, None]].set(nt[:, None], mode="drop")
            if k > 1:
                drafts = _ngram_draft(cext, clen, nt, n=ngram, k=k)
                feed = jnp.concatenate([nt[:, None], drafts], axis=1)
            else:
                feed = nt[:, None]                               # [B, 1]
            # --- one verify forward of T=k tokens at true positions.
            # Chunk mode: the grid stays read-only; the fed tokens land at
            # uniform chunk cols 0..k-1 (one dynamic-update-slice, no
            # per-sequence scatter), and attention spans grid ∪ chunk.
            positions = clen[:, None] + jnp.arange(k)[None, :]
            gmask = jnp.broadcast_to(
                jnp.arange(M)[None, None, :] < clen[:, None, None],
                (B, k, M))
            emask = jnp.broadcast_to(
                jnp.arange(k)[None, None, :] <= jnp.arange(k)[None, :, None],
                (B, k, k))
            logits, chunk = llama.forward_cached(
                params, feed, positions, cache, None, gmask, cfg, rules,
                chunk=chunk, chunk_col=0, chunk_mask=emask)
            if sampled:
                # Rejection sampling over the point-mass draft (shared
                # helpers — the rolling engine's sampled spec path uses
                # the same math): exact, emitted tokens are distributed
                # as non-speculative sampling from the same filtered p.
                rng, ku, ks = jax.random.split(rng, 3)
                probs = _probs(logits)                           # [B,k,V]
                acc = rejection_accept(probs, feed, ku, k=k)
                nxt = residual_next(probs, feed, acc, ks, k=k)
            else:
                g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B,k]
                # acceptance prefix: drafts[i] (= feed[i+1]) vs g[:, i]
                if k > 1:
                    ok = (feed[:, 1:] == g[:, :-1])
                    acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32),
                                              axis=1), axis=1)   # 0..k-1
                else:
                    acc = jnp.zeros((B,), jnp.int32)
                # next carried token: the model's argmax after the last
                # accepted token (correction on reject, bonus on full
                # accept)
                nxt = jnp.take_along_axis(
                    g, jnp.clip(acc, 0, k - 1)[:, None], axis=1)[:, 0]
            emit = 1 + acc                                       # nt + drafts
            # eos truncation within the emitted prefix
            if eos_id is not None:
                is_eos = (feed == eos_id) & \
                    (jnp.arange(k)[None, :] < emit[:, None])
                any_eos = jnp.any(is_eos, axis=1)
                first = jnp.argmax(is_eos, axis=1)
                emit = jnp.where(any_eos, first + 1, emit)
                new_done = done | any_eos
            else:
                new_done = done
            emit = jnp.where(done, 0, emit)
            emit = jnp.minimum(emit, max_new - out_len)
            # --- scatter emitted tokens into the output buffer
            opos = out_len[:, None] + jnp.arange(k)[None, :]
            valid = jnp.arange(k)[None, :] < emit[:, None]
            sidx = jnp.where(valid, opos, max_new)
            out = out.at[bidx, sidx].set(
                jnp.where(valid, feed, pad_id), mode="drop")
            # --- advance: context mirrors the cache's accepted prefix
            # (emit is 0 for done rows, so cvalid needs no done guard)
            cpos = clen[:, None] + jnp.arange(k)[None, :]
            cvalid = jnp.arange(k)[None, :] < emit[:, None]
            ctx = ctx.at[bidx, jnp.where(cvalid, cpos, L)].set(
                jnp.where(cvalid, feed, 0), mode="drop")
            # --- merge ONLY the accepted prefix of the chunk into the
            # grid (llama.merge_chunk_into_grid: a row loop of slice
            # updates over each row's k-column window, the columns at or
            # past ``emit`` left as they were); rejected drafts never
            # land, so there is nothing to roll back. ``emit`` is already
            # 0 for done rows (not visited) and budget-clamped — it IS
            # the per-row advance.
            cache = llama.merge_chunk_into_grid(cache, chunk, clen, emit)
            clen = clen + emit
            out_len = out_len + emit
            nt = jnp.where(new_done, nt, nxt)
            new_done = new_done | (out_len >= max_new)
            return (cache, chunk, ctx, clen, nt, out, out_len, new_done,
                    rounds + 1, rng)

        state = (cache, chunk0, ctx0, prompt_lens.astype(jnp.int32), nt0,
                 out0, jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool),
                 jnp.int32(0), rng)
        state = jax.lax.while_loop(cond, body, state)
        out, out_len, rounds = state[5], state[6], state[8]
        return out, out_len, rounds

    # -------------------------------------------------------------- api
    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        max_new_tokens: int = 128,
        eos_id: Optional[int] = None,
        return_stats: bool = False,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        seed: int = 0,
    ):
        """Continuations; optionally also per-call stats
        ``{"rounds", "tokens", "tokens_per_pass"}``.

        ``temperature=0`` (default): greedy, token-identical to
        non-speculative greedy. ``temperature>0``: speculative rejection
        sampling — exact samples from the same filtered distribution as
        ``Generator.generate`` (module docstring), drafts accepted with
        probability ``p(draft)``."""
        B = len(prompts)
        lens = np.array([len(p) for p in prompts], np.int32)
        if (lens <= 0).any():
            raise ValueError("empty prompt")
        Pmax = int(lens.max())
        max_len = Pmax + max_new_tokens + self.k + 1
        if max_len > self.cfg.max_seq_len + self.k + 1:
            raise ValueError(
                f"prompt+generation {Pmax + max_new_tokens} exceeds "
                f"max_seq_len {self.cfg.max_seq_len}")
        toks = np.full((B, Pmax), self.pad_id, np.int32)
        ctx0 = np.zeros((B, max_len + 1), np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p
            ctx0[i, :len(p)] = p

        ctx = (jax.set_mesh(self.mesh) if self.mesh is not None
               else contextlib.nullcontext())
        with ctx:
            first_logits, cache = self._prefill(
                self.params, jnp.asarray(toks), jnp.asarray(lens),
                max_len=max_len)
            out, out_len, rounds = self._decode(
                self.params, cache, first_logits, jnp.asarray(lens),
                jnp.asarray(ctx0), jax.random.key(seed),
                max_new=max_new_tokens, k=self.k,
                ngram=self.ngram, eos_id=eos_id, pad_id=self.pad_id,
                temperature=float(temperature), top_k=top_k, top_p=top_p)
        out = np.asarray(jax.device_get(out))
        out_len = np.asarray(jax.device_get(out_len))
        rounds = int(jax.device_get(rounds))
        results: List[List[int]] = []
        for b, row in enumerate(out):
            seq = row[:out_len[b]].tolist()
            if eos_id is not None and eos_id in seq:
                seq = seq[:seq.index(eos_id) + 1]
            results.append(seq)
        if return_stats:
            total = int(sum(len(r) for r in results))
            return results, {
                "rounds": rounds, "tokens": total,
                "tokens_per_pass": total / max(rounds, 1) / B * 1.0
                if B else 0.0}
        return results
