"""Continuous (rolling) batching for KV-cache generation.

The reference serves LLMs by deploying vLLM as an ``App`` workload
(reference: ``examples/tutorials/vllm_inference/``); the TPU build owns the
serving compute, so it needs vLLM's core scheduling idea natively: requests
join and leave a shared decode batch at any time, instead of the whole
batch blocking until its slowest member finishes (the static
:class:`~kubetorch_tpu.models.generate.Generator` contract).

TPU shape discipline + dispatch discipline:

- Everything is static-shaped. The engine owns a cache whose leaves are of
  two sorts (``models/decoder.py``): positional ones, ``[L, max_slots,
  max_len, ...]`` (keys and values, a latent: read to a row's depth), and
  row-state ones, ``[L, max_slots, ...]`` with no position axis (a
  recurrent state: what a row is after its last token, whatever its depth).
  A positional leaf may be a RING of its own span, ``[L, max_slots, span,
  ...]`` with position ``p`` at ``p % span`` (a window layer's keys and
  values: read to ``min(depth, span)``).
  A *slot* is a batch row. New requests prefill into a free slot (jitted per
  padded-length bucket), and decode advances **all** active slots, each at
  its own depth.
- The layers and what a cache leaf holds are the decoder's
  (``models/decoder.py``: ``decoder_for(cfg)``); this module names no leaf
  and asks only which leaves are row state (``row_leaves``) and which are
  rings (``ring_leaves``).
- All decode state (cache, pending logits, depths, active mask) lives on
  device between calls; the host holds only bookkeeping. Each
  :meth:`step` is ONE jit call running ``steps_per_call`` tokens through a
  ``lax.scan`` and ONE host sync for the emitted block — chunking
  amortizes the per-token Python dispatch of a naive rolling loop. Requests finish
  mid-chunk: their surplus tokens are trimmed on the host and their slot
  frees at the chunk boundary (≤ ``steps_per_call − 1`` wasted
  slot-tokens), which is the latency/throughput knob.

Greedy rolling decode is token-identical to isolated ``Generator`` runs
(pinned in ``tests/test_rolling.py``).
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kubetorch_tpu.config import env_float, env_int
from kubetorch_tpu.lookahead import LookaheadState, spec_stats_dict
from kubetorch_tpu.observability import devstats
from kubetorch_tpu.models.decoder import (decoder_for, grid_dims,
                                          off_grid_leaves, position_bytes,
                                          ring_leaves, ring_position_bytes,
                                          row_bytes, row_leaves)
from kubetorch_tpu.models.generate import filter_logits
from kubetorch_tpu.ops import grid_write
from kubetorch_tpu.parallel.sharding import ShardingRules


def _named(impl, **fixed):
    """``partial(impl, **fixed)`` under the implementation's own name:
    ``jax.jit`` names the executable for the function it is given, and a
    bare ``partial`` has none, so a profiler trace would list every
    engine executable as ``jit__unknown``."""
    fn = partial(impl, **fixed)
    fn.__name__ = impl.__name__
    fn.__qualname__ = impl.__qualname__
    return fn


def _ctx_admit_impl(ctx, rows, slots):
    return ctx.at[slots].set(rows, mode="drop")


def _fold(key):
    """Raw key data as ``RollingGenerator._draw_key`` hands it out -> the
    dispatch's own key."""
    return jax.random.wrap_key_data(key)


# jitted in its own right: every executable that ends in it (one a prefill
# bucket and width, the decode chunk's step) then traces it once a shape,
# not once an executable, and the compiler inlines it all the same
@partial(jax.jit, static_argnames=("top_k", "top_p"))
def draw_tokens(logits, temps, penalties, window, key, top_k, top_p):
    """Logits ``[B, V]`` to a token a row: the one sampler of the rolling
    engine, called by a decode step on the carried logits and by an
    admission on the prefill's.

    ``window`` [B, W] holds each row's recent token ids (-1 = empty);
    ``penalties`` [B] apply HF-style repetition penalty to those ids
    (positive logits divided, negative multiplied). Rows with ``temps > 0``
    draw from the tempered, filtered distribution under ``key``; the others
    take the argmax of the penalised logits."""
    B = logits.shape[0]
    pen = penalties[:, None]                               # [B, 1]
    idx = jnp.maximum(window, 0)
    gathered = jnp.take_along_axis(logits, idx, axis=1)    # [B, W]
    adjusted = jnp.where(gathered > 0, gathered / pen, gathered * pen)
    # Empty window slots (-1) scatter out of range and drop: a
    # duplicate-index .set is nondeterministic, so routing them to
    # index 0 could silently erase token 0's penalty.
    sidx = jnp.where(window >= 0, window, logits.shape[-1])
    logits = logits.at[jnp.arange(B)[:, None], sidx].set(
        adjusted, mode="drop")
    # temper BEFORE filtering (generate.sample_tokens order), so the top-p
    # nucleus is computed on the tempered distribution (filter-then-temper
    # picked a different support whenever top_p was set and
    # temperature != 1)
    logits_f = filter_logits(
        logits / jnp.maximum(temps, 1e-6)[:, None],
        top_k=top_k, top_p=top_p)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampled = jax.random.categorical(
        key, logits_f, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def _bucket(n: int, lo: int = 16) -> int:
    """Pad length → power-of-two bucket (few compiles cover all prompts)."""
    b = lo
    while b < n:
        b *= 2
    return b


class Request:
    __slots__ = ("rid", "prompt", "max_new_tokens", "temperature",
                 "tokens", "done", "slot", "prefix_id", "stop",
                 "repetition_penalty", "adapter_id", "consumed")

    def __init__(self, rid, prompt, max_new_tokens, temperature):
        self.rid = rid
        self.prompt = list(prompt)
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.tokens: List[int] = []
        self.done = False
        self.slot: Optional[int] = None
        self.prefix_id: Optional[int] = None
        self.stop: List[List[int]] = []
        self.repetition_penalty: float = 1.0
        self.adapter_id: int = -1
        self.consumed = 0  # prompt tokens already prefilled (chunked path)

    def match_stop(self) -> Optional[int]:
        """Earliest index (exclusive) at which a stop sequence completes in
        ``tokens``; None if no stop sequence has appeared."""
        best = None
        for seq in self.stop:
            n = len(seq)
            for end in range(n, len(self.tokens) + 1):
                if self.tokens[end - n:end] == seq:
                    if best is None or end < best:
                        best = end
                    break
        return best


class RollingGenerator:
    """Continuous-batching engine over a fixed slot grid.

    >>> eng = RollingGenerator(params, cfg, max_slots=8)
    >>> rid = eng.submit([1, 2, 3], max_new_tokens=64)
    >>> while eng.pending:
    ...     for rid, toks, done in eng.step():
    ...         ...
    """

    def __init__(self, params: Dict[str, Any], cfg,
                 max_slots: int = 8, max_len: Optional[int] = None,
                 mesh=None, rules: Optional[ShardingRules] = None,
                 eos_id: Optional[int] = None, top_k: Optional[int] = None,
                 top_p: Optional[float] = None, seed: int = 0,
                 steps_per_call: int = 8, admit_width: int = 0,
                 adapters=None, adapter_scale: Optional[float] = None,
                 lora_slots: Optional[int] = None,
                 kv_dtype: str = "bf16", spec_k: Optional[int] = 0,
                 spec_ngram: Optional[int] = None,
                 spec_ema_alpha: Optional[float] = None,
                 prefill_chunk: Optional[int] = None):
        """``kv_dtype="int8"``: per-vector-quantized grid — halves the
        serving cache's stream and residency, moving the slot ceiling the
        same way it moved the static Generator's batch ceiling (112 → 192
        at 8B). Decode chunks stay bf16 and quantize at the once-per-chunk
        merge; admission prefills quantize on write.

        ``spec_k > 1``: speculative continuous batching — each decode
        "step" becomes a VERIFY ROUND: per-slot prompt-lookup (n-gram)
        drafts ride one chunk-mode forward, and only each slot's
        accepted prefix merges into the grid
        (``models/speculative.py`` machinery, per-slot depths). Greedy
        output stays token-identical to the plain engine;
        ``steps_per_call`` then counts rounds per dispatch. Decode is
        weight-bound below the compute roofline, so at low-to-mid
        occupancy every accepted draft is nearly free — this is the
        latency-regime lever vLLM gets from its n-gram speculator.

        ``spec_k`` is the MAXIMUM per-row lookahead (``None`` reads
        ``KT_SPEC_K_MAX``): each row carries its OWN ``k``, driven by
        a per-row acceptance-rate EMA (``spec_ema_alpha`` /
        ``KT_SPEC_EMA_ALPHA``; state machine in
        ``kubetorch_tpu/lookahead.py``) — high-accept rows grow toward
        ``spec_k``, random-text rows collapse to ``k = 1`` (plain
        decode: no drafts offered, no verify FLOPs wasted). Rows at
        different ``k`` coexist in one dispatch: the forward runs at
        the power-of-two width covering the widest active row and
        per-slot masking forced-rejects positions past each row's
        ``k`` — rejected drafts never merge. ``spec_cap`` /
        :meth:`set_spec_cap` is the serving scheduler's occupancy
        throttle (cap 1 = every row clamps to plain decode while the
        batch is compute-bound).

        Composes with the int8 grid (verify reads int8 grid + bf16 chunk;
        accepted prefixes quantize at the merge), per-request LoRA
        (the adapter index rides the verify forward; drafting is
        model-free), shared prefixes (the prefix tokens seed the draft
        haystack), and CHUNKED PREFILL (the haystack seeds when the
        prompt's last chunk lands and the row activates — a long
        prompt never stalls the speculating rows around it).
        ``temperature > 0`` runs exact per-slot speculative
        REJECTION sampling (drafts accepted with probability ``p(draft)``
        under the filtered distribution; rejections draw from the
        residual — the emitted stream is distributed exactly as
        non-speculative sampling); ``repetition_penalty != 1`` is
        rejected, matching the static ``SpeculativeGenerator``.

        ``prefill_chunk``: prompts longer than this prefill in
        ``prefill_chunk``-token chunks written STRAIGHT INTO the shared
        grid at the row's current depth (one ``_prefill_extend`` dispatch
        per chunk, interleaved between decode chunks by the serving
        engine) instead of one monolithic private-cache prefill — a long
        prompt never stalls token emission for the live rows. ``None``
        (default) keeps the one-shot bucketed admission path everywhere;
        requests with ``prefix_id`` (their context is mostly
        pre-computed) keep it regardless."""
        self.params = params
        self.cfg = cfg
        self.model = decoder_for(cfg)
        # a decoder that lacks a serving feature says so here, by name
        self.model.check_serving(
            cfg, kv_dtype=kv_dtype,
            spec=(spec_k if spec_k is not None
                  else env_int("KT_SPEC_K_MAX")) > 1,
            adapters=adapters is not None,
            mesh=mesh is not None and mesh.size > 1)
        self.mesh = mesh
        self.rules = rules or ShardingRules.default()
        self.max_slots = max_slots
        self.max_len = max_len or cfg.max_seq_len
        # Widest single prefill call. At serving scale (112 slots × 8B)
        # full-width admission is wrong twice over: the private prefill
        # cache is [L, width, p_pad, Hkv, D] (≈2 GB transient at width
        # 112 beside the 4 GB grid + 9 GB weights), and a churn wave of
        # 3 arrivals would pay a 112-row prefill. 0 = max_slots (the
        # small-engine default, where one width keeps compiles at 2).
        self.admit_width = min(admit_width or max_slots, max_slots)
        self.eos_id = eos_id
        self.top_k = top_k
        self.top_p = top_p
        self.steps_per_call = max(1, steps_per_call)
        # every executable that samples takes its key as raw data made on
        # the host (``_draw_key``): a key of its own for every dispatch
        # with no split to dispatch first and nothing to trace for it
        self._key_data = np.array(jax.random.key_data(jax.random.key(seed)))
        self._draws = 0
        # multi-adapter serving (models/lora.py stack_adapters): a
        # per-slot adapter INDEX rides every prefill/decode call
        # (−1 = base model); llama._lora_apply gathers each row's own
        # rank-r factors, so select cost is flat in the adapter count.
        # ``lora_slots`` (default KT_LORA_SLOTS; 0 = off) pads the
        # stacked tree's adapter axis to a FIXED width so an adapter
        # pool can hot-load/evict slots without recompiling.
        if adapters is not None and adapter_scale is None:
            raise ValueError("adapters need adapter_scale "
                             "(= LoraConfig.scale used in training)")
        if adapters is not None:
            if lora_slots is None:
                lora_slots = env_int("KT_LORA_SLOTS")
            if lora_slots:
                from kubetorch_tpu.models.lora import pad_adapter_slots

                adapters = pad_adapter_slots(adapters, lora_slots)
        self.adapters = adapters
        self.adapter_scale = adapter_scale
        self.n_adapters = (next(iter(adapters.values()))["a"].shape[1]
                           if adapters is not None else 0)
        if adapters is not None:
            from kubetorch_tpu.models.lora import validate_adapter_targets

            # fail fast on fused/unfused target mismatch (a missing
            # target silently contributes a zero delta inside the model)
            validate_adapter_targets(adapters, params["layers"])
        self._slot_adapter = np.full(max_slots, -1, np.int32)

        # device-resident decode state
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype must be 'bf16' or 'int8', "
                             f"got {kv_dtype!r}")
        if spec_k is None:
            spec_k = env_int("KT_SPEC_K_MAX")
        if spec_k < 0 or spec_k == 1:
            raise ValueError("spec_k must be 0 (off) or >= 2")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, "
                             f"got {prefill_chunk}")
        self.prefill_chunk = prefill_chunk
        self.kv_quantized = kv_dtype == "int8"
        self.spec_k = spec_k
        self.spec_ngram = (spec_ngram if spec_ngram is not None
                           else env_int("KT_SPEC_NGRAM"))
        self.spec_ema_alpha = (spec_ema_alpha if spec_ema_alpha is not None
                               else env_float("KT_SPEC_EMA_ALPHA"))
        self.spec = spec_k > 1
        self.cache = self.model.init_cache(cfg, max_slots, self.max_len,
                                           quantized=self.kv_quantized)
        self._logits = jnp.zeros((max_slots, cfg.vocab_size), jnp.float32)
        self._dpos = jnp.zeros((max_slots,), jnp.int32)
        self._dactive = jnp.zeros((max_slots,), bool)
        # Carried next token a row, with a validity flag. An admission
        # draws the row's first token from its prefill's logits and leaves
        # it here (``_finish_admit``): the driver reads it while the decode
        # chunk behind the admission runs, and step 0 of that chunk takes
        # it as given. Speculative rounds carry their next token here all
        # the time: exact speculative SAMPLING draws the post-rejection
        # token from the RESIDUAL distribution inside the verify round, a
        # distribution that cannot be reconstructed later from logits. A
        # row whose flag is False (one that came by ``import_row`` without
        # a carried token) draws its next token from ``_logits``.
        self._dnt = jnp.zeros((max_slots,), jnp.int32)
        self._dnt_valid = jnp.zeros((max_slots,), bool)
        # slot -> (the admission's token array, the row's index in it):
        # first tokens drawn and not yet read. Read, routed and emptied by
        # the next decode chunk, behind its dispatch (``_first_events``).
        self._first_pending: Dict[int, Tuple[Any, int]] = {}
        # rows admitted (each one's prefill complete), and those of them
        # whose first token left at the admission, ahead of the chunk
        self._admissions = {"admitted": 0, "first_tokens_at_admit": 0}
        # ``first_frames(events)``: the serving engine installs its router
        # here, and a fresh row's first token goes out as a frame of its
        # own while the device works off the decode chunk. Hand-driven
        # (None) the token heads the row's list of that chunk, as ever.
        self.first_frames = None
        if self.spec:
            # device-resident token context per slot (prompt + accepted
            # tokens) — the n-gram draft matcher's haystack. Width
            # max_len + 1 so the carried token can sit at slot pos.
            self._ctx = jnp.zeros((max_slots, self.max_len + 1), jnp.int32)
            # acceptance accounting for the serving bench / stats API
            self._spec_rounds = 0
            self._spec_emitted = 0
            self._spec_drafted = 0
            # sticky: flips True on the first sampled request (see
            # _decode_spec_chunk)
            self._spec_sampling = False
            # per-row adaptive lookahead: slot -> LookaheadState
            # (created at admission/activation, dropped with the row);
            # spec_cap is the serving scheduler's occupancy throttle
            # (0 = uncapped, 1 = clamp every row to plain decode)
            self._spec_state: Dict[int, LookaheadState] = {}
            self.spec_cap = 0

        # host bookkeeping
        self._free = list(range(max_slots))
        self._slots: Dict[int, Request] = {}
        self._queue: List[Request] = []
        # slot -> Request mid-chunked-prefill: the row is OWNED (not in
        # _free) but not decoding yet (_dactive False); prefill_step()
        # advances these one chunk per dispatch
        self._prefilling: Dict[int, Request] = {}
        self._next_rid = 0
        self._temps = np.zeros(max_slots, np.float32)
        self._penalties = np.ones(max_slots, np.float32)
        # recent-token window per slot for repetition penalty (−1 = empty)
        self._win = np.full((max_slots, 64), -1, np.int32)
        # prefix_id -> {k, v, len, logits} (device KV blocks, see
        # register_prefix). Ids come from a counter, NOT len(_prefixes):
        # drop_prefix (the KV pool's LRU eviction) punches holes, and a
        # reused id would silently serve the wrong prefix to an old
        # submitter.
        self._prefixes: Dict[int, dict] = {}
        self._next_prefix_id = 0
        # prompt tokens actually run through a prefill forward (suffix
        # only for prefixed admissions; each shared prefix counts once
        # at register_prefix) — the numerator of the serving engine's
        # prefix-sharing savings ratio
        self.prefill_tokens = 0
        # What decode attention reads of the grid, summed per decode chunk
        # on the host (every step of a chunk reads as many): positions
        # below the decoding rows' depths (``live``), positions the
        # attention implementation fetches for them (``read``: live rounded
        # up to the ragged kernel's key blocks, or the whole grid where the
        # einsum pair runs) and the grid's own size. ``_depth`` mirrors
        # ``_dpos`` for decoding rows, so none of it waits for the device.
        self._depth = np.zeros(max_slots, np.int64)
        self._kv_positions = {"live": 0, "read": 0, "grid": 0}
        # What the once-a-chunk merges land and what they rewrite to land
        # it (``_count_merge``), from the same mirror.
        self._merge_positions = {"new": 0, "written": 0}
        # What the bucketed admissions land (each admitted row's own-cache
        # span) and what they rewrite of the grid to land it
        # (``_count_admit``).
        self._admit_positions = {"new": 0, "written": 0}
        # Padded positions of every bucketed admission, and of those whose
        # attention took the flash kernel (``_count_admission``).
        self._prefill_positions = {"prefill_positions": 0,
                                   "prefill_flash_positions": 0}
        with self._mesh_ctx():
            self._ragged_block = self.model.ragged_block(
                cfg, self.max_len, self.cache, self.spec)
        # the decoder's own per-step counters (``model.counters``): decode
        # chunks sum them on the device and they ride the tokens' fetch;
        # what a prefill adds is counted here, on the host
        self._model_counts = {name: 0 for name in self.model.counters}
        # a decoder with routed experts: what its expert layers make of
        # the bucketed admissions (``_count_experts``), by shapes and
        # prompt lengths alone. Tokens a pass and the row tile by bucket
        # (gauges), row tiles of the work lists and those of them that
        # held no pair (the buckets' padding, skipped).
        self._has_experts = "moe_rows_multiplied" in self._model_counts
        self._expert_buckets: Dict[int, Tuple[int, int]] = {}
        self._expert_tiles = {"moe_admission_tiles": 0,
                              "moe_padding_tiles_skipped": 0}
        self._kv_position_bytes = position_bytes(self.model, cfg,
                                                 self.kv_quantized)
        # Row-state leaves (no position axis): what a row holds whatever its
        # depth. Their names, their bytes a row (a gauge), and what decode
        # and admission do with them: rows decoding against rows whose state
        # a step reads and writes, summed over decode steps, and positions
        # the admissions' recurrent scans walk (rounded by chunk and bucket)
        # beside the prompt tokens those admissions held.
        self._row_leaves = row_leaves(self.model, cfg)
        self._state_row_bytes = row_bytes(self.model, cfg,
                                          self.kv_quantized)
        self._state_rows = {"live": 0, "touched": 0}
        self._scan_positions = {"linear_scan_positions": 0,
                                "linear_scan_prompt_tokens": 0}
        # Rings (positional leaves of their own span: a window layer's K
        # and V): name -> span; the leaves that do not lie along max_len;
        # the bytes a position holds over the ring layers (a gauge beside
        # the span: a row holds min(depth, span) of them); what decode
        # reads of them against what the decoding rows hold there, and the
        # key blocks the window layers' admission attention visits against
        # those the band touches (``_count_kv_read``, ``_count_admission``).
        self._ring_leaves = ring_leaves(self.model, cfg)
        self._off_grid = off_grid_leaves(self.model, cfg)
        self._ring_position_bytes = ring_position_bytes(self.model, cfg,
                                                        self.kv_quantized)
        self._ring_span = max(self._ring_leaves.values(), default=0)
        self._window_positions = {"live": 0, "read": 0}
        self._window_blocks = {"prefill_window_key_blocks": 0,
                               "prefill_window_key_blocks_band": 0}
        with self._mesh_ctx():
            self._ring_block = (self.model.ragged_block(
                cfg, self._ring_span, self.cache, self.spec)
                if self._ring_leaves else None)

        # Device-truth utilization accounting: every jitted dispatch
        # below routes through this accumulator (``_dispatch``), which
        # captures each executable's cost_analysis() once per (kind,
        # static-shape key) — mixed spec-k widths attribute to the right
        # executable — and counts per-dispatch FLOPs/HBM bytes for the
        # engine's MFU/MBU gauges.
        self._devstats = devstats.ExecutableCosts()
        self._devstats_peaks: Any = "unset"
        # ``dispatched(kind, key)``: called wherever an executable has
        # been queued. The serving engine installs its tick timer's here
        # beside ``tick_phase``: the tick is filed by what it held, and
        # the stretch in which the device had nothing to do ends.
        self.dispatched = devstats.no_dispatch
        # ``tick_phase(name)`` -> context manager. The serving engine
        # installs its phase timer here so that a decode chunk reports
        # its own halves (``decode_dispatch`` up to the return of the
        # jitted call, ``decode_sync`` the one blocking read) and its
        # host bookkeeping (``route``) where they happen. Hand-driven
        # (or warmed before an engine exists) every phase is a no-op.
        self.tick_phase = contextlib.nullcontext

        # Donation matters here: the cache grid is the largest buffer in
        # the server and every call rewrites it — aliasing in/out keeps
        # updates in place.
        # the sampler's filter is the generator's own and never changes:
        # the admission executables hold it fixed, so a bucket's prefill
        # stays ONE program (keyed by ``p_pad`` and the padded width)
        self._prefill = jax.jit(
            _named(self._prefill_impl, cfg=cfg, rules=self.rules,
                   top_k=top_k, top_p=top_p),
            static_argnames=("p_pad",), donate_argnums=(1, 2, 3, 4, 5, 6))
        self._decode = jax.jit(
            _named(self._decode_impl, cfg=cfg, rules=self.rules),
            static_argnames=("top_k", "top_p", "n_steps"),
            donate_argnums=(1, 2, 3, 6))
        self._prefix_fill = jax.jit(
            _named(self._prefix_fill_impl, cfg=cfg, rules=self.rules,
                   quantized=self.kv_quantized),
            static_argnames=("p_pad",))
        self._prefill_px = jax.jit(
            _named(self._prefill_px_impl, cfg=cfg, rules=self.rules,
                   top_k=top_k, top_p=top_p),
            static_argnames=("p_pad",), donate_argnums=(1, 2, 3, 4, 5, 6))
        self._prefill_ext = jax.jit(
            _named(self._prefill_extend_impl, cfg=cfg, rules=self.rules,
                   top_k=top_k, top_p=top_p),
            static_argnames=("C",), donate_argnums=(1, 2, 3, 4, 5, 6))
        if self.adapters is not None:
            # hot-load: write ONE adapter's factors into a slot of the
            # stacked tree. The slot index is a traced scalar and the
            # destination donates, so the pool loads/evicts with a
            # single compile and zero extra HBM residency — the fixed
            # adapter axis (lora_slots) is what keeps every serving
            # executable valid across loads.
            def _adapter_write_impl(dst, src, idx):
                return jax.tree_util.tree_map(
                    lambda d, s: jax.lax.dynamic_update_slice(
                        d, s.astype(d.dtype),
                        (0, idx) + (0,) * (d.ndim - 2)),
                    dst, src)

            self._adapter_write = jax.jit(_adapter_write_impl,
                                          donate_argnums=(0,))
        if self.spec:
            self._decode_sp = jax.jit(
                _named(self._decode_spec_impl, cfg=cfg, rules=self.rules),
                static_argnames=("k", "ngram", "n_rounds", "top_k",
                                 "top_p", "sampling"),
                donate_argnums=(1, 3, 5, 6, 7))
            self._ctx_admit = jax.jit(_ctx_admit_impl,
                                      donate_argnums=(0,))

    def _dispatch(self, kind: str, key: Any, fn, *args, **kwargs):
        """Run an executable: the ONE place the generator does. Kinds:
        ``prefill`` / ``prefill_px`` keyed ``(n_pad, p_pad)`` (a bucketed
        admission, own or prefix-extended), ``prefill_ext`` keyed by the
        chunk, ``prefix_fill`` by its bucket, ``decode`` by the steps,
        ``decode_spec`` by ``(width, sampling)``, ``ctx_admit`` by its
        padded rows, ``adapter_write``. In a profiler trace the call lies
        in a ``kt.dispatch`` host event with ``kind`` and ``key``, on the
        clock of the module event it queues; once ``fn`` has returned the
        engine's hook hears of it."""
        with jax.profiler.TraceAnnotation("kt.dispatch", kind=kind, key=key):
            out = self._devstats.call(kind, key, fn, *args, **kwargs)
        self.dispatched(kind, key)
        return out

    def _check_adapter_id(self, adapter_id: int) -> None:
        if adapter_id >= 0 and self.adapters is None:
            raise ValueError("adapter_id passed but engine has no "
                             "adapters")
        if adapter_id != -1 and not 0 <= adapter_id < self.n_adapters:
            # mirror Generator: -1 = base model; any other negative is a
            # caller bug, not a base-model request
            raise ValueError(f"adapter id {adapter_id} out of range "
                             f"({self.n_adapters} adapters; -1 = base)")

    # ------------------------------------------------------------ public
    @property
    def pending(self) -> int:
        return (len(self._queue) + len(self._slots)
                + len(self._prefilling))

    @property
    def queued(self) -> int:
        """Requests waiting for a row (not yet admitted)."""
        return len(self._queue)

    @property
    def free_rows(self) -> int:
        return len(self._free)

    def stats(self) -> Dict[str, int]:
        """Host-only counters of this generator (no device read): what
        decode attention read of the KV grid. ``read / grid`` is 1.0 where
        the einsum pair runs and the live share, rounded up to key blocks,
        where the ragged kernel does; and what the merges wrote:
        ``merge_positions_written / _new`` is 1.0 where every landing row
        lands a whole chunk (plain decode) and the window's share above it
        where a row lands part of one; what the bucketed admissions landed
        in the grid and rewrote of it to do so: ``admit_positions_written
        / _new`` is 1.0 while only the admitted rows' spans are written
        (a dummy row of a padded width counts in neither); and what they
        ran: ``prefill_positions`` (rows x the bucket's padded length) and
        the part of them whose attention took the flash kernel,
        ``prefill_flash_positions``. Beside them the decoder's own
        counters (fetched with the tokens of each decode chunk) and the
        bytes one position holds over the layers that keep positions, a
        gauge. For a decoder with row-state leaves (0 for the others):
        ``decode_state_rows_live`` / ``_touched`` (rows decoding, and rows
        whose state a decode step reads and writes, summed over steps),
        ``linear_scan_positions`` / ``_prompt_tokens`` (positions the
        admissions' recurrent scans walk, a layer, and the prompt tokens
        they held) and ``state_row_bytes``, the bytes a row holds whatever
        its depth, a gauge. And ``admitted`` (rows whose prefill is
        complete) beside ``first_tokens_at_admit`` (those of them whose
        first token was read and routed behind the dispatch of the decode
        chunk that follows the admission, not at that chunk's end): equal
        wherever the admission draws the token, which is everywhere but a
        row that leaves before it decodes (a handoff, an eviction). For a
        decoder with rings (0 for the others): ``decode_kv_positions_*``
        count the layers that keep every position, and beside them
        ``decode_window_positions_live`` / ``_read`` (ring positions the
        decoding rows hold, ``min(depth, span)``, and those decode attention
        fetched, by chunk as the other pair), ``prefill_window_key_blocks``
        / ``_band`` (key blocks the window layers' admission attention
        computed, a layer, and those the band touches) and the gauges
        ``window_position_bytes`` (bytes a position holds over the ring
        layers) and ``window_positions`` (the span). For a decoder with
        routed experts: ``moe_rows_multiplied`` beside ``moe_assignments``
        among its device counters (rows the grouped product multiplied
        over the decode steps against the pairs that count), and of its
        bucketed admissions, from shapes and prompt lengths on the host:
        ``moe_piece_tokens_b<bucket>`` and ``moe_tile_rows_b<bucket>``
        (tokens a pass of the expert layer takes and its row tile, gauges
        of every bucket admitted so far), ``moe_admission_tiles`` and
        ``moe_padding_tiles_skipped`` (row tiles of the work lists, and
        those of them that held only the bucket's padding)."""
        out = {f"decode_kv_positions_{k}": int(v)
               for k, v in self._kv_positions.items()}
        out.update(self._admissions)
        out.update((f"merge_positions_{k}", int(v))
                   for k, v in self._merge_positions.items())
        out.update((f"admit_positions_{k}", int(v))
                   for k, v in self._admit_positions.items())
        out.update(self._prefill_positions)
        out.update(self._model_counts)
        if self._has_experts:
            out.update(self._expert_tiles)
            for p_pad, (piece, tile) in self._expert_buckets.items():
                out[f"moe_piece_tokens_b{p_pad}"] = piece
                out[f"moe_tile_rows_b{p_pad}"] = tile
        out["kv_position_bytes"] = self._kv_position_bytes
        out.update((f"decode_state_rows_{k}", int(v))
                   for k, v in self._state_rows.items())
        out.update(self._scan_positions)
        out["state_row_bytes"] = self._state_row_bytes
        out.update((f"decode_window_positions_{k}", int(v))
                   for k, v in self._window_positions.items())
        out.update(self._window_blocks)
        out["window_position_bytes"] = self._ring_position_bytes
        out["window_positions"] = self._ring_span
        return out

    def _count_prefill(self, prompt_tokens: int) -> None:
        for name, n in self.model.prefill_counters(
                self.cfg, prompt_tokens).items():
            self._model_counts[name] += n

    def _count_experts(self, lens, p_pad: int) -> None:
        """Account one bucketed admission's expert layers (a decoder that
        has them): ``lens`` the rows' prompt lengths, a dummy row's 1.
        Called under the generator's mesh, which the kernel's rule asks
        for."""
        if not self._has_experts:
            return
        piece, tile, tiles, skipped = self.model.expert_admission(
            self.cfg, lens, p_pad)
        self._expert_buckets[p_pad] = (piece, tile)
        self._expert_tiles["moe_admission_tiles"] += tiles
        self._expert_tiles["moe_padding_tiles_skipped"] += skipped

    def _count_kv_read(self) -> None:
        """Account one decode chunk, from the depths it starts at."""
        live = self._depth[list(self._slots)]
        grid = self.max_slots * self.max_len
        block = self._ragged_block
        self._kv_positions["live"] += int(live.sum())
        self._kv_positions["read"] += (
            grid if block is None else int((-(-live // block) * block).sum()))
        self._kv_positions["grid"] += grid
        if self._ring_leaves:
            span, block = self._ring_span, self._ring_block
            held = np.minimum(live, span)
            self._window_positions["live"] += int(held.sum())
            self._window_positions["read"] += (
                self.max_slots * span if block is None
                else int((-(-held // block) * block).sum()))

    def _count_state_rows(self, steps: int) -> None:
        """Account one decode chunk of ``steps`` steps: the rows that
        decode, and the rows whose row-state leaves each step reads and
        writes (the decoder says: the decoding rows where its step skips
        an idle row, all of the grid's where it holds one in place)."""
        if not self._row_leaves:
            return
        live = len(self._slots)
        self._state_rows["live"] += steps * live
        self._state_rows["touched"] += steps * self.model.state_rows_touched(
            self.cfg, self.max_slots, live)

    def _count_scan(self, rows: int, length: int, prompt_tokens: int) -> None:
        """Account a prefill of ``rows`` (padded) rows of ``length``
        (padded) positions that held ``prompt_tokens`` real ones: what the
        decoder's recurrent scans walk for it, a layer."""
        walked = self.model.scan_positions(self.cfg, rows, length)
        if walked:
            self._scan_positions["linear_scan_positions"] += walked
            self._scan_positions["linear_scan_prompt_tokens"] += (
                prompt_tokens)

    def _count_merge(self, counts, cols: int) -> None:
        """Account merges of ``cols``-column chunks: ``counts`` is what each
        row lands in each (0 for a row that sits the merge out). ``new`` is
        the positions that land, ``written`` what ``grid_write`` rewrites
        for them: a window of ``cols`` for every row that lands anything,
        and nothing for the others."""
        self._merge_positions["new"] += int(np.sum(counts))
        self._merge_positions["written"] += grid_write.positions_written(
            counts, cols)

    def _count_admit(self, rows: int, slots, span: int) -> None:
        """Account one bucketed admission's landing: ``rows`` requests go to
        ``slots`` (of the padded width: ``max_slots`` for a dummy row), each
        with the ``span`` positions of the own cache. ``new`` is the
        admitted rows' spans, ``written`` what ``grid_write`` rewrites of
        the grid for them."""
        self._admit_positions["new"] += rows * span
        self._admit_positions["written"] += grid_write.row_positions_written(
            slots, self.max_slots, span)

    def _count_admission(self, rows: int, p_pad: int, own: bool) -> None:
        """Account one bucketed admission of ``rows`` (padded) rows at
        ``p_pad`` positions each; ``own``: the prompt's own prefill from
        position 0 (``_prefill_impl``, which states its mask as causal), the
        one the flash kernel can take, not a prefix-extended one. Called
        under the generator's mesh, which the kernel's rule asks for."""
        n = rows * p_pad
        self._prefill_positions["prefill_positions"] += n
        if own and self.model.prefill_flash_engages(self.cfg, p_pad):
            self._prefill_positions["prefill_flash_positions"] += n
        if self._ring_leaves:
            visited, band = self.model.window_key_blocks(self.cfg, p_pad)
            self._window_blocks["prefill_window_key_blocks"] += rows * visited
            self._window_blocks["prefill_window_key_blocks_band"] += (
                rows * band)

    def devstats_snapshot(self) -> Dict[str, float]:
        """Cumulative compiler-truth dispatch costs (FLOPs / HBM bytes
        / dispatch count) — the MFU/MBU numerators. Same surface as
        ``SimRollingEngine.devstats_snapshot``."""
        return self._devstats.snapshot()

    def devstats_peaks(self) -> Optional[Tuple[float, float]]:
        """(peak_flops, peak_bytes_per_s) for this process's device, or
        None on CPU/unknown hardware — the engine then publishes no
        MFU/MBU gauge (absent, not zero). Cached after first read."""
        if self._devstats_peaks == "unset":
            self._devstats_peaks = devstats.device_peaks()
        return self._devstats_peaks

    @property
    def active_rows(self) -> int:
        return len(self._slots)

    @property
    def prefilling_rows(self) -> int:
        return len(self._prefilling)

    @property
    def spec_stats(self) -> Dict[str, float]:
        """Cumulative speculative acceptance: ``tokens_per_pass`` is the
        wall-clock-free speedup bound (each verify pass costs ≈ one
        plain decode step in the weight-bound regime);
        ``accept_rate`` = accepted drafts / drafts offered, and
        ``verify_waste`` its complement in positions — the verify FLOPs
        the per-row adaptation exists to stop spending; ``k_mean`` the
        live rows' mean lookahead."""
        if not self.spec:
            return {}
        return spec_stats_dict(self._spec_rounds, self._spec_emitted,
                               self._spec_drafted, self.spec_row_ks(),
                               self.spec_k, self.spec_cap)

    def set_spec_cap(self, cap: int) -> None:
        """Occupancy throttle (serving scheduler): cap every row's
        lookahead at ``cap`` (0 = uncapped). Takes effect at the next
        decode chunk — rows above the cap clamp immediately."""
        if self.spec:
            self.spec_cap = max(0, int(cap))

    def spec_row_ks(self) -> List[int]:
        """Live rows' current per-row lookahead (metrics / bench).
        Read LOCK-FREE by the serving path's stats/control-frame
        pollers while the driver thread admits and frees rows, so the
        dicts are snapshotted (``list()`` is atomic under the GIL) and
        indexed with ``get`` — a row freed mid-read just drops out."""
        if not self.spec:
            return []
        states = self._spec_state
        ks = (states.get(s) for s in list(self._slots))
        return [st.k for st in ks if st is not None]

    def load_adapter_slot(self, slot: int, adapter) -> None:
        """Hot-load one adapter into slot ``slot`` of the resident
        stacked tree (``serving/adapterpool.py``'s device-apply hook).
        ``adapter`` is a single-adapter stacked tree —
        ``stack_adapters([tree], lcfg, layer_names=params["layers"])``,
        i.e. ``{name: {"a": [L, 1, K, r], "b": [L, 1, r, N]}}`` with
        the same targets as the engine's tree. One dynamic-index
        ``dynamic_update_slice`` per leaf under a single compiled
        executable (the slot index is traced, the destination donates) —
        load/evict never recompiles, and rows decoding under OTHER
        slots are untouched: the gather select reads only each row's
        own slot. The caller must never overwrite a slot with live
        rows — the engine does not refcount slots (the pool does)."""
        if self.adapters is None:
            raise ValueError(
                "engine has no adapter tree (construct with adapters=)")
        if not 0 <= slot < self.n_adapters:
            raise ValueError(f"adapter slot {slot} out of range "
                             f"({self.n_adapters} slots)")
        if set(adapter) != set(self.adapters):
            raise ValueError(
                f"adapter targets {sorted(adapter)} do not match the "
                f"engine tree's {sorted(self.adapters)} — stack with "
                f"the same layer_names")
        with self._mesh_ctx():
            self.adapters = self._dispatch(
                "adapter_write", None, self._adapter_write,
                self.adapters, adapter, jnp.int32(slot))

    def submit(self, prompt, max_new_tokens: int = 128,
               temperature: float = 0.0,
               prefix_id: Optional[int] = None,
               stop: Optional[List[List[int]]] = None,
               repetition_penalty: float = 1.0,
               adapter_id: int = -1) -> int:
        """``stop``: token sequences that terminate generation when they
        appear (included in the output, like ``eos_id``). Checked host-side
        per chunk — multi-token stop strings cost nothing on device.
        ``repetition_penalty`` > 1 discounts tokens seen in the last 64
        positions (HF semantics), applied on device inside the scan."""
        self._check_adapter_id(adapter_id)
        if prefix_id is not None and prefix_id in self._prefixes:
            # prefix KV is weight-dependent: it must have been computed
            # with exactly the adapter this request decodes under, or the
            # spliced rows would silently mix two models
            pfx_aid = self._prefixes[prefix_id]["adapter_id"]
            if pfx_aid != adapter_id:
                raise ValueError(
                    f"prefix {prefix_id} was registered with adapter "
                    f"{pfx_aid}; submit passed adapter_id {adapter_id} "
                    f"(prefix KV is weight-dependent — register one "
                    f"prefix per adapter)")
        if self.spec and repetition_penalty != 1.0:
            # penalty windows would need per-draft-position
            # re-application inside the verify (same restriction as the
            # static SpeculativeGenerator). Sampling IS supported: exact
            # per-slot speculative rejection sampling.
            raise ValueError(
                "speculative engine (spec_k > 1) does not support "
                "repetition_penalty (temperature/top-k/top-p are fine)")
        prefix_len = 0
        if prefix_id is not None:
            if prefix_id not in self._prefixes:
                raise KeyError(f"unknown prefix_id {prefix_id}")
            if not prompt:
                raise ValueError("prefixed submit needs >= 1 suffix token")
            prefix_len = self._prefixes[prefix_id]["len"]
        total = prefix_len + len(prompt) + max_new_tokens
        # worst-case per-dispatch overrun: a request can finish mid-chunk
        # and keep advancing until the chunk boundary (spec: every round
        # can emit spec_k tokens)
        margin = self.steps_per_call * (self.spec_k if self.spec else 1)
        if total + margin > self.max_len:
            raise ValueError(
                f"prefix+prompt+max_new_tokens+chunk_margin "
                f"{prefix_len}+{len(prompt)}+{max_new_tokens}"
                f"+{margin} exceeds max_len {self.max_len}")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, prompt, max_new_tokens, temperature)
        req.prefix_id = prefix_id
        req.stop = [list(s) for s in (stop or []) if s]
        req.repetition_penalty = float(repetition_penalty)
        req.adapter_id = adapter_id
        self._queue.append(req)
        return rid

    def step(self) -> List[Tuple[int, List[int], bool]]:
        """Admit queued requests into free slots, advance any chunked
        prefills by one chunk, run one decode chunk (``steps_per_call``
        tokens). Returns ``(rid, new_tokens, finished)`` per active
        request. The serving engine drives :meth:`admit` /
        :meth:`prefill_step` / :meth:`decode_step` individually (for
        per-phase spans and scheduling control); ``step()`` composes
        them for hand-driven use."""
        self.admit()
        self.prefill_step()
        return self.decode_step()

    def admit(self, max_rows: Optional[int] = None) -> List[int]:
        """Row-granular admission: move queued requests into free rows of
        the LIVE batch (at most ``max_rows`` this wave). Short prompts
        take the grouped private-cache prefill + splice path
        (:meth:`_admit_group`/:meth:`_finish_admit`); prompts longer than
        ``prefill_chunk`` enter CHUNKED prefill — their row is claimed
        now but fills one :meth:`prefill_step` chunk at a time, so a long
        prompt never blocks the decode cadence of the rows around it.
        Returns the rids that left the queue, in admission order (as
        :meth:`prefill_step` returns the rids it activated).

        Batched admission: all same-(bucket, prefix) arrivals prefill in
        ONE call (a per-call dispatch costs more than the prefill compute
        for short prompts; grouping cuts admission dispatches
        ~max_slots×)."""
        admitted: List[int] = []
        by_key: Dict[tuple, List[Request]] = {}
        while self._free and self._queue and (
                max_rows is None or len(admitted) < max_rows):
            req = self._queue.pop(0)
            req.slot = self._free.pop(0)
            admitted.append(req.rid)
            if (self.prefill_chunk is not None
                    and req.prefix_id is None
                    and len(req.prompt) > self.prefill_chunk):
                self._start_chunked(req)
                continue
            key = (_bucket(len(req.prompt)), req.prefix_id)
            by_key.setdefault(key, []).append(req)
        for (p_pad, prefix_id), group in by_key.items():
            for i in range(0, len(group), self.admit_width):
                self._admit_group(group[i:i + self.admit_width], p_pad,
                                  prefix_id)
        return admitted

    def decode_step(self) -> List[Tuple[int, List[int], bool]]:
        """One decode chunk over the active rows (no admission)."""
        if not self._slots:
            return []
        if self.spec:
            return self._decode_spec_chunk()
        return self._decode_chunk()

    def prefill_step(self) -> List[int]:
        """Advance every mid-chunked-prefill row by one
        ``prefill_chunk``-token chunk — ONE dispatch for all of them,
        written straight into the shared grid at each row's depth —
        activating rows whose prompt completes. Returns the rids that
        became decode-active this call."""
        if not self._prefilling:
            return []
        C = self.prefill_chunk
        B = self.max_slots
        feed = np.zeros((B, C), np.int32)
        counts = np.zeros(B, np.int32)
        finals = np.zeros(B, bool)
        done_reqs: List[Request] = []
        for slot, req in self._prefilling.items():
            rem = req.prompt[req.consumed:req.consumed + C]
            feed[slot, :len(rem)] = rem
            counts[slot] = len(rem)
            req.consumed += len(rem)
            if req.consumed >= len(req.prompt):
                finals[slot] = True
                done_reqs.append(req)
                # the chunk that completes the prompt draws its first token
                self._seat(req, len(req.prompt))
        self._count_merge(counts, C)
        self._count_scan(B, C, int(counts.sum()))
        key = self._draw_key()
        with self._mesh_ctx():
            (self.cache, self._logits, self._dpos, self._dactive,
             self._dnt, self._dnt_valid, first) = self._dispatch(
                "prefill_ext", C, self._prefill_ext,
                self.params, self.cache, self._logits, self._dpos,
                self._dactive, self._dnt, self._dnt_valid, feed, counts,
                finals, self._temps, self._penalties, self._win, key,
                self._lora(self._slot_adapter), C=C)
        activated: List[int] = []
        for req in done_reqs:
            del self._prefilling[req.slot]
            self._first_pending[req.slot] = (first, req.slot)
            activated.append(req.rid)
        if self.spec and done_reqs:
            # the chunked-prefill × speculation composition: the draft
            # haystack seeds when the prompt's LAST chunk lands (the
            # grid KV extended chunk by chunk; the host has held the
            # full token sequence all along) — one _ctx_admit dispatch
            # per activation wave, same two padded widths as admission
            n = len(done_reqs)
            n_pad = 1 if n == 1 else self.max_slots
            rows = np.zeros((n_pad, self._ctx.shape[1]), np.int32)
            slots = np.full(n_pad, self.max_slots, np.int32)
            for i, req in enumerate(done_reqs):
                rows[i, :len(req.prompt)] = req.prompt
                slots[i] = req.slot
            self._seed_drafts(done_reqs, rows, slots)
        return activated

    def evict(self, rid: int) -> bool:
        """Row-granular eviction: cancel a queued, mid-prefill, or
        decoding request and free its row immediately. The freed row's
        positional planes are reusable as-is — attention is masked to rows
        below each slot's depth (and a fresh admission rewrites from
        row 0), so stale K/V is never read; its row-state leaves, which
        have no depth to mask by, are zeroed (``_free_rows``). Returns
        whether the rid was found."""
        for i, req in enumerate(self._queue):
            if req.rid == rid:
                self._queue.pop(i)
                return True
        slot = None
        for s, req in self._prefilling.items():
            if req.rid == rid:
                slot = s
                break
        if slot is not None:
            del self._prefilling[slot]
        else:
            for s, req in self._slots.items():
                if req.rid == rid:
                    slot = s
                    break
            if slot is None:
                return False
            del self._slots[slot]
        self._free_rows([slot])
        return True

    def run(self) -> Dict[int, List[int]]:
        """Drain everything; → {rid: generated tokens}."""
        out: Dict[int, List[int]] = {}
        while self.pending:
            for rid, toks, done in self.step():
                out.setdefault(rid, []).extend(toks)
        return out

    def register_prefix(self, tokens, adapter_id: int = -1) -> int:
        """Prefill a shared prefix (system prompt) ONCE; later submissions
        pass ``prefix_id`` and only their suffix is prefetched — the
        prefix's KV rows are copied into the slot at admission. vLLM's
        prefix-caching idea at slot granularity (static shapes: the prefix
        KV block is [L, 1, p_pad, Hkv, D]).

        On the int8 grid the prefix fills a QUANTIZED private cache (the
        same per-vector absmax writes admission prefills use), so its
        int8 values + scale planes splice straight into the grid — the
        serving config keeps both the int8 density win and the
        shared-prefix win. (The prefix forward runs at its own padded
        width, so low-bit K/V values — and near-tie argmaxes — can
        differ from a full-prompt admission, like any cross-width
        comparison.)

        ``adapter_id``: prefix KV is weight-dependent, so a prefix is
        bound to the adapter it was computed with (−1 = base model);
        ``submit`` must pass the matching ``adapter_id``. Per-adapter
        prefix caches are just multiple ``register_prefix`` calls."""
        self._check_adapter_id(adapter_id)
        self.model.check_serving(self.cfg, prefix=True)
        tokens = list(tokens)
        p_pad = _bucket(len(tokens))
        toks = np.zeros((1, p_pad), np.int32)
        toks[0, :len(tokens)] = tokens
        idx = np.full(1, adapter_id, np.int32)
        with self._mesh_ctx():
            planes, logits = self._dispatch(
                "prefix_fill", p_pad, self._prefix_fill,
                self.params, jnp.asarray(toks),
                jnp.int32(len(tokens)), self._lora(idx), p_pad=p_pad)
        pid = self._next_prefix_id
        self._next_prefix_id += 1
        self._prefixes[pid] = {
            "planes": planes, "len": len(tokens), "logits": logits,
            "tokens": tokens, "adapter_id": adapter_id,
        }
        self.prefill_tokens += len(tokens)
        return pid

    def drop_prefix(self, prefix_id: int) -> bool:
        """Release a registered prefix's device KV block (the KV pool's
        LRU eviction hook). Rows already spliced keep their copy — the
        splice is a value copy, not a reference — so dropping is safe at
        any time; only FUTURE submits with this id fail."""
        return self._prefixes.pop(prefix_id, None) is not None

    def prefix_len(self, prefix_id: int) -> int:
        return self._prefixes[prefix_id]["len"]

    def export_row(self, rid: int, block_tokens: int = 16
                   ) -> Dict[str, Any]:
        """Export a decode-active row as a host pytree — its grid KV up
        to the row's depth, its row-state leaves whole (``row_state``:
        what a decoder keeps a row whatever its depth, a recurrent state;
        absent for a decoder that keeps none), plus everything needed to
        resume the request
        elsewhere/later (sampler params, penalty window, emitted tokens,
        stop sequences). The serving engine's session-park path publishes
        this tree through the store codec (``serving/kvpool.py``).

        KV ships as PER-BLOCK leaves (``block_tokens`` positions each,
        depth padded up to a block boundary): under a delta-manifest
        publish a RE-park of a grown conversation ships only its new
        blocks, and the block-rounded depth keeps :meth:`import_row`'s
        splice to O(few) compiled shapes. On the int8 grid the exported
        planes are the grid's ``(q, scale)`` pairs verbatim — restoring
        them is bit-exact. A prefixed row exports its SPLICED prefix
        rows too (depth includes the prefix), so the state is
        self-contained: restore needs no prefix registered.

        Speculative rows export their round-carried state too — the
        device draft context (``spec_ctx``, stale-tail-zeroed like the
        KV planes), the carried next token, and the row's adaptive
        lookahead ``k`` + acceptance EMA — so a parked spec session
        resumes mid-generation with its drafts still landing (greedy
        resumes stay token-identical: the carried token IS the next
        emission).

        Deliberately scoped: queued / mid-chunked-prefill rows raise
        (their logits aren't seeded yet — park after the first
        chunk)."""
        slot = None
        for s, req in self._slots.items():
            if req.rid == rid:
                slot = s
                break
        if slot is None:
            raise KeyError(
                f"rid {rid} is not decode-active (queued and "
                f"mid-prefill rows cannot export)")
        from kubetorch_tpu.serving.kvpool import padded_blocks

        req = self._slots[slot]
        bt = max(1, int(block_tokens))
        dpos = int(np.asarray(self._dpos[slot]))
        dend = padded_blocks(dpos, bt, self.max_len) * bt
        if dend > self.max_len:
            # the grid tail is not block-aligned: fall back to whole
            # blocks only, which must still cover the row's depth
            dend = (self.max_len // bt) * bt
            if dpos > dend:
                raise ValueError(
                    f"cannot export a depth-{dpos} row in {bt}-token "
                    f"blocks on a max_len-{self.max_len} grid — pick a "
                    f"KT_KV_BLOCK_TOKENS that divides max_len")
        kv: Dict[str, Dict[str, np.ndarray]] = {}
        for kk in self.cache:
            if kk in self._off_grid:
                continue
            plane = np.array(self.cache[kk][:, slot, :dend])
            # ZERO the block-padded tail beyond the row's depth: freed
            # rows never clear their cache planes (attention masks them
            # out), so positions >= dpos still hold the slot's PREVIOUS
            # occupant's K/V — exporting them would publish another
            # session's data to the store. Zeroing also keeps the pad
            # blocks byte-stable for the delta manifest.
            plane[:, dpos:] = 0
            kv[kk] = {f"{b:05d}": plane[:, b * bt:(b + 1) * bt]
                      for b in range(dend // bt)}
        stop_flat = [t for seq in req.stop for t in seq]
        state = {
            "kv": kv,
            "logits": np.asarray(self._logits[slot]),
            "win": np.asarray(self._win[slot]),
            "sampler": np.asarray(
                [req.temperature, req.repetition_penalty], np.float32),
            "prompt": np.asarray(req.prompt, np.int64),
            "tokens": np.asarray(req.tokens, np.int64),
            "stop_flat": np.asarray(stop_flat, np.int64),
            "stop_lens": np.asarray([len(s) for s in req.stop],
                                    np.int64),
            # [ctx_tokens, emitted, max_new_tokens, ...] — the first
            # three are the engine-agnostic header kvpool.state_summary
            # reads; the rest are this engine's own
            "scalars": np.asarray(
                [dpos, len(req.tokens), req.max_new_tokens,
                 req.adapter_id, int(self.kv_quantized), bt],
                np.int64),
            # grid geometry the row was exported under — import_row on
            # another engine refuses typed when any axis differs
            # (cross-tier handoff must never splice into a mismatched
            # grid): [block_tokens, max_len, lora_slots]
            "geom": np.asarray([bt, self.max_len, self.n_adapters],
                               np.int64),
        }
        if self._row_leaves:
            # what the row holds whatever its depth (a recurrent state, a
            # convolution's tail), each leaf whole: ``[L, *shape]``. No
            # block structure and no stale tail: it is all the row's own.
            state["row_state"] = {
                kk: np.array(self.cache[kk][:, slot])
                for kk in sorted(self._row_leaves)}
        if self._ring_leaves:
            # a ring whole, ``[L, span, *shape]``, as it lies: it means
            # what it means beside the depth it was written to (``scalars``
            # carries it: position p sits at p % span). Slots the row has
            # not reached still hold the slot's previous occupant: zeroed,
            # as the planes' tails are.
            state["rings"] = {}
            for kk, span in sorted(self._ring_leaves.items()):
                ring = np.array(self.cache[kk][:, slot])
                ring[:, min(dpos, span):] = 0
                state["rings"][kk] = ring
        if self.spec:
            # round-carried speculation state. The draft haystack ships
            # explicitly (a prefixed row's prefix tokens live only on
            # device) at the same block-padded depth as the KV, with
            # the tail past dpos ZEROED — freed slots keep their ctx
            # rows, so an un-zeroed export would publish the previous
            # occupant's tokens (the same cross-tenant hygiene as the
            # KV planes) and break the delta manifest's byte stability.
            ctx_row = np.array(self._ctx[slot, :dend], np.int32)
            ctx_row[dpos:] = 0
            st = self._spec_state.get(slot) or LookaheadState(
                self.spec_k, self.spec_cap)
            state["spec_ctx"] = ctx_row
            state["spec"] = np.asarray(
                [int(np.asarray(self._dnt[slot])),
                 int(bool(np.asarray(self._dnt_valid[slot]))),
                 st.k], np.int64)
            state["spec_ema"] = np.asarray([st.ema], np.float32)
        return state

    def _check_geometry(self, state: Dict[str, Any],
                        expect_block_tokens: "int | None") -> None:
        """Typed cross-geometry guard: an exported row names the grid
        geometry it left (``geom`` leaf: block size, max_len, LoRA
        slot-axis width); importing into an engine that differs on ANY
        axis raises :class:`KVGeometryMismatch` naming both geometries
        instead of splicing corrupt state. States without the leaf
        (pre-geometry exports) keep the legacy shape-fit checks only.
        Row-state leaves are held to the same guard: the state's
        ``row_state`` must hold exactly this grid's, each of this grid's
        ``[L, *shape]``; and so are rings (``rings``: each of this grid's
        ``[L, span, *shape]``: a ring of another span holds other
        positions in other slots)."""
        from kubetorch_tpu.exceptions import KVGeometryMismatch

        for axis, key, sort, names, what in (
                ("row_state", "row_state", "row-state", self._row_leaves,
                 "a row's recurrent state is whole or it is nothing"),
                ("ring", "rings", "ring", self._ring_leaves,
                 "a ring is read modulo its own span")):
            exported = {kk: tuple(np.shape(v))
                        for kk, v in (state.get(key) or {}).items()}
            importer = {kk: (self.cache[kk].shape[:1]
                             + self.cache[kk].shape[2:]) for kk in names}
            if exported != importer:
                raise KVGeometryMismatch(
                    f"cannot import row: exported {sort} leaves {exported} "
                    f"do not match the importing engine's {importer} "
                    f"({what})",
                    axis=axis, exported=exported, importer=importer)
        geom = state.get("geom")
        if geom is None:
            return

        g = [int(x) for x in np.asarray(geom).reshape(-1)]
        exported = {"block_tokens": g[0], "max_len": g[1],
                    "lora_slots": g[2] if len(g) > 2 else 0}
        importer = {"block_tokens": (int(expect_block_tokens)
                                     if expect_block_tokens else g[0]),
                    "max_len": int(self.max_len),
                    "lora_slots": int(self.n_adapters)}
        for axis in ("block_tokens", "max_len", "lora_slots"):
            if exported[axis] != importer[axis]:
                raise KVGeometryMismatch(
                    f"cannot import row: exported geometry "
                    f"(block_tokens={exported['block_tokens']}, "
                    f"max_len={exported['max_len']}, "
                    f"lora_slots={exported['lora_slots']}) does not "
                    f"match importing engine geometry "
                    f"(block_tokens={importer['block_tokens']}, "
                    f"max_len={importer['max_len']}, "
                    f"lora_slots={importer['lora_slots']}): "
                    f"{axis} mismatch",
                    axis=axis, exported=exported, importer=importer)

    def import_row(self, state: Dict[str, Any],
                   block_tokens: "int | None" = None) -> int:
        """Splice an exported row into a free slot of THIS engine and
        resume decoding it — the restore half of :meth:`export_row`
        (same grid geometry required: layer/head/dim AND ``kv_dtype``
        must match, depth must fit ``max_len``).

        The splice writes the row's KV at positions ``[0, depth)`` with
        one ``.at[].set`` per positional plane — a fresh compile per
        distinct block-rounded depth, which the block rounding keeps to a
        handful of shapes — and each row-state leaf whole at the row.
        Returns the NEW rid (rids are engine-local). Sampler
        RNG is engine-global and not part of the export: greedy resumes
        are token-identical to an uninterrupted run; sampled resumes are
        distribution-correct but draw a fresh key sequence.

        Speculation: a spec engine restores a spec export's draft
        context + carried token + lookahead/EMA verbatim (the row keeps
        drafting where it left off), and accepts a PLAIN export too —
        the haystack rebuilds from prompt+tokens (a prefixed export's
        prefix tokens are absent, which only costs draft quality, never
        correctness) and the first token reads from the exported
        logits. A plain engine importing a spec export raises: the spec
        row's next token lives in the carried-token state, not in its
        (admission-stale) logits."""
        if "spec" in state and not self.spec:
            raise ValueError(
                "state was exported from a speculative engine — its "
                "next token is round-carried draft state a plain "
                "engine cannot resume; import into a spec_k > 1 engine")
        self._check_geometry(state, block_tokens)
        if not self._free:
            raise RuntimeError("no free row to import into")
        positional = set(self.cache) - self._off_grid
        if set(state["kv"]) != positional:
            raise ValueError(
                f"KV planes {sorted(state['kv'])} do not match this "
                f"grid's {sorted(positional)} — kv_dtype mismatch "
                f"between export and import engines")
        scalars = [int(x) for x in np.asarray(state["scalars"])]
        dpos, n_emitted, max_new = scalars[0], scalars[1], scalars[2]
        adapter_id = scalars[3] if len(scalars) > 3 else -1
        self._check_adapter_id(adapter_id)
        planes = {
            kk: np.concatenate(
                [np.asarray(blocks[b]) for b in sorted(blocks)], axis=1)
            for kk, blocks in state["kv"].items()}
        dend = next(iter(planes.values())).shape[1]
        for kk, plane in planes.items():
            grid = self.cache[kk].shape
            if dend > self.max_len or plane.shape[0] != grid[0] or \
                    plane.shape[1] != dend or plane.shape[2:] != grid[3:]:
                raise ValueError(
                    f"imported KV shape {plane.shape} does not fit "
                    f"grid {grid} (max_len {self.max_len})")
        margin = self.steps_per_call * (self.spec_k if self.spec else 1)
        if dpos + (max_new - n_emitted) + margin > self.max_len:
            raise ValueError(
                f"restored depth {dpos} + remaining budget "
                f"{max_new - n_emitted} + chunk margin {margin} exceeds "
                f"max_len {self.max_len}")
        slot = self._free.pop(0)
        with self._mesh_ctx():
            for kk in planes:
                self.cache[kk] = self.cache[kk].at[:, slot, :dend].set(
                    jnp.asarray(planes[kk]).astype(self.cache[kk].dtype))
            for key, names in (("row_state", self._row_leaves),
                               ("rings", self._ring_leaves)):
                for kk in names:
                    self.cache[kk] = self.cache[kk].at[:, slot].set(
                        jnp.asarray(state[key][kk]).astype(
                            self.cache[kk].dtype))
            self._logits = self._logits.at[slot].set(
                jnp.asarray(np.asarray(state["logits"], np.float32)))
            self._dpos = self._dpos.at[slot].set(dpos)
            self._dactive = self._dactive.at[slot].set(True)
        temp, penalty = (float(x) for x in np.asarray(state["sampler"]))
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, [int(t) for t in np.asarray(state["prompt"])],
                      max_new, temp)
        req.tokens = [int(t) for t in np.asarray(state["tokens"])]
        req.consumed = len(req.prompt)
        req.repetition_penalty = penalty
        req.adapter_id = adapter_id
        stop_flat = [int(t) for t in np.asarray(state["stop_flat"])]
        stops, at = [], 0
        for n in (int(x) for x in np.asarray(state["stop_lens"])):
            stops.append(stop_flat[at:at + n])
            at += n
        req.stop = stops
        req.slot = slot
        self._temps[slot] = temp
        self._penalties[slot] = penalty
        self._win[slot] = np.asarray(state["win"], np.int32)
        self._slot_adapter[slot] = adapter_id
        self._slots[slot] = req
        self._depth[slot] = dpos
        if self.spec:
            Lctx = self._ctx.shape[1]
            ctx_row = np.zeros(Lctx, np.int32)
            if "spec" in state:
                sc = np.asarray(state["spec_ctx"], np.int32)
                ctx_row[:min(len(sc), Lctx)] = sc[:Lctx]
                dnt, dnt_ok, k0 = (int(x)
                                   for x in np.asarray(state["spec"]))
                ema0 = float(np.asarray(state["spec_ema"]).reshape(-1)[0])
            else:
                # plain export: rebuild the haystack grid-aligned to
                # end at the row's depth (prefix tokens, if any, stay
                # absent — draft quality only). dnt_ok = 0 routes the
                # first token through the exported (fresh) logits.
                seq = req.prompt + req.tokens
                place = seq[-min(len(seq), dpos):] if seq else []
                start = dpos - len(place)
                ctx_row[start:start + len(place)] = place
                dnt, dnt_ok, k0, ema0 = 0, 0, 0, 1.0
            with self._mesh_ctx():
                self._ctx = self._ctx.at[slot].set(jnp.asarray(ctx_row))
                self._dnt = self._dnt.at[slot].set(jnp.int32(dnt))
                self._dnt_valid = self._dnt_valid.at[slot].set(
                    bool(dnt_ok))
            st = LookaheadState(self.spec_k, self.spec_cap,
                                k0=k0 or None, ema0=ema0)
            self._spec_state[slot] = st
        return rid

    def warmup(self, prompt_buckets=(16, 64, 128),
               sampling: bool = False) -> None:
        """Compile the serving shapes up front: the decode chunk plus both
        admission widths for each prompt bucket. Call before taking
        traffic — a cold (bucket, width) pair compiles mid-request
        otherwise (tens of seconds on a cold compile cache).

        ``sampling=True`` on a speculative engine also compiles the
        SAMPLING decode executable (the sticky upgrade the first
        ``temperature > 0`` request would otherwise trigger
        mid-traffic); plain engines bake sampling into the one
        executable, so the flag is a no-op there."""
        temp = 1.0 if sampling and self.spec else 0.0
        # warmup's garbage drafts must not leak into the acceptance
        # accounting: accept_rate / tokens_per_pass feed the serving
        # scheduler's shed pricing and the published engine_spec_*
        # counters (the same skew class PR 10 fixed for the
        # prefix-savings ratio) — restore the counters afterwards
        spec_counts = ((self._spec_rounds, self._spec_emitted,
                        self._spec_drafted) if self.spec else None)
        try:
            for p_pad in sorted(set(_bucket(b) for b in prompt_buckets)):
                for width in sorted({1, self.max_slots}):
                    for _ in range(width):
                        self.submit([1] * min(p_pad, self.max_len // 2),
                                    max_new_tokens=1, temperature=temp)
                    self.run()
            if self.spec:
                # compile every adaptive dispatch width ({1, 2, 4, ...,
                # spec_k}): per-row adaptation reaches them mid-traffic
                # otherwise, paying a cold compile each
                widths, w = [], 1
                while w < self.spec_k:
                    widths.append(w)
                    w *= 2
                widths.append(self.spec_k)
                for w in widths:
                    self.submit([1, 2], max_new_tokens=1,
                                temperature=temp)
                    self.admit()
                    for st in self._spec_state.values():
                        st.k = min(w, self.spec_k)
                    self.run()
        finally:
            if spec_counts is not None:
                (self._spec_rounds, self._spec_emitted,
                 self._spec_drafted) = spec_counts

    # ----------------------------------------------------------- interns
    def _start_chunked(self, req: Request) -> None:
        """Claim the row for a chunked prefill. No dispatch here: the
        row's ``dpos`` is already 0 and its row-state leaves zero (rows
        reset on free/evict) and its grid rows are rewritten from position
        0 by the chunk forwards.
        Only the slot's adapter index must be live during prefill — the
        chunk forwards run under it."""
        req.consumed = 0
        self._slot_adapter[req.slot] = req.adapter_id
        self._prefilling[req.slot] = req
        self.prefill_tokens += len(req.prompt)
        self._count_prefill(len(req.prompt))

    def _admit_group(self, group: List[Request], p_pad: int,
                     prefix_id: Optional[int] = None):
        """Prefill N same-(bucket, prefix) requests in one call. N pads
        to one of two widths (dummy rows target slot ``max_slots``, which
        lands nothing) so compile count stays O(buckets)."""
        n = len(group)
        # two admission shapes only (single vs full-width) — prefill FLOPs
        # on dummy rows are cheap; compiles are not
        n_pad = 1 if n == 1 else self.admit_width
        toks = np.zeros((n_pad, p_pad), np.int32)
        lens = np.ones(n_pad, np.int32)
        slots = np.full(n_pad, self.max_slots, np.int32)  # OOB → dropped
        idx = np.full(n_pad, -1, np.int32)
        # the admitted rows' own sampler inputs: the admission draws each
        # row's first token (a dummy row: greedy, no penalty, empty window)
        temps = np.zeros(n_pad, np.float32)
        penalties = np.ones(n_pad, np.float32)
        win = np.full((n_pad, self._win.shape[1]), -1, np.int32)
        head = (self._prefixes[prefix_id]["len"] if prefix_id is not None
                else 0)
        for i, req in enumerate(group):
            toks[i, :len(req.prompt)] = req.prompt
            lens[i] = len(req.prompt)
            slots[i] = req.slot
            aid = getattr(req, "adapter_id", -1)
            idx[i] = aid
            self._slot_adapter[req.slot] = aid
            self._seat(req, head + len(req.prompt))
            temps[i] = req.temperature
            penalties[i] = req.repetition_penalty
            win[i] = self._win[req.slot]
            self.prefill_tokens += len(req.prompt)
            self._count_prefill(len(req.prompt))
        self._count_scan(n_pad, p_pad, sum(len(r.prompt) for r in group))
        key = self._draw_key()
        with self._mesh_ctx():
            self._count_admission(n_pad, p_pad, own=prefix_id is None)
            self._count_experts(lens, p_pad)
            state = (self.cache, self._logits, self._dpos, self._dactive,
                     self._dnt, self._dnt_valid)
            # host arrays go in as they are: the call uploads them itself,
            # which costs a fraction of an upload each made beforehand
            rows_in = (toks, lens, slots, temps, penalties, win, key,
                       self._lora(idx))
            if prefix_id is None:
                self._count_admit(n, slots, p_pad)
                out = self._dispatch(
                    "prefill", (n_pad, p_pad), self._prefill,
                    self.params, *state, *rows_in, p_pad=p_pad)
            else:
                pfx = self._prefixes[prefix_id]
                # the own cache there: the prefix's bucket and the
                # suffix's, cut at the grid's end (``_prefill_px_impl``)
                self._count_admit(n, slots, min(
                    grid_dims(pfx["planes"], self._off_grid)[1] + p_pad,
                    self.max_len))
                out = self._dispatch(
                    "prefill_px", (n_pad, p_pad), self._prefill_px,
                    self.params, *state, pfx["planes"],
                    jnp.int32(pfx["len"]), *rows_in, p_pad=p_pad)
            (self.cache, self._logits, self._dpos, self._dactive,
             self._dnt, self._dnt_valid, first) = out
        for i, req in enumerate(group):
            self._first_pending[req.slot] = (first, i)
        if self.spec:
            # seed the draft haystack: the full token context (shared
            # prefix + prompt) per admitted slot. One extra tiny
            # dispatch per admission wave — the hot path (the decode
            # chunk) stays one dispatch.
            rows = np.zeros((n_pad, self._ctx.shape[1]), np.int32)
            head_toks = (self._prefixes[prefix_id]["tokens"]
                         if prefix_id is not None else [])
            for i, req in enumerate(group):
                seq = head_toks + req.prompt
                rows[i, :len(seq)] = seq
            self._seed_drafts(group, rows, slots)

    def _seat(self, req: Request, depth: int) -> None:
        """The host half of an admission, before its dispatch: the row's
        sampler inputs and penalty window (the prompt's tail), its place
        among the decoding rows and its depth's mirror."""
        slot = req.slot
        self._temps[slot] = req.temperature
        self._penalties[slot] = req.repetition_penalty
        tail = req.prompt[-self._win.shape[1]:]
        self._win[slot] = -1
        if req.repetition_penalty != 1.0 and tail:
            self._win[slot, -len(tail):] = tail
        self._slots[slot] = req
        self._depth[slot] = depth
        self._admissions["admitted"] += 1

    def _seed_drafts(self, reqs: List[Request], rows, slots) -> None:
        """Speculation's share of an admission: each row's token context
        into the draft matcher's haystack (one ``ctx_admit`` dispatch a
        wave) and a fresh lookahead state."""
        for req in reqs:
            self._spec_state[req.slot] = LookaheadState(
                self.spec_k, self.spec_cap)
        with self._mesh_ctx():
            self._ctx = self._dispatch(
                "ctx_admit", len(slots), self._ctx_admit,
                self._ctx, rows, slots)

    def _draw_key(self):
        """Key data for the next sampling dispatch (``_fold`` makes the key
        of it on the device): the seed's own key with the dispatch's number
        in its first word, which a seed of 32 bits leaves 0. Distinct
        dispatches hold distinct keys; no key is ever split off another,
        which would take a dispatch of its own."""
        self._draws += 1
        data = self._key_data.copy()
        data[0] = self._draws & 0xFFFFFFFF
        return data

    def _lora(self, slots_np):
        """None when no adapters — the hot path must not pay a
        host->device index upload it would discard."""
        if self.adapters is None:
            return None
        return {"adapters": self.adapters,
                "slots": jnp.asarray(slots_np, dtype=jnp.int32),
                "scale": float(self.adapter_scale)}

    def _mesh_ctx(self):
        return (jax.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def _first_events(self):
        """Read the first tokens that admissions have drawn since the last
        decode chunk and send each on as a one-token frame. Called BEHIND
        the dispatch of the chunk that follows the admissions: the read
        returns when the last prefill ends, with the chunk queued behind
        it, so the device never waits for this host round trip. Trimming
        (budget, eos, stop) is any frame's: a request of one token
        finishes here and frees its row.

        Returns what ``_chunk_events`` needs to end the chunk in flight,
        None where no admission preceded it: the slots whose step-0 token
        has now gone out and, driven by hand (no ``first_frames``), the
        events made here with the rows' order, so that the token can head
        its row's list of the chunk as it always did."""
        pending, self._first_pending = self._first_pending, {}
        # a row that left between its admission and this chunk (an export,
        # an eviction) was dropped from ``pending`` when its row was freed
        if not pending:
            return None
        with self.tick_phase("first_sync"):
            new = {slot: [int(np.asarray(first)[i])]
                   for slot, (first, i) in pending.items()}
        with self.tick_phase("route"):
            order = {req.rid: n for n, req in enumerate(self._slots.values())}
            self._admissions["first_tokens_at_admit"] += len(new)
            events = self._finish_events(new)
            if self.first_frames is not None:
                self.first_frames(events)
                events = []
        return list(new), events, order

    def _chunk_events(self, new_by_slot: Dict[int, List[int]], first):
        """The events of a chunk whose tokens are ``new_by_slot``, after
        ``first`` (``_first_events``): a row whose first token has gone out
        drops it from the chunk's (step 0 took it as given)."""
        if first is None:
            return self._finish_events(new_by_slot)
        sent, early, order = first
        for slot in sent:
            if slot in new_by_slot:            # not finished on that token
                del new_by_slot[slot][:1]
        events = self._finish_events(new_by_slot)
        if not early:
            return events
        rest = {rid: (toks, done) for rid, toks, done in events}
        out = []
        for rid, toks, done in early:
            if not done:
                more, done = rest.pop(rid)
                toks = toks + more
            out.append((rid, toks, done))
        out.extend((rid,) + rest[rid] for rid in rest)
        return sorted(out, key=lambda ev: order[ev[0]])

    def _decode_chunk(self) -> List[Tuple[int, List[int], bool]]:
        with self.tick_phase("decode_dispatch"):
            self._count_kv_read()
            self._count_state_rows(self.steps_per_call)
            self._count_merge(
                np.full(len(self._slots), self.steps_per_call),
                self.steps_per_call)
            self._depth[list(self._slots)] += self.steps_per_call
            key = self._draw_key()
            with self._mesh_ctx():
                (self.cache, self._logits, self._dpos, self._dnt_valid,
                 toks) = self._dispatch(
                    "decode", self.steps_per_call, self._decode,
                    self.params, self.cache, self._logits, self._dpos,
                    self._dactive, self._dnt, self._dnt_valid,
                    self._temps, self._penalties, self._win,
                    key, self._lora(self._slot_adapter),
                    top_k=self.top_k, top_p=self.top_p,
                    n_steps=self.steps_per_call)
        first = self._first_events()
        with self.tick_phase("decode_sync"):
            toks = np.asarray(toks)                   # [K, B] — the one sync
        with self.tick_phase("route"):
            # the decoder's counters came as rows under the tokens
            for i, name in enumerate(self.model.counters):
                self._model_counts[name] += int(
                    toks[self.steps_per_call + i, 0])
            toks = toks[:self.steps_per_call]
            # roll the host-side penalty windows by this chunk's tokens
            K = toks.shape[0]
            W = self._win.shape[1]
            if K >= W:
                self._win[:] = toks[-W:].T
            else:
                self._win[:, :-K] = self._win[:, K:]
                self._win[:, -K:] = toks.T
            return self._chunk_events(
                {slot: [int(t) for t in toks[:, slot]]
                 for slot in self._slots}, first)

    def _decode_spec_chunk(self) -> List[Tuple[int, List[int], bool]]:
        """One dispatch = ``steps_per_call`` verify rounds; each round
        emits 1..k_row tokens per slot (the accepted draft prefix plus
        the model's own next token).

        Per-row adaptive lookahead: each slot runs at its OWN ``k``
        (``LookaheadState``). The dispatch width is the power-of-two
        covering the widest active row (a handful of executables total:
        {1, 2, 4, ..., spec_k} × sampling flag) and the per-slot ``kk``
        array masks draft positions past each row's lookahead inside
        the shared forward — rows at different ``k`` coexist in one
        chunk-mode dispatch, and an all-collapsed batch (every row at
        k = 1) dispatches the width-1 forward, i.e. plain decode."""
        with self.tick_phase("decode_dispatch"):
            # STICKY sampling flag: the first sampled request upgrades the
            # dispatch to the sampling executable and it stays there rather
            # than flapping between the greedy and sampling executables per
            # occupancy mix
            if not self._spec_sampling and any(
                    self._slots[s].temperature > 0 for s in self._slots):
                self._spec_sampling = True
            kk = np.ones(self.max_slots, np.int32)
            for slot in self._slots:
                st = self._spec_state.get(slot)
                if st is None:      # imported/hand-driven rows late-create
                    st = self._spec_state[slot] = LookaheadState(
                        self.spec_k, self.spec_cap)
                kk[slot] = st.k
            k_widest = max((int(kk[s]) for s in self._slots), default=1)
            kd = 1
            while kd < k_widest:
                kd *= 2
            kd = max(1, min(kd, self.spec_k))
            self._count_kv_read()
            key = self._draw_key()
            with self._mesh_ctx():
                (self.cache, self._dpos, self._ctx, self._dnt,
                 self._dnt_valid, toks, emits) = self._dispatch(
                    "decode_spec", (kd, self._spec_sampling), self._decode_sp,
                    self.params, self.cache, self._logits, self._dpos,
                    self._dactive, self._ctx, self._dnt, self._dnt_valid,
                    self._temps, kk, key,
                    self._lora(self._slot_adapter),
                    k=kd, ngram=self.spec_ngram,
                    n_rounds=self.steps_per_call,
                    top_k=self.top_k, top_p=self.top_p,
                    sampling=self._spec_sampling)
        first = self._first_events()
        with self.tick_phase("decode_sync"):
            toks = np.asarray(toks)            # [R, B, kd] — the one sync
            emits = np.asarray(emits)          # [R, B]
        with self.tick_phase("route"):
            R = toks.shape[0]
            self._count_merge(emits, kd)       # one merge a round
            new_by_slot: Dict[int, List[int]] = {}
            for slot in self._slots:
                new: List[int] = []
                for r in range(R):
                    e = int(emits[r, slot])
                    if e:
                        new.extend(int(t) for t in toks[r, slot, :e])
                new_by_slot[slot] = new
                self._depth[slot] += int(emits[:, slot].sum())
                self._spec_rounds += R
                self._spec_emitted += len(new)
                # fold this chunk's acceptance into the row's EMA, then one
                # adaptation move (grow/shrink/probe) for the next chunk
                st = self._spec_state[slot]
                k_used = int(kk[slot])
                self._spec_drafted += R * (k_used - 1)
                for r in range(R):
                    st.observe(int(emits[r, slot]), k_used,
                               alpha=self.spec_ema_alpha)
                st.adapt(self.spec_k, self.spec_cap)
            return self._chunk_events(new_by_slot, first)

    def _finish_events(self, new_by_slot: Dict[int, List[int]]
                       ) -> List[Tuple[int, List[int], bool]]:
        """Trim each given slot's freshly decoded tokens to its budget /
        eos / stop sequences, emit (rid, tokens, done) events, and free
        the slots that finished."""
        events: List[Tuple[int, List[int], bool]] = []
        freed: List[int] = []
        for slot, new in new_by_slot.items():
            req = self._slots[slot]
            # trim to budget; cut at eos
            room = req.max_new_tokens - len(req.tokens)
            new = new[:room]
            if self.eos_id is not None and self.eos_id in new:
                new = new[: new.index(self.eos_id) + 1]
            prev_len = len(req.tokens)
            req.tokens.extend(new)
            stopped = False
            if req.stop:
                cut = req.match_stop()
                if cut is not None:
                    req.tokens = req.tokens[:cut]
                    new = req.tokens[prev_len:]
                    stopped = True
            done = (stopped
                    or len(req.tokens) >= req.max_new_tokens
                    or (self.eos_id is not None
                        and bool(new) and new[-1] == self.eos_id))
            events.append((req.rid, new, done))
            if done:
                req.done = True
                del self._slots[slot]
                freed.append(slot)
        if freed:
            self._free_rows(freed)
        return events

    def _free_rows(self, freed: List[int]) -> None:
        """Release rows back to the free pool (finish or evict).

        FIXED-shape mask update, never a variable-length index
        scatter: `.at[freed].set` compiles a fresh executable per
        distinct len(freed), and speculative drains (scattered finish
        times) reach a new freed-count mid-traffic."""
        mask = np.zeros(self.max_slots, bool)
        mask[freed] = True
        mask = jnp.asarray(mask)
        self._dactive = jnp.where(mask, False, self._dactive)
        self._dpos = jnp.where(mask, 0, self._dpos)
        # a token the row's admission drew and no chunk took (the row left
        # before it decoded) must not be the next occupant's
        self._dnt_valid = jnp.where(mask, False, self._dnt_valid)
        # a row-state leaf has no depth to mask a stale row by: a freed row
        # goes back to a sequence's start, which is what a chunked prefill
        # begins from (a bucketed admission splices the whole row anyway).
        # A ring needs none of this: it is read to min(depth, span) as a
        # plane is read to its depth, and the depth is 0 from here on
        for kk in self._row_leaves:
            leaf = self.cache[kk]
            self.cache[kk] = jnp.where(
                mask.reshape((1, -1) + (1,) * (leaf.ndim - 2)),
                jnp.zeros((), leaf.dtype), leaf)
        self._slot_adapter[freed] = -1
        self._depth[freed] = 0
        for slot in freed:
            self._win[slot] = -1
            self._penalties[slot] = 1.0
            self._first_pending.pop(slot, None)
            if self.spec:
                self._spec_state.pop(slot, None)
        self._free.extend(freed)

    # ------------------------------------------------------------- jitted
    @staticmethod
    def _prefill_impl(params, cache, logits, dpos, dactive, dnt, dnt_valid,
                      tokens, prompt_lens, slots, temps, penalties, window,
                      key, lora, *, p_pad, top_k, top_p, cfg, rules):
        """Prefill N slots at once: one forward over a private N-row
        cache, then land the rows in the shared grid at ``slots``
        (out-of-range dummy rows land nothing) and draw each row's first
        token (``_finish_admit``; ``temps`` / ``penalties`` / ``window``
        are the N rows' own sampler inputs, ``key`` a ``_draw_key()``).

        The private cache covers only the ``p_pad`` rows prefill writes —
        full-``M`` would be a second multi-GB grid live beside the real
        one (4 GB transient at 8B serving scale). Likewise the forward
        unembeds at the last real token only (``unembed_positions``):
        [N, P, V] float32 logits are 7 GB at N=112, V=128k."""
        N = tokens.shape[0]
        positions = jnp.broadcast_to(jnp.arange(p_pad)[None, :], (N, p_pad))
        m = jnp.arange(p_pad)[None, None, :]
        t = positions[:, :, None]
        mask = (m <= t) & (m < prompt_lens[:, None, None])
        model = decoder_for(cfg)
        own = model.init_cache_like(cfg, cache, N, p_pad)
        out, own, _ = model.forward_cached(
            params, tokens, positions, own, 0, mask, cfg, rules,
            unembed_positions=prompt_lens - 1, lora=lora,
            causal_lens=prompt_lens)
        return RollingGenerator._finish_admit(
            cache, own, out[:, 0], logits, dpos, dactive, dnt, dnt_valid,
            slots, prompt_lens,
            draw_tokens(out[:, 0], temps, penalties, window, _fold(key),
                        top_k, top_p))

    @staticmethod
    def _finish_admit(cache, own, last, logits, dpos, dactive, dnt,
                      dnt_valid, slots, new_pos, first):
        """Land own-cache rows in the grid and update per-slot state.

        Row ``n`` of ``own`` goes to grid row ``slots[n]`` by slice update
        (``grid_write.write_rows``: why that is neither a scatter nor a
        select over the grid, its module's docstring); a dummy row's slot
        is out of range and nothing of it lands. ``own`` spans positions
        [0, M_own) of the grid's M axis — prefill always writes from
        position 0 (prefixed admission broadcasts the prefix into the
        own-cache first), so the row takes that span whole, pad positions
        past its depth included, and the rest of the grid row stays. A
        row-state leaf (``[L, B, *shape]``, no position axis) takes the
        own-cache's whole, which the prefill left at the row's last real
        token. ``last``: [N, V] logits at each row's final real token;
        ``first``: [N] the token drawn from them, each row's first. It
        becomes the row's carried token (step 0 of the row's first decode
        chunk takes it as given) and is returned as the last output, a
        small array of its own: the host reads it when this executable
        ends, whatever is queued behind it.
        """
        cache = grid_write.write_rows(cache, own, slots)
        logits = logits.at[slots].set(last, mode="drop")
        dpos = dpos.at[slots].set(new_pos, mode="drop")
        dactive = dactive.at[slots].set(True, mode="drop")
        dnt = dnt.at[slots].set(first, mode="drop")
        dnt_valid = dnt_valid.at[slots].set(True, mode="drop")
        return cache, logits, dpos, dactive, dnt, dnt_valid, first

    @staticmethod
    def _prefix_fill_impl(params, tokens, prefix_len, lora, *, p_pad, cfg,
                          rules, quantized=False):
        """Forward a shared prefix once → its KV planes + last logits.

        On the int8 grid the private cache is quantized, so the stored
        block carries int8 values + per-vector scale planes written by
        the exact same path admission prefills use and splices straight
        into the grid (this forward runs at the prefix's own padded
        width, so low bits can differ from a full-prompt admission).
        ``lora``: adapter-bound prefixes forward under the owning
        adapter's slot index."""
        positions = jnp.arange(p_pad)[None, :]
        m = jnp.arange(p_pad)[None, None, :]
        mask = (m <= positions[:, :, None]) & (m < prefix_len)
        model = decoder_for(cfg)
        own = model.init_cache(cfg, 1, p_pad, quantized=quantized)
        out, own, _ = model.forward_cached(
            params, tokens, positions, own, 0, mask, cfg, rules,
            unembed_positions=(prefix_len - 1)[None], lora=lora)
        return own, out[0, 0]

    @staticmethod
    def _prefill_px_impl(params, cache, logits, dpos, dactive, dnt,
                         dnt_valid, planes, prefix_len, tokens, prompt_lens,
                         slots, temps, penalties, window, key, lora, *,
                         p_pad, top_k, top_p, cfg, rules):
        """Prefill N suffixes on top of a shared, already-computed prefix:
        the prefix KV block is broadcast into each slot's rows [0, Ppad)
        and only the suffix runs through the model (vLLM prefix caching at
        slot granularity). Suffix rows write at ``prefix_len``, so the
        layout stays contiguous and any prefix-pad garbage lives beyond
        every future ``pos`` — never attended.

        ``planes``: the stored prefix cache dict — bf16 {k, v} or int8
        {k, v, ks, vs}; quantized planes broadcast into a quantized
        private cache, so the int8 serving grid composes with shared
        prefixes. ``lora``: the suffix forward runs under the prefix's
        owning adapter (submit enforced the match)."""
        model = decoder_for(cfg)
        rows = off_grid_leaves(model, cfg)
        M = grid_dims(cache, rows)[1]
        N = tokens.shape[0]
        Ppad = grid_dims(planes, rows)[1]
        # Rows needed: the prefix block plus the suffix span — suffix rows
        # write at [prefix_len, prefix_len + p_pad) and prefix_len ≤ Ppad.
        # Clamped to the grid's M: a long prefix whose BUCKET plus the
        # suffix bucket overshoots max_len (the real tokens fit — submit()
        # checked) must not build an own-cache wider than the grid it
        # splices into.
        own = model.init_cache_like(cfg, cache, N, min(Ppad + p_pad, M))

        def bcast(plane_own, plane_px):
            shp = (plane_px.shape[0], N) + plane_px.shape[2:]
            return jax.lax.dynamic_update_slice(
                plane_own, jnp.broadcast_to(plane_px, shp)
                .astype(plane_own.dtype), (0,) * plane_own.ndim)

        own = {kk: bcast(own[kk], planes[kk]) for kk in own}
        positions = prefix_len + jnp.broadcast_to(
            jnp.arange(p_pad)[None, :], (N, p_pad))
        m = jnp.arange(grid_dims(own, rows)[1])[None, None, :]
        mask = m <= positions[:, :, None]
        out, own, _ = model.forward_cached(
            params, tokens, positions, own, prefix_len, mask, cfg, rules,
            unembed_positions=prompt_lens - 1, lora=lora)
        return RollingGenerator._finish_admit(
            cache, own, out[:, 0], logits, dpos, dactive, dnt, dnt_valid,
            slots, prefix_len + prompt_lens,
            draw_tokens(out[:, 0], temps, penalties, window, _fold(key),
                        top_k, top_p))

    @staticmethod
    def _prefill_extend_impl(params, cache, logits, dpos, dactive, dnt,
                             dnt_valid, feed, counts, finals, temps,
                             penalties, window, key, lora, *, C, top_k,
                             top_p, cfg, rules):
        """Advance N in-progress chunked prefills by ≤ ``C`` tokens each,
        GRID-RESIDENT: the chunk forward runs at full grid width (rows
        with ``counts == 0`` are masked out and merge nothing), attends
        over each row's already-written grid rows plus the causal chunk,
        and merges the new K/V at each row's depth via the shared row
        loop of slice updates (``merge_chunk_into_grid``: only the
        ``C``-column windows of the rows that prefill are written) — the
        exact write path decode chunks use, so ONE compiled executable
        per ``C`` covers every chunk of every prompt length.

        ``finals`` marks rows whose prompt completes in this chunk:
        their last real token's logits (``unembed_positions`` keeps the
        unembed at one position per row — [B, C, V] float32 would be
        multi-GB at serving scale) seed the decode loop, the row's first
        token is drawn from them as a bucketed admission draws it (the
        row's carried token, and the last output's entry at the row: the
        sampler inputs are the grid's, ``[B]``) and the row
        activates. Rows mid-prompt keep ``dactive`` False — decode
        chunks skip them (zero merge count, no depth advance) while
        this path fills them, which is what lets the serving engine
        interleave prefill chunks between decode chunks without ever
        stalling token emission."""
        B = feed.shape[0]
        model = decoder_for(cfg)
        M = grid_dims(cache, off_grid_leaves(model, cfg))[1]
        live = counts > 0
        positions = dpos[:, None] + jnp.arange(C)[None, :]
        gmask = jnp.broadcast_to(
            (jnp.arange(M)[None, None, :] < dpos[:, None, None])
            & live[:, None, None], (B, C, M))
        # causal within the chunk, clipped to each row's real tokens;
        # queries past count attend only real columns (their outputs are
        # discarded — unembed reads count-1 — and their chunk-cache
        # writes land at columns >= count, which the merge drops)
        emask = ((jnp.arange(C)[None, None, :]
                  <= jnp.arange(C)[None, :, None])
                 & (jnp.arange(C)[None, None, :]
                    < counts[:, None, None]))
        chunk = model.init_chunk(cfg, cache, B, C)
        out, chunk, _ = model.forward_cached(
            params, feed, positions, cache, None, gmask, cfg, rules,
            chunk=chunk, chunk_col=0, chunk_mask=emask,
            unembed_positions=jnp.maximum(counts - 1, 0), lora=lora)
        cache = model.merge_chunk_into_grid(cache, chunk, dpos, counts)
        fin = finals & live
        logits = jnp.where(fin[:, None], out[:, 0], logits)
        first = draw_tokens(out[:, 0], temps, penalties, window,
                            _fold(key), top_k, top_p)
        return (cache, logits, dpos + counts, dactive | fin,
                jnp.where(fin, first, dnt), dnt_valid | fin, first)

    @staticmethod
    def _decode_impl(params, cache, last_logits, pos, active, dnt,
                     dnt_valid, temps, penalties, window, key, lora, *,
                     top_k, top_p, n_steps, cfg, rules):
        """``n_steps`` tokens for every slot, each at its own depth, in one
        ``lax.scan`` — one dispatch, one emitted [K, B] block.

        Deferred cache merge: inside the scan each step's K/V lands at the
        step-index column of a small [L, B, n_steps] *chunk* cache (a
        uniform-offset write, like the static decoder's), and attention
        merges the read-only grid with the chunk. The chunk lands in the
        grid ONCE after the scan, each active row's ``n_steps`` columns at
        its own depth: ``B`` scalar offsets are ``B`` contiguous slice
        updates (``ops/grid_write.py``), which move the chunk's bytes and
        nothing else. (Writing at per-sequence offsets every step, as a
        one-hot select over the whole layer, measured ~2× the whole step
        at 8B serving scale, 38 → ~20 ms/step at B=96; the same select
        once a chunk was still 30% of the decode executable until PR 28.)

        Which attention reads the grid: this is the one chunk-mode caller
        with a single query position, and it hands the grid mask down as
        a length too (``grid_depth``), so on one TPU device the grid half
        runs in the ragged Pallas kernel, which fetches each row's K/V
        only to its depth (``cached_attn_ragged``). On CPU, under
        a tp mesh, or with a ``max_len`` no key block divides, the same
        call runs the einsum pair over all ``max_len`` positions
        (``cached_attn_merged_q`` / ``cached_attn_merged``: all three in
        ``ops/cached_attention.py``), the kernel's oracle. ``stats()``
        counts what was read either way.

        Each step draws with the engine's one sampler (``draw_tokens``:
        ``window`` [B, W] the slots' recent token ids, ``penalties`` [B]).
        The window rolls inside the scan so a token sampled at step k is
        already penalized at step k+1. A row whose admission drew its
        first token (``dnt`` where ``dnt_valid``) takes that token at step
        0 in place of a draw of its own, and the flag is spent: the
        returned ``dnt_valid`` is False for every row that decoded."""
        B = last_logits.shape[0]
        model = decoder_for(cfg)
        M = grid_dims(cache, off_grid_leaves(model, cfg))[1]
        pos0 = pos
        # Grid contents never change during the chunk: rows < pos0 hold
        # every previous token, the current chunk's rows live in the
        # chunk cache. So the grid mask is loop-invariant.
        gmask = ((jnp.arange(M)[None, None, :] < pos0[:, None, None])
                 & active[:, None, None])
        # the same mask as a length: what the ragged kernel reads to
        depth0 = jnp.where(active, pos0, 0)
        chunk0 = model.init_chunk(cfg, cache, B, n_steps)
        # the decoder's counters of a step, summed over the chunk ({} for a
        # decoder that has none: nothing is added to the program)
        counts0 = {name: jnp.zeros((), jnp.int32)
                   for name in model.counters}

        def one(carry, inp):
            chunk, logits, pos, win, counts = carry
            j, step_key = inp
            tok = draw_tokens(logits, temps, penalties, win, step_key,
                              top_k, top_p)
            # step 0 of a row the admission drew for takes that token (it
            # has gone out already; a sampled row must not draw again)
            tok = jnp.where(dnt_valid & (j == 0), dnt, tok)
            win = jnp.concatenate([win[:, 1:], tok[:, None]], axis=1)

            positions = pos[:, None]
            emask = ((jnp.arange(n_steps)[None, None, :] <= j)
                     & active[:, None, None])
            out, chunk, step = model.forward_cached(
                params, tok[:, None], positions, cache, None, gmask, cfg,
                rules, chunk=chunk, chunk_col=j, chunk_mask=emask,
                lora=lora, grid_depth=depth0)
            counts = {name: counts[name] + step[name] for name in counts}
            return (chunk, out[:, 0], pos + 1, win, counts), tok

        (chunk, logits, pos, _, counts), toks = jax.lax.scan(
            one, (chunk0, last_logits, pos, window, counts0),
            (jnp.arange(n_steps), jax.random.split(_fold(key), n_steps)))
        if counts:
            # one row a counter under the tokens, its value in column 0:
            # fetched by the one read that fetches the tokens
            toks = jnp.concatenate(
                [toks, jnp.zeros((len(counts), B), jnp.int32).at[:, 0].set(
                    jnp.stack([counts[name] for name in model.counters]))])

        # Merge the chunk into the grid at each slot's offset — shared
        # row loop of slice updates (llama.merge_chunk_into_grid; see
        # its docstring for why never take_along_axis/scatter). Inactive
        # slots merge nothing (count 0: the loop never visits them) and
        # their depth must not advance either: a row mid-CHUNKED-PREFILL
        # (owned but not yet decoding) rides through decode chunks, and a
        # drifting dpos would land its next prefill chunk past the real
        # prompt.
        new_cache = model.merge_chunk_into_grid(
            cache, chunk, pos0, jnp.where(active, n_steps, 0))
        return (new_cache, logits, jnp.where(active, pos, pos0),
                dnt_valid & ~active, toks)

    @staticmethod
    def _decode_spec_impl(params, cache, last_logits, pos, active, ctx,
                          dnt, dnt_valid, temps, kk, key, lora, *, k,
                          ngram, n_rounds, top_k, top_p, sampling, cfg,
                          rules):
        """``n_rounds`` speculative verify rounds in one ``lax.scan``.

        Per round and slot: the carried next token plus up to ``k − 1``
        prompt-lookup drafts from the slot's device context run through
        ONE chunk-mode forward at the slot's own depth; the accepted
        prefix merges into the grid with the shared row loop of slice
        updates (per-slot variable count inside a ``k``-column window —
        rejected drafts never land, so there is no rollback).

        ``kk`` [B]: per-slot lookahead inside the width-``k`` dispatch
        — draft positions past ``kk − 1`` are forced-rejected (greedy:
        masked out of the acceptance cumprod; sampled: masked inside
        ``rejection_accept``, with ``residual_next`` treating
        ``acc == kk − 1`` as the row's full accept), so each row emits
        and merges exactly as a ``k = kk`` dispatch would. This is how
        rows at different adaptive ``k`` share one executable.

        Greedy slots (temp 0): a draft survives where it equals the
        model's argmax and the carried token becomes the argmax at the
        break — token-identical to the plain engine. Sampled slots:
        exact speculative REJECTION sampling per slot (the static
        ``SpeculativeGenerator``'s math) — draft ``d`` accepted with
        probability ``p(d)`` under the filtered/tempered distribution;
        on rejection the next token draws from the residual (``d``'s
        mass removed, renormalized). The residual draw cannot be
        reconstructed outside the round, so rounds carry the drawn
        TOKEN (``dnt``). A fresh admission's is the token its prefill
        drew (``_finish_admit``); only a ``dnt_valid=False`` row (one
        imported from a plain engine's export) takes its first token
        from its carried logits instead.

        Unlike the plain chunk (grid merged once per dispatch), each
        round merges: round r+1's verify must read round r's accepted
        K/V, and per-slot acceptance lengths break the uniform-column
        chunk layout. One merge per ~tokens_per_pass tokens instead of
        one per ``steps_per_call``: cheap since the merge writes only the
        rows' ``k``-column windows (it rewrote every layer's whole plane
        a round until PR 28).
        """
        from kubetorch_tpu.models.speculative import (
            _ngram_draft,
            rejection_accept,
            residual_next,
        )

        B = last_logits.shape[0]
        model = decoder_for(cfg)
        M = grid_dims(cache, off_grid_leaves(model, cfg))[1]
        Lctx = ctx.shape[1]
        bidx = jnp.arange(B)[:, None]
        # `sampling` is STATIC (the host re-jits once if sampled traffic
        # ever appears): all-greedy dispatches — the established serving
        # path — must not pay the softmax/filter/categorical machinery
        # whose outputs a where() would discard.
        sampled = temps > 0
        tk = jnp.maximum(temps, 1e-6)

        def _probs(lg):
            # temper BEFORE filtering — generate.sample_tokens order, so
            # the rejection test draws from the identical distribution
            shp = lg.shape
            flat = filter_logits(
                (lg / tk.reshape((-1,) + (1,) * (lg.ndim - 1))
                 ).reshape(-1, shp[-1]), top_k, top_p)
            return jax.nn.softmax(flat, axis=-1).reshape(shp)

        # a row with no carried token (imported from a plain export) takes
        # its first from its (loop-invariant) logits: computed ONCE, not
        # per round
        key, k_fresh = jax.random.split(_fold(key))
        nt0 = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        if sampling:
            nt0 = jnp.where(
                sampled,
                jax.random.categorical(
                    k_fresh, jnp.log(_probs(last_logits) + 1e-30)
                ).astype(jnp.int32),
                nt0)
        dnt = jnp.where(dnt_valid, dnt, nt0)

        def one(carry, key_r):
            cache, pos, ctx, dnt, dnt_valid = carry
            k_acc, k_res = jax.random.split(key_r)
            nt = dnt
            cext = ctx.at[bidx, pos[:, None]].set(nt[:, None],
                                                  mode="drop")
            if k > 1:
                drafts = _ngram_draft(cext, pos, nt, n=ngram, k=k)
                feed = jnp.concatenate([nt[:, None], drafts], axis=1)
            else:
                feed = nt[:, None]
            positions = pos[:, None] + jnp.arange(k)[None, :]
            gmask = jnp.broadcast_to(
                (jnp.arange(M)[None, None, :] < pos[:, None, None])
                & active[:, None, None], (B, k, M))
            emask = jnp.broadcast_to(
                jnp.arange(k)[None, None, :]
                <= jnp.arange(k)[None, :, None], (B, k, k)) \
                & active[:, None, None]
            chunk = model.init_chunk(cfg, cache, B, k)
            lg, chunk, _ = model.forward_cached(
                params, feed, positions, cache, None, gmask, cfg, rules,
                chunk=chunk, chunk_col=0, chunk_mask=emask, lora=lora)
            g = jnp.argmax(lg, axis=-1).astype(jnp.int32)         # [B, k]
            if k > 1:
                # per-slot lookahead mask: positions past kk-1 are
                # forced rejects, so acc never exceeds the row's own k
                ok_g = ((feed[:, 1:] == g[:, :-1])
                        & (jnp.arange(k - 1)[None, :]
                           < (kk[:, None] - 1))).astype(jnp.int32)
                acc = jnp.sum(jnp.cumprod(ok_g, axis=1), axis=1)  # 0..k-1
            else:
                acc = jnp.zeros((B,), jnp.int32)
            if sampling:
                # exact per-slot rejection sampling — shared helpers
                # with the static SpeculativeGenerator (kk-masked)
                probs = _probs(lg)                               # [B,k,V]
                acc_s = rejection_accept(probs, feed, k_acc, k=k, kk=kk)
                acc = jnp.where(sampled, acc_s, acc)
            emit = jnp.where(active, 1 + acc, 0)
            cache = model.merge_chunk_into_grid(cache, chunk, pos, emit)
            # context mirrors the grid's accepted prefix
            cpos = pos[:, None] + jnp.arange(k)[None, :]
            cvalid = jnp.arange(k)[None, :] < emit[:, None]
            ctx = ctx.at[bidx, jnp.where(cvalid, cpos, Lctx)].set(
                jnp.where(cvalid, feed, 0), mode="drop")
            # next carried token at the acceptance break: the model's
            # correction/bonus (greedy) or a residual draw (sampled)
            j = jnp.clip(acc, 0, k - 1)
            dnt = jnp.take_along_axis(g, j[:, None], axis=1)[:, 0]
            if sampling:
                nxt_s = residual_next(probs, feed, acc, k_res, k=k,
                                      kk=kk)
                dnt = jnp.where(sampled, nxt_s, dnt)
            dnt_valid = dnt_valid | active
            return (cache, pos + emit, ctx, dnt, dnt_valid), (feed, emit)

        (cache, pos, ctx, dnt, dnt_valid), (toks, emits) = jax.lax.scan(
            one, (cache, pos, ctx, dnt, dnt_valid),
            jax.random.split(key, n_rounds))
        return cache, pos, ctx, dnt, dnt_valid, toks, emits


class RollingDecoder:
    """Remote-facing decode driver: the serving twin of driving a local
    :class:`RollingGenerator` by hand.

    Deploy as a ``kt.cls`` (one instance per worker process owns the
    engine + TPU) and drive it over the **persistent pipelined call
    channel** (``serving/channel.py``): every method takes/returns plain
    JSON-able values, and ``step()`` is safe to pipeline at depth ≥ 2 —
    the channel executes calls FIFO per connection, so chunk N+1 is
    serialized + shipped while chunk N is still on device, hiding the
    per-call dispatch tax the POST path pays.

    >>> remote = kt.cls(MyDecoderFactory)(...).to(compute)
    >>> chan = remote.channel(depth=2)
    >>> chan.call("submit", prompt, max_new_tokens=64)
    >>> calls = []
    >>> while True:
    ...     while len(calls) < 2:           # keep the pipeline full
    ...         calls.append(chan.submit(method="step"))
    ...     out = calls.pop(0).result()     # chunk N; N+1 already queued
    ...     if not out["pending"]:
    ...         break
    """

    def __init__(self, engine: "RollingGenerator"):
        self.engine = engine

    def submit(self, prompt, max_new_tokens: int = 128,
               temperature: float = 0.0,
               prefix_id: Optional[int] = None,
               stop: Optional[List[List[int]]] = None,
               repetition_penalty: float = 1.0,
               adapter_id: int = -1) -> int:
        return self.engine.submit(
            [int(t) for t in prompt], max_new_tokens=max_new_tokens,
            temperature=temperature, prefix_id=prefix_id, stop=stop,
            repetition_penalty=repetition_penalty, adapter_id=adapter_id)

    def register_prefix(self, tokens, adapter_id: int = -1) -> int:
        """Prefill a shared prefix once, server-side; the returned id
        goes back into :meth:`submit`'s ``prefix_id`` (JSON-able both
        ways — this is the client surface the wire field was waiting
        for). Per-adapter prefixes are separate registrations, matching
        the engine's weight-dependence rule."""
        return int(self.engine.register_prefix(
            [int(t) for t in tokens], adapter_id=int(adapter_id)))

    def drop_prefix(self, prefix_id: int) -> bool:
        return bool(self.engine.drop_prefix(int(prefix_id)))

    def step(self) -> Dict[str, Any]:
        """One decode chunk. Returns ``{"events": [[rid, tokens, done],
        ...], "pending": n, "device_ms": t}`` — ``device_ms`` is the
        chunk's measured wall time in the engine-owning process, the
        ground truth the call-path latency decomposition compares its
        ``device`` stage against."""
        import time as _time

        t0 = _time.perf_counter()
        events = self.engine.step()
        device_ms = (_time.perf_counter() - t0) * 1e3
        return {
            "events": [[rid, [int(t) for t in toks], bool(done)]
                       for rid, toks, done in events],
            "pending": self.engine.pending,
            "device_ms": round(device_ms, 3),
        }

    def pending(self) -> int:
        """Host bookkeeping only — no device sync. Prefer
        ``chan.control("stats")`` for polling: a control frame is
        answered by the pod server out-of-band (it never queues behind
        pipelined ``step()`` calls in the channel FIFO and never pays a
        worker hop), from the engine snapshot the worker piggybacks on
        call responses."""
        return self.engine.pending

    def warmup(self, prompt_buckets=(16, 64, 128)) -> bool:
        self.engine.warmup(tuple(int(b) for b in prompt_buckets))
        return True

    def stats(self) -> Dict[str, Any]:
        """Host bookkeeping only (no device sync) — see :meth:`pending`
        for the cheaper control-frame polling path."""
        eng = self.engine
        return {"max_slots": eng.max_slots, "max_len": eng.max_len,
                "steps_per_call": eng.steps_per_call,
                "free_slots": len(eng._free), "queued": len(eng._queue),
                "active": len(eng._slots),
                "prefilling": len(eng._prefilling),
                **({"spec": eng.spec_stats} if eng.spec else {})}


class RollingService:
    """Thread-safe facade: concurrent callers share one rolling batch.

    This is what a ``kt.cls`` model server wants — the pod server runs
    requests on a thread pool, and every concurrent ``generate()`` call
    lands in the same continuous batch instead of serializing whole-batch
    generations. A single driver thread advances the engine while any
    request is pending.
    """

    def __init__(self, engine: "RollingGenerator"):
        import threading

        self.engine = engine
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._results: Dict[int, List[int]] = {}
        self._done: Dict[int, bool] = {}
        self._live: Dict[int, Any] = {}  # rid -> token queue (generate_iter)
        import contextvars

        # copy_context: driver-thread log lines keep the submitter's ids
        self._driver = threading.Thread(
            target=contextvars.copy_context().run, args=(self._drive,),
            name="kt-rolling-driver", daemon=True)
        self._driver.start()

    def generate(self, prompt, max_new_tokens: int = 128,
                 temperature: float = 0.0, prefix_id: Optional[int] = None,
                 stop: Optional[List[List[int]]] = None,
                 timeout: Optional[float] = None,
                 adapter_id: int = -1) -> List[int]:
        """Submit and block until this request finishes; other callers'
        requests decode in the same chunks meanwhile."""
        import time as _time

        deadline = None if timeout is None else _time.time() + timeout
        with self._wake:
            rid = self.engine.submit(prompt, max_new_tokens=max_new_tokens,
                                     temperature=temperature,
                                     prefix_id=prefix_id, stop=stop,
                                     adapter_id=adapter_id)
            self._results[rid] = []
            self._done[rid] = False
            self._wake.notify_all()
            while not self._done[rid]:
                rem = None if deadline is None else deadline - _time.time()
                if rem is not None and rem <= 0:
                    raise TimeoutError(f"request {rid} timed out")
                self._wake.wait(timeout=rem if rem is not None else 1.0)
            self._done.pop(rid)
            return self._results.pop(rid)

    def generate_iter(self, prompt, max_new_tokens: int = 128,
                      temperature: float = 0.0,
                      prefix_id: Optional[int] = None,
                      stop: Optional[List[List[int]]] = None,
                      adapter_id: int = -1):
        """Yield tokens as decode chunks land — compose with the call
        path's result streaming for end-to-end token streaming."""
        import queue as _queue

        live: "_queue.SimpleQueue" = _queue.SimpleQueue()
        with self._wake:
            rid = self.engine.submit(prompt, max_new_tokens=max_new_tokens,
                                     temperature=temperature,
                                     prefix_id=prefix_id, stop=stop,
                                     adapter_id=adapter_id)
            self._live[rid] = live
            self._wake.notify_all()
        while True:
            item = live.get()
            if item is None:
                return
            yield item

    def _drive(self):
        while True:
            with self._wake:
                while not self.engine.pending:
                    self._wake.wait()
                events = self.engine.step()
                for rid, toks, done in events:
                    live = self._live.get(rid)
                    if live is not None:
                        for tok in toks:
                            live.put(tok)
                        if done:
                            live.put(None)
                            del self._live[rid]
                        continue
                    self._results.setdefault(rid, []).extend(toks)
                    if done:
                        self._done[rid] = True
                if any(done for _, _, done in events):
                    self._wake.notify_all()
