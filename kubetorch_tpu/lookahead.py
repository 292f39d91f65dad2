"""Per-row adaptive speculative lookahead — the k-adaptation state
machine shared by the real rolling engine, the host-only sim engine,
and the scheduler tests.

One instance tracks ONE batch row's speculative lookahead ``k`` (the
verify-forward width: 1 carried token + ``k − 1`` prompt-lookup
drafts) and its draft acceptance-rate EMA. The machine has three
regimes:

- **grow**: acceptance EMA ≥ ``GROW_AT`` — the row's drafts land
  (code editing, RAG quoting, any extractive traffic), so lookahead
  grows one step per decode chunk toward ``k_max``
  (``KT_SPEC_K_MAX``): every accepted draft is nearly free in the
  weight-bound regime.
- **shrink**: EMA < ``SHRINK_AT`` — drafts don't land (random text),
  so lookahead decays one step per chunk toward ``k = 1``: at the
  floor the row IS plain decode (the verify forward carries one token
  and offers no drafts) and verify FLOPs stop being spent where they
  never pay.
- **probe**: a row sitting at ``k = 1`` produces no acceptance
  evidence (there are no drafts to accept), so after ``PROBE_EVERY``
  chunks at the floor it tries ``k = 2`` once. A regime change (the
  conversation turned extractive) shows up in the probe's EMA and the
  row grows back; otherwise the EMA stays low and the next adaptation
  returns it to the floor — an adversarial-random row therefore
  *settles* at k = 1 (p50) at a ~1/PROBE_EVERY probing cost.

``cap`` is the scheduler's occupancy throttle
(``KT_SPEC_OCCUPANCY_THROTTLE``): under high occupancy decode is
compute-bound and verify width is no longer free, so the driver caps
every row's lookahead (cap = 1 → immediate clamp to plain decode);
when occupancy falls back into the latency regime the cap lifts and
high-accept rows regrow. ``cap = 0`` means uncapped.

Rows START at ``k_max`` (optimistic, ``ema0 = 1.0``): the lever
exists for the latency regime, where the first chunks are exactly the
ones a TTFT-bound caller feels, and a wrong guess decays within
``~log`` chunks. Greedy token output is invariant to ``k`` by
construction (a draft survives only where it equals the model's own
argmax), so the adaptation schedule can never change WHAT is emitted
— only how many verify positions are spent emitting it.

Stdlib-only, and deliberately OUTSIDE ``models/`` (whose package init
imports jax): ``serving/engine.py`` — which must stay importable
without jax — and its :class:`SimRollingEngine` twin import this
directly; spec model code reaches it via the
``models.speculative.LookaheadState`` re-export.
"""

from __future__ import annotations

from typing import Dict, Sequence

GROW_AT = 0.55      # acceptance EMA at/above which k grows
SHRINK_AT = 0.25    # acceptance EMA below which k shrinks
PROBE_EVERY = 8     # chunks at k=1 between k=2 probes


def spec_stats_dict(rounds: int, emitted: int, drafted: int,
                    live_ks: Sequence[int], k_max: int,
                    cap: int) -> Dict[str, float]:
    """The ``spec_stats`` derivation shared by the real rolling engine
    and the CPU sim — one copy, because the derived ratios feed both
    the shed-check verify pricing and the published ``engine_spec_*``
    metrics, and the sim is what the scheduler tests assert against:
    a formula fix applied to one engine but not the other would
    silently split them."""
    accepted = max(0, emitted - rounds)
    return {"rounds": rounds, "emitted": emitted,
            "tokens_per_pass": emitted / rounds if rounds else 0.0,
            "drafted": drafted, "accepted": accepted,
            "accept_rate": accepted / drafted if drafted else 0.0,
            "verify_waste": max(0, drafted - accepted),
            "k_mean": (sum(live_ks) / len(live_ks)
                       if live_ks else 0.0),
            "k_cap": LookaheadState.cap_k(k_max, cap)}


class LookaheadState:
    """One row's adaptive lookahead: current ``k``, acceptance EMA,
    and the floor-probe counter. :meth:`observe` folds one verify
    round's acceptance into the EMA; :meth:`adapt` moves ``k`` one
    step per decode chunk."""

    __slots__ = ("k", "ema", "floor_chunks")

    def __init__(self, k_max: int, cap: int = 0, k0: int | None = None,
                 ema0: float = 1.0):
        cap_k = self.cap_k(k_max, cap)
        self.k = max(1, min(k0 if k0 is not None else cap_k, cap_k))
        self.ema = float(ema0)
        self.floor_chunks = 0

    @staticmethod
    def cap_k(k_max: int, cap: int) -> int:
        """Effective lookahead ceiling: ``k_max`` under ``cap`` (0 =
        uncapped)."""
        k_max = max(1, int(k_max))
        return max(1, min(k_max, int(cap))) if cap else k_max

    def observe(self, emitted: int, k_used: int, *,
                alpha: float) -> None:
        """Fold one verify round's acceptance into the EMA:
        ``emitted`` tokens landed (1 carried + accepted drafts) out of
        ``k_used`` offered. A ``k_used == 1`` round offers no drafts
        and carries no evidence — the EMA holds (the probe path in
        :meth:`adapt` supplies fresh evidence instead)."""
        if k_used <= 1:
            return
        rate = (min(emitted, k_used) - 1) / (k_used - 1)
        self.ema = (1.0 - alpha) * self.ema + alpha * rate

    def adapt(self, k_max: int, cap: int = 0, *,
              grow_at: float = GROW_AT, shrink_at: float = SHRINK_AT,
              probe_every: int = PROBE_EVERY) -> int:
        """One adaptation move (call once per decode chunk); → the new
        ``k``. The cap clamps IMMEDIATELY (the throttle must bite this
        chunk, not k_max chunks later); grow/shrink move one step."""
        cap_k = self.cap_k(k_max, cap)
        if self.k > cap_k:
            self.k = cap_k
            return self.k
        if self.k == 1:
            self.floor_chunks += 1
            if cap_k > 1 and (self.ema >= grow_at
                              or self.floor_chunks >= probe_every):
                self.k = 2
                self.floor_chunks = 0
            return self.k
        if self.ema >= grow_at:
            self.k = min(self.k + 1, cap_k)
        elif self.ema < shrink_at:
            self.k -= 1
            if self.k == 1:
                self.floor_chunks = 0
        return self.k
