"""One general load generator, driven by a traffic file. No JAX here.

A traffic file fixes the mix: ``loop`` (open / closed), arrivals or client
count, the distributions of prompt and output lengths, the ramp before the
window, and the ``deployment`` an operator would size for it. The generator
draws ONE sequence of (gap, prompt length, output length) from the file's
own ``shape_seed`` and every run replays it from its head: the run's
``--seed`` draws the token ids (and the weights), not the schedule. Every
seed so offers the same work at the same instants, and two runs differ by
the system alone. A tail at 0.8 x knee is made by where the bursts fall; a
schedule drawn anew by each seed moved ``ttft_p90_ms`` by 30% of its median
(PERF.md section 2), which no bound of at most 10% admits. The price: the
tail is that of one draw, and a later PR could fit it; other draws are other
traffic files.
"""

from __future__ import annotations

import threading
import time

import numpy as np


def draw(spec: dict, n: int, rng) -> np.ndarray:
    """``n`` samples of one distribution of a traffic file."""
    kind = spec["dist"]
    if kind == "fixed":
        x = np.full(n, spec["value"], float)
    elif kind == "uniform":
        x = rng.uniform(spec["min"], spec["max"], n)
    elif kind == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif kind == "exponential":
        x = rng.exponential(spec["mean"], n)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in spec or "max" in spec:
        x = np.clip(x, spec.get("min", -np.inf), spec.get("max", np.inf))
    return x


def build(traffic: dict, seconds: float) -> dict:
    """The run's requests: ``{"loop", "ramp_s", "requests": [...]}``; each
    request has ``id``, ``prompt_len``, ``out_len`` and, in an open loop,
    ``due_s`` relative to the window's start (negative inside the ramp); in
    a closed loop ``client``. Token ids are drawn by :func:`prompt_tokens`."""
    ramp = float(traffic.get("ramp_s", 0.0))
    horizon = seconds + ramp
    shape = np.random.default_rng(traffic["shape_seed"])
    loop = traffic["loop"]
    if loop == "open":
        arr = traffic["arrivals"]
        n = max(1, int(round(arr["rate_per_s"] * horizon)))
        gaps = draw({**arr.get("gaps", {"dist": "exponential"}),
                     "mean": 1.0 / arr["rate_per_s"]}, n, shape)
        gaps *= horizon / gaps.sum()          # the same offered load always
    elif loop == "closed":
        n = int(traffic["clients"] * traffic["requests_per_client"])
        gaps = None
    else:
        raise ValueError(f"loop must be open or closed, got {loop!r}")
    plen = draw(traffic["prompt_len"], n, shape).round().astype(int)
    olen = draw(traffic["output_len"], n, shape).round().astype(int)
    reqs = [{"id": i, "prompt_len": int(plen[i]), "out_len": int(olen[i])}
            for i in range(n)]
    if gaps is not None:
        due = np.cumsum(gaps) - ramp
        for r, t in zip(reqs, due):
            r["due_s"] = float(t)
    else:
        for r in reqs:
            r["client"] = r["id"] % traffic["clients"]
    return {"loop": loop, "ramp_s": ramp, "requests": reqs}


def prompt_tokens(seed: int, rid: int, n: int, vocab: int) -> list:
    return np.random.default_rng([seed, 15485863, rid]).integers(
        1, vocab, n).tolist()


class Record:
    """What the client saw of one request, on ``time.perf_counter``."""
    __slots__ = ("id", "due", "sent", "frames", "tokens", "done", "error",
                 "prompt_len", "out_len")

    def __init__(self, req):
        self.id, self.prompt_len, self.out_len = (
            req["id"], req["prompt_len"], req["out_len"])
        self.due = self.sent = None
        self.frames = []            # (arrival, tokens in the frame)
        self.tokens = []
        self.done = False
        self.error = None


def _consume(rec, submit, prompt, t0):
    rec.sent = time.perf_counter() - t0
    try:
        for frame in submit(prompt, rec.out_len):
            toks = frame.get("tokens") or []
            if toks:
                rec.frames.append((time.perf_counter() - t0, len(toks)))
                rec.tokens.extend(toks)
            if frame.get("done"):
                rec.done = True
    except Exception as exc:  # noqa: BLE001 — a failed request is a datum
        rec.error = f"{type(exc).__name__}: {exc}"


def drive(plan: dict, submit, seed: int, vocab: int, seconds: float,
          drain_s: float, on_window=None):
    """Offer ``plan`` through ``submit(prompt, n) -> frames``. Returns
    ``(records, t_close)`` with every time relative to the window's start
    (the ramp is negative). Open loop: each request is sent at its due time
    whatever the system does. Closed loop: each client sends its next
    request when the last one finished. After the window closes nothing
    new is sent; streams in flight are given ``drain_s`` to show a frame.
    ``on_window(t_rel)`` is called once, when the window opens."""
    ramp = plan["ramp_s"]
    reqs = plan["requests"]
    prompts = {r["id"]: prompt_tokens(seed, r["id"], r["prompt_len"], vocab)
               for r in reqs}
    records = [Record(r) for r in reqs]
    threads = []
    t0 = time.perf_counter() + ramp         # the window opens at t0
    stop = threading.Event()

    def opener():
        delay = t0 - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        if on_window is not None:
            on_window(time.perf_counter() - t0)

    opener_t = threading.Thread(target=opener, daemon=True)
    opener_t.start()
    if plan["loop"] == "open":
        for r, rec in zip(reqs, records):
            rec.due = r["due_s"]
            delay = t0 + r["due_s"] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            th = threading.Thread(
                target=_consume, args=(rec, submit, prompts[r["id"]], t0),
                daemon=True)
            th.start()
            threads.append(th)
        time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    else:
        by_client = {}
        for r, rec in zip(reqs, records):
            by_client.setdefault(r["client"], []).append((r, rec))

        def client(items):
            for r, rec in items:
                if stop.is_set():
                    return
                rec.due = time.perf_counter() - t0
                _consume(rec, submit, prompts[r["id"]], t0)

        for items in by_client.values():
            th = threading.Thread(target=client, args=(items,), daemon=True)
            th.start()
            threads.append(th)
        time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        stop.set()
    t_close = time.perf_counter() - t0
    deadline = time.perf_counter() + drain_s
    if plan["loop"] == "open":
        # wait for a first frame of everything that was due, no longer
        for rec, th in zip(records, threads):
            while (not rec.frames and rec.error is None and th.is_alive()
                   and time.perf_counter() < deadline):
                time.sleep(0.01)
    opener_t.join(timeout=1.0)
    return records, t_close
