"""The one traffic generator: a mix's parameters in, seeded work out.

Open-loop arrivals follow `serving/load.py`'s idea (a seeded schedule,
replayed against the clock whatever the server does), with the lengths
made parameters. Every seed gets the same work: each stretch of constant
rate holds `round(rate * length)` arrivals whose gaps are the same
exponential quantiles, shuffled, and the prompt and output lengths are
the mix's weights applied to the request count, shuffled. The mix's
`schedule_seed` fixes that shuffle, so every seed replays one trace with
its own token ids.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np


@dataclass(frozen=True)
class Arrival:
    due_s: float            # from the window's start
    prompt: np.ndarray      # (len,) int32
    max_new_tokens: int


def rate_segments(traffic: Dict[str, Any], seconds: float
                  ) -> List[Tuple[float, float, float]]:
    """[(start_s, end_s, rate_hz)] covering [0, seconds): the base rate,
    with fixed burst windows where the mix has them."""
    base = float(traffic["rate_hz"])
    b = traffic.get("bursts")
    cuts: List[Tuple[float, float, float]] = []
    t = 0.0
    if b:
        start = float(b["start_s"])
        while start < seconds:
            end = min(start + float(b["length_s"]), seconds)
            if start > t:
                cuts.append((t, start, base))
            cuts.append((start, end, float(b["rate_hz"])))
            t = end
            start += float(b["every_s"])
    if t < seconds:
        cuts.append((t, seconds, base))
    return cuts


def _counts(weights: Sequence[float], n: int) -> List[int]:
    """Largest-remainder split of n by weights."""
    w = np.asarray(weights, float) / float(np.sum(weights))
    raw = w * n
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out), kind="stable")[: n - int(out.sum())]:
        out[i] += 1
    return out.tolist()


def _spread(values: Sequence[int], weights: Sequence[float], n: int,
            rng: np.random.Generator) -> np.ndarray:
    vals = np.repeat(np.asarray(values, int), _counts(weights, n))
    rng.shuffle(vals)
    return vals


def arrival_times(traffic: Dict[str, Any], seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    times: List[float] = []
    for start, end, rate in rate_segments(traffic, seconds):
        n = int(round(rate * (end - start)))
        if n == 0:
            continue
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
        gaps *= (end - start) / gaps.sum()
        rng.shuffle(gaps)
        times.extend(start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]]))
    return np.asarray(times)


def serve_schedule(traffic: Dict[str, Any], seconds: float, seed: int,
                   vocab_size: int) -> List[Arrival]:
    """The window's requests, in order of when they are due. The order of
    the gaps and lengths comes from the mix's `schedule_seed` (one fixed
    trace, replayed with every seed's own token ids)."""
    order = np.random.default_rng([int(traffic["schedule_seed"]), 0x5E27E])
    due = arrival_times(traffic, seconds, order)
    n = len(due)
    plens = _spread(traffic["prompt_lens"], traffic["prompt_weights"], n,
                    order)
    outs = _spread(traffic["output_lens"], traffic["output_weights"], n,
                   order)
    ids = np.random.default_rng([int(seed), 0x70C5])
    return [Arrival(float(t), ids.integers(1, vocab_size, size=int(p),
                                           dtype=np.int32), int(o))
            for t, p, o in zip(due, plens, outs)]


def train_rows(traffic: Dict[str, Any], vocab_size: int, seed: int,
               step: int) -> np.ndarray:
    """(batch, seq_len) int32 token rows of one step: Zipf-distributed
    ids (an LM-token surrogate), different for every step and seed."""
    rng = np.random.default_rng([int(seed), int(step), 0x7EA1])
    z = rng.zipf(float(traffic["zipf_a"]),
                 size=(int(traffic["batch"]), int(traffic["seq_len"])))
    return (z % (vocab_size - 2) + 1).astype(np.int32)
