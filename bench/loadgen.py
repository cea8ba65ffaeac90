"""Requests and arrivals from a traffic file and a seed.

The traffic file fixes the schedule of work: prompt and output lengths
and inter-arrival gaps are drawn once with the file's own ``"draw_seed"``,
so every run seed offers the same sizes at the same times.  The run seed
picks the token ids.  With the few requests a window of this engine
holds, the order of the sizes moves the end-to-end numbers by a fifth
from seed to seed, so it is fixed too.

Lengths are lognormal (``median``, ``sigma``), each draw clipped to
[``min``, ``max``]; arrivals are Poisson at ``rate_per_s``.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Item:
    """One request as the client sends it."""
    uid: int
    prompt: np.ndarray
    max_new: int
    due: float = 0.0          # seconds after the arrivals begin


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def count(traffic: dict, seconds: float) -> int:
    """Requests that arrive in ``seconds``."""
    return max(1, int(round(traffic["arrival"]["rate_per_s"] * seconds)))


def generate(traffic: dict, seconds: float, seed_seq, vocab: int,
             uid0: int = 0) -> list[Item]:
    """The requests that arrive in ``seconds``, sorted by due time."""
    n = count(traffic, seconds)
    fixed = np.random.default_rng(traffic["draw_seed"])
    prompts = _lengths(fixed, traffic["prompt"], n)
    outputs = _lengths(fixed, traffic["output"], n)
    gaps = fixed.exponential(1.0 / traffic["arrival"]["rate_per_s"], n)
    # every arrival falls inside ``seconds``: gaps rescaled to end there
    due = np.cumsum(gaps * (seconds * n / (n + 1)) / gaps.sum())
    rng = np.random.default_rng(seed_seq)
    return [Item(uid0 + i, rng.integers(0, vocab, int(prompts[i]))
                 .astype(np.int32), int(outputs[i]), float(due[i]))
            for i in range(n)]


def warmup(traffic: dict, seed_seq, vocab: int, uid0: int) -> list[Item]:
    """Requests that compile and run every shape the window uses before
    it opens; their tokens share nothing with the window's."""
    w = traffic["warmup"]
    rng = np.random.default_rng(seed_seq)
    return [Item(uid0 + i, rng.integers(0, vocab, w["prompt"]).astype(np.int32),
                 w["output"]) for i in range(w["requests"])]
