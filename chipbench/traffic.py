"""Serving traffic, generated from a cell's parameters and the seed.

Every seed gets the same multiset of sizes and gaps in another order:
lengths are the lognormal's quantiles at ``(i + 0.5) / n``, clipped;
adapters follow a Zipf law in exact counts; Poisson inter-arrival gaps
are the exponential's quantiles. The seed permutes them and draws the
prompt tokens, so seeds change the order of the work and not its
amount.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Job:
    prompt: np.ndarray          # int32 token ids
    max_new: int
    adapter: int                # row in the cell's adapter set
    due: float = 0.0            # seconds after the window opens (open loop)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(n: int, spec: dict) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def zipf_counts(n: int, n_items: int, s: float) -> np.ndarray:
    """``n`` draws split over ``n_items`` in proportion to ``1 / rank^s``,
    rounded so they sum to ``n``."""
    p = 1.0 / np.arange(1, n_items + 1) ** s
    p = p / p.sum()
    c = np.floor(n * p).astype(np.int64)
    c[np.argsort(-(n * p - c))[: n - c.sum()]] += 1
    return c


def jobs(w: dict, vocab: int, n: int, seed: int, *, rate: float = 0.0,
         block: int = 0) -> List[Job]:
    """``n`` jobs of cell parameters ``w`` (``prompt``, ``output``,
    ``n_adapters``, ``zipf_s``); with ``rate`` > 0 they carry Poisson due
    times at that many per second. With ``block``, every consecutive
    ``block`` jobs hold the same multiset of sizes and adapters (a closed
    loop's window sees a few waves of ``block`` requests, so each wave
    carries the same work whatever the seed)."""
    rng = np.random.default_rng([seed, 0x5E7E])
    b = block or n

    def per_block(values):
        return np.concatenate([rng.permutation(values)
                               for _ in range(-(-n // b))])[:n]

    plen = per_block(lognormal_lengths(b, w["prompt"]))
    olen = per_block(lognormal_lengths(b, w["output"]))
    ads = per_block(np.repeat(np.arange(w["n_adapters"]),
                              zipf_counts(b, w["n_adapters"], w["zipf_s"])))
    due = np.zeros(n)
    if rate > 0:
        gaps = rng.permutation(-np.log1p(-_quantiles(n)) / rate)
        due = np.cumsum(gaps) - gaps[0]
    return [Job(prompt=rng.integers(0, vocab, int(p), dtype=np.int32),
                max_new=int(o), adapter=int(a), due=float(d))
            for p, o, a, d in zip(plen, olen, ads, due)]
