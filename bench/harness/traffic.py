"""The one traffic generator: reads a mix's parameters, draws from the seed.

A mix (``bench/traffic/<name>.json``) gives length distributions. Sizes are
stratified quantiles of the stated distributions, in one fixed order drawn
from ``ORDER_SEED``; the seed draws the token ids. So every seed gets the
same prompt and output lengths in the same order: a window holds only part
of a block of the mix, and a per-seed order would change how much work it
holds (the share of decode-fill steps, which produce no output token).

Prompts share nothing: their first tokens are drawn without replacement
(until the vocabulary is used up), so the radix prefix cache finds neither
a whole cached block nor a one-token partial match to fork.

    {"prompt": {"dist": "lognormal", "median": 128, "sigma": 1.0,
                "min": 16, "max": 1024},
     "output": {"dist": "fixed", "value": 1}}
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, Iterator, List

import numpy as np

ORDER_SEED = 0  # the order of the sizes, the same for every run


@dataclass
class Gen:
    """One generated request: token ids and output budget."""
    prompt: List[int]
    max_new: int


def quantiles(dist: Dict, n: int) -> List[int]:
    """``n`` stratified draws of a length distribution, ascending."""
    if dist["dist"] == "fixed":
        return [int(dist["value"])] * n
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = NormalDist()
    out = []
    for i in range(n):
        x = dist["median"] * math.exp(dist["sigma"] * z.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out


def _firsts(rng: np.random.Generator, vocab: int) -> Iterator[int]:
    while True:
        yield from (rng.permutation(vocab - 1) + 1).tolist()


def _requests(mix: Dict, order: np.random.Generator, rng: np.random.Generator,
              n: int, vocab: int, firsts: Iterator[int]) -> List[Gen]:
    plens = order.permutation(quantiles(mix["prompt"], n))
    outs = order.permutation(quantiles(mix["output"], n))
    out = []
    for p, o in zip(plens, outs):
        ids = rng.integers(1, vocab, int(p)).tolist()
        ids[0] = next(firsts)
        out.append(Gen(ids, int(o)))
    return out


def stream(mix: Dict, seed: int, vocab: int) -> Iterator[Gen]:
    """An endless queue, in blocks of ``mix["block"]`` requests that each
    hold the same multiset of sizes."""
    order, rng = np.random.default_rng(ORDER_SEED), np.random.default_rng(seed)
    firsts = _firsts(rng, vocab)
    while True:
        yield from _requests(mix, order, rng, int(mix["block"]), vocab,
                             firsts)


def probes(mix: Dict, seed: int, n: int, vocab: int) -> List[Gen]:
    """``n`` independent requests of the mix, one per scale-out."""
    order, rng = np.random.default_rng(ORDER_SEED), np.random.default_rng(seed)
    return _requests(mix, order, rng, n, vocab, _firsts(rng, vocab))
