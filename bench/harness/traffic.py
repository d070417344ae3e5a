"""The one traffic generator: a mix file's parameters in, the requests a
run offers out.

Every run of a cell offers the same work, whatever its ``--seed``. A mix
fixes a multiset of ``block`` (prompt length, output length) pairs: the
``(i + 1/2) / block`` quantiles of each length distribution, paired by a
permutation that the mix's own ``pair_seed`` fixes. A length distribution
is exponential with the mean the mix states (``{"mean": m}``): the mix's
source publishes means, and the exponential is the distribution that
assumes nothing beyond its mean. The schedule is made of whole blocks in
an order the mix's ``pool_seed`` fixes (and, where the count is not a
multiple of the block, one last partial block holding an evenly spaced
share of it). Every request is due when the window opens: an offline
queue holding ``requests`` requests.

A prompt's token ids are drawn uniformly from the vocabulary (ids 0 and 1
left out), from the pool seed. ``--seed`` draws only the sample of
finished requests that the correctness check reads. Neither the token ids
nor the order may follow ``--seed``: with random weights, what DyTC
drafts, and so what a round costs, follows the content; and the server
keeps its acceptance history per slot and drafts every round to the
deepest budget of any slot, so the order, which decides what runs beside
what and which request inherits which slot's history, changes the work as
well. Weights never depend on the seed either: the configuration has its
own.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Tuple

import numpy as np


@dataclasses.dataclass
class Offered:
    index: int
    due_s: float                 # seconds after the window opens
    prompt: np.ndarray
    max_new_tokens: int


def quantile_lengths(dist: dict, n: int) -> List[int]:
    """The (i + 1/2)/n quantiles of an exponential of mean ``dist["mean"]``,
    rounded, and at least 1."""
    return [max(1, round(-dist["mean"] * math.log(1.0 - (i + 0.5) / n)))
            for i in range(n)]


def length_pairs(mix: dict) -> List[Tuple[int, int]]:
    """The mix's multiset of (prompt length, output length)."""
    n = int(mix["block"])
    prompts = quantile_lengths(mix["prompt"], n)
    outputs = quantile_lengths(mix["output"], n)
    perm = np.random.default_rng(int(mix["pair_seed"])).permutation(n)
    return [(prompts[i], outputs[int(perm[i])]) for i in range(n)]


def make_prompt(rng: np.random.Generator, length: int,
                vocab: int) -> np.ndarray:
    return rng.integers(2, vocab, size=length).astype(np.int32)


def schedule(mix: dict, vocab: int) -> List[Offered]:
    """Every request the run offers, in the order the queue holds them."""
    pool = int(mix["pool_seed"])
    pairs = length_pairs(mix)
    n = int(mix["requests"])
    k = len(pairs)
    picked: List[Tuple[int, int]] = []          # (block, pair index)
    for b in range(-(-n // k)):
        size = min(k, n - b * k)
        # a last, partial block: an evenly spaced share of the multiset
        idx = [(j * k) // size for j in range(size)]
        order = np.random.default_rng([pool, 0, b]).permutation(size)
        picked.extend((b, idx[int(i)]) for i in order)
    out = []
    for j, (b, i) in enumerate(picked):
        p, o = pairs[i]
        rng = np.random.default_rng([pool, 2, b, i])
        out.append(Offered(j, 0.0, make_prompt(rng, p, vocab), o))
    return out


def warm_lengths(mix: dict) -> List[int]:
    """Distinct prompt lengths of the mix (the admission shapes to warm)."""
    return sorted({p for p, _ in length_pairs(mix)})
