"""The one traffic generator: a mix's parameters in, requests out.

A mix file (``traffic/<mix>.json``) gives discrete distributions of prompt
and output lengths (``values`` with ``weights``), the closed loop's client
count, and the profiling sample's size.  Lengths are dealt in blocks
(``block`` requests per block) whose bucket counts are the weights exactly,
spread evenly through the block (smooth weighted round robin), so any run of
consecutive requests holds each bucket near its share.  Every seed deals the
same lengths in the same order: a window sees a few tens of requests, fewer
than a block, and a seeded start in the block would change which lengths it
sees and so the work.  The seed draws the token ids.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Request:
    tokens: np.ndarray      # (prompt_len,) int32
    budget: int             # output tokens asked for

    @property
    def prompt_len(self) -> int:
        return int(self.tokens.shape[0])


def counts(weights, n: int, at_least_one: bool = False) -> list:
    """Bucket counts summing to ``n`` in proportion to ``weights`` (largest
    remainder); with ``at_least_one`` every bucket gets one first."""
    w = np.asarray(weights, float)
    w = w / w.sum()
    base = np.ones(len(w), int) if at_least_one else np.zeros(len(w), int)
    rest = n - int(base.sum())
    if rest < 0:
        raise ValueError(f"{n} requests cannot cover {len(w)} buckets")
    share = w * n - base
    share = np.maximum(share, 0.0)
    share = share / share.sum() * rest if share.sum() else share
    c = np.floor(share).astype(int)
    order = np.argsort(-(share - c), kind="stable")
    c[order[:rest - int(c.sum())]] += 1
    return (base + c).tolist()


def spread(values, c) -> list:
    """``c[i]`` copies of ``values[i]``, each bucket spread evenly through
    the sequence (smooth weighted round robin)."""
    n = sum(c)
    cur = [0] * len(c)
    out = []
    for _ in range(n):
        cur = [a + b for a, b in zip(cur, c)]
        i = max(range(len(c)), key=lambda j: (cur[j], -j))
        cur[i] -= n
        out.append(values[i])
    return out


def _dealt(dist: dict, n: int) -> list:
    """``n`` lengths from ``dist``: blocks of exact bucket counts, spread
    evenly."""
    block = spread(dist["values"], counts(dist["weights"], dist["block"]))
    return [int(block[i % len(block)]) for i in range(n)]


def rng_for(seed: int, stream: int):
    return np.random.default_rng([int(seed), stream])


def _tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=n, dtype=np.int32)


def deck(mix: dict, seed: int, vocab: int, scale=(1, 1)) -> list:
    """The closed loop's requests in submission order (``mix["deck"]`` of
    them; the loop cycles when a run exhausts them).  ``scale`` divides
    prompt and output lengths (rehearsal at a tiny size)."""
    rng = rng_for(seed, 1)
    n = int(mix["deck"])
    prompts = _dealt(mix["prompt_tokens"], n)
    outputs = _dealt(mix["output_tokens"], n)
    return [Request(_tokens(rng, max(1, p // scale[0]), vocab),
                    max(2, o // scale[1]))
            for p, o in zip(prompts, outputs)]


def sample(mix: dict, seed: int, vocab: int, scale=(1, 1)) -> list:
    """The profiling sample the plan comes from, which is also the
    warm-up: ``mix["sample_requests"]`` requests whose sizes are the same
    for every seed (each prompt bucket at least once, the rest by weight;
    outputs by weight), paired in a fixed order.  Token ids come from the
    seed."""
    n = int(mix["sample_requests"])
    pd, od = mix["prompt_tokens"], mix["output_tokens"]
    prompts = [v for v, k in zip(pd["values"],
                                 counts(pd["weights"], n, at_least_one=True))
               for _ in range(k)]
    outputs = [v for v, k in zip(od["values"], counts(od["weights"], n))
               for _ in range(k)]
    outputs = [outputs[i] for i in np.random.default_rng(0).permutation(n)]
    rng = rng_for(seed, 2)
    return [Request(_tokens(rng, max(1, p // scale[0]), vocab),
                    max(2, o // scale[1]))
            for p, o in zip(prompts, outputs)]
