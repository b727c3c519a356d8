"""Requests for a serving cell, from a mix's parameters and a seed.

Mix keys: ``requests`` (how many), ``sizes`` (how many lengths a round
holds), ``prompt`` and ``output`` (each ``{"mean", "sigma", "min", "max"}``:
a log-normal length of that mean, clipped) and ``vocab_below``.

A round is ``sizes`` requests. Its prompt lengths are the distribution's
quantiles at ``(k + 1/2) / sizes``, its output lengths likewise: every
round, and so every seed, offers the same work, which is what lets two runs
on different seeds be compared at all (drawn one by one from the seed, the
lengths a 40 s window happens to hold move its tokens by several percent).
What the seed draws is which prompt length meets which output length, the
order of each round, and every token id. Every request is due at 0: a
backlog. A driver submits a request when its ``due`` time has come and
times it from then, so an open loop is another generator's file.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Offered:
    rid: int
    due: float  # seconds after the window opens
    prompt: np.ndarray  # (P,) int32
    max_new_tokens: int


def round_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths: the quantiles at ``(k + 1/2) / n`` of the log-normal
    with ``spec``'s mean and sigma, rounded and clipped."""
    mu = np.log(spec["mean"]) - spec["sigma"] ** 2 / 2.0
    z = np.array([NormalDist().inv_cdf((k + 0.5) / n) for k in range(n)])
    return np.clip(np.rint(np.exp(mu + spec["sigma"] * z)),
                   spec["min"], spec["max"]).astype(np.int64)


def requests(mix: dict, seed: int) -> list[Offered]:
    rng = np.random.default_rng(int(seed))
    n, k = int(mix["requests"]), int(mix["sizes"])
    prompts = round_lengths(mix["prompt"], k)
    outputs = round_lengths(mix["output"], k)
    out = []
    while len(out) < n:
        for p, o in zip(rng.permutation(prompts), rng.permutation(outputs)):
            ids = rng.integers(0, int(mix["vocab_below"]), int(p),
                               dtype=np.int32)
            out.append(Offered(len(out), 0.0, ids, int(o)))
    return out[:n]
