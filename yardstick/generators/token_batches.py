"""Token batches for a training cell, from a mix's parameters and a seed.

Mix keys: ``batch`` and ``seq`` (the step's shape), ``vocab_below`` (ids are
uniform in ``[0, vocab_below)``). Step ``i`` of seed ``s`` is always the same
batch, whoever asks and in what order, so the reference can be handed the
first steps again after the window. Every row differs.
"""

from __future__ import annotations

import itertools

import numpy as np


def batch_at(mix: dict, seed: int, step: int) -> dict:
    rng = np.random.default_rng([int(seed), int(step)])
    tokens = rng.integers(0, int(mix["vocab_below"]),
                          (int(mix["batch"]), int(mix["seq"])),
                          dtype=np.int32)
    return {"tokens": tokens}


def batches(mix: dict, seed: int):
    """The endless stream a window consumes."""
    return (batch_at(mix, seed, i) for i in itertools.count())


def tokens_per_step(mix: dict) -> int:
    return int(mix["batch"]) * int(mix["seq"])
