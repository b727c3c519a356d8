"""The causal flash-attention forward kernel of ``ops/flash_attention.py``:
q, k, v in; o and the row statistics out."""

from __future__ import annotations

from yardstick import counts
from yardstick.kernels import flash_shared

SIGNATURE = "pallas:3->bf16+f32"


def matches(op_name: str) -> bool:
    return flash_shared.matches(op_name, SIGNATURE)


def least_seconds(facts: dict, events: list) -> float:
    return flash_shared.least_seconds(counts.flash_forward, facts, events)
