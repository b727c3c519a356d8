"""The flash-attention backward kernel that produces dq: q, k, v, do and the
two statistics in; dq out."""

from __future__ import annotations

from yardstick import counts
from yardstick.kernels import flash_shared

SIGNATURE = "pallas:6->bf16"


def matches(op_name: str) -> bool:
    return flash_shared.matches(op_name, SIGNATURE)


def least_seconds(facts: dict, events: list) -> float:
    return flash_shared.least_seconds(counts.flash_backward_dq, facts, events)
