"""The flash-attention backward kernel that produces dk and dv: the same six
in; dk and dv out."""

from __future__ import annotations

from yardstick import counts
from yardstick.kernels import flash_shared

SIGNATURE = "pallas:6->bf16+bf16"


def matches(op_name: str) -> bool:
    return flash_shared.matches(op_name, SIGNATURE)


def least_seconds(facts: dict, events: list) -> float:
    return flash_shared.least_seconds(counts.flash_backward_dkv, facts, events)
