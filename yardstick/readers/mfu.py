"""The whole step's share of the chip's bf16 peak: the model operations of
the traced window's work (``facts["model_flops"]``, which the driver counts
with ``yardstick.counts``) over the window's length times peak times
chips."""

from __future__ import annotations


def read(facts: dict):
    flops = facts.get("model_flops")
    if not flops:
        return None
    chips = len(facts["trace"]["devices"])
    return 100.0 * flops / (
        facts["window_s"] * facts["peaks"]["bf16_flops_per_s"] * chips)
