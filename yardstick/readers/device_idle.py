"""1 minus the union of device-operation intervals over the traced
window, in percent."""

from __future__ import annotations


def read(facts: dict):
    return 100.0 * (1.0 - facts["busy_s"] / facts["window_s"])
