"""Device milliseconds of a jitted program's runs in the traced window:
``stat`` is ``mean`` or ``median`` over its runs on the first device."""

from __future__ import annotations

import statistics

from yardstick import reduce as reduction


def read(facts: dict, *, program: str | None = None, stat: str = "mean"):
    trace = facts["trace"]
    device = sorted(trace["devices"])[0]
    lo, hi = reduction.window_ns(trace)
    runs = [d for _, s, d in reduction.program_events(
        trace, device, program or facts["program"]) if lo <= s < hi]
    if not runs:
        return None
    pick = {"mean": statistics.fmean, "median": statistics.median}[stat]
    return pick(runs) / 1e6
