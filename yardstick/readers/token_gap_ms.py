"""A percentile of the time between consecutive tokens of one request,
over all tokens of all requests in the window (``facts["token_times"]``:
request -> the times its tokens were handed back)."""

from __future__ import annotations

import statistics


def read(facts: dict, *, percentile: int = 95):
    gaps = [b - a for times in facts.get("token_times", {}).values()
            for a, b in zip(times, times[1:])]
    if len(gaps) < 20:
        return None
    return 1e3 * statistics.quantiles(gaps, n=100)[percentile - 1]
