"""The share, in percent, of the first device's idle time in the window
that lies under a leaf span of the program's own (``dtg.*``: a span with no
span inside it): idle time the program itself can put a name to. What is
left lies between spans, or outside every span, in the caller's code.
Standard error gets the idle seconds by leaf span. The parts under a span
are the ones ``reduce.idle_gaps_by_span`` hands the breakdown."""

from __future__ import annotations

from yardstick import harness
from yardstick import reduce as reduction


def read(facts: dict):
    trace = facts["trace"]
    if not trace.get("program"):
        return None
    idle = reduction.idle_gaps(trace)
    if not idle:
        return None
    by_name: dict[str, float] = {}
    covered = []
    for _, name, a, b in reduction.under_spans(idle,
                                               reduction.leaf_spans(trace)):
        by_name[name] = by_name.get(name, 0.0) + (b - a)
        covered.append((a, b))
    explained = reduction.total(reduction.union(covered))
    harness.say(idle_s=reduction.total(idle) / 1e9,
                idle_s_by_leaf_span={
                    k: v / 1e9 for k, v in sorted(
                        by_name.items(), key=lambda kv: -kv[1])})
    return 100.0 * explained / reduction.total(idle)
