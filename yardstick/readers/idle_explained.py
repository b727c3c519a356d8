"""The share, in percent, of the first device's idle time in the window
that lies under a leaf span of the program's own (``dtg.*``: a span with no
span inside it): idle time the program itself can put a name to. What is
left lies between spans, or outside every span, in the caller's code.
Standard error gets the idle seconds by leaf span."""

from __future__ import annotations

import bisect

from yardstick import harness, program_spans
from yardstick import reduce as reduction


def read(facts: dict, *, cell: str):
    rows = program_spans.load(cell)
    if not rows:
        return None
    trace = facts["trace"]
    lo, hi = reduction.window_ns(trace)
    device = sorted(trace["devices"])[0]
    idle = reduction.gaps(
        reduction.busy_intervals(trace, device, lo, hi), lo, hi)
    if not idle:
        return None
    # the gaps are sorted and disjoint, so those a span overlaps are a run
    starts, ends = [a for a, _ in idle], [b for _, b in idle]
    by_name: dict[str, float] = {}
    covered = []
    for name, start, dur, _, _ in program_spans.leaves(rows):
        under = reduction.clip(
            idle[bisect.bisect_right(ends, start):
                 bisect.bisect_left(starts, start + dur)],
            start, start + dur)
        if under:
            by_name[name] = by_name.get(name, 0.0) + reduction.total(under)
            covered.extend(under)
    explained = reduction.total(reduction.union(covered))
    harness.say(idle_s=reduction.total(idle) / 1e9,
                idle_s_by_leaf_span={
                    k: v / 1e9 for k, v in sorted(
                        by_name.items(), key=lambda kv: -kv[1])})
    return 100.0 * explained / reduction.total(idle)
