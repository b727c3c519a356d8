"""Device milliseconds of a jitted program's runs in the traced window per
unit of a number its launches' ``engine.dispatch`` spans carry (``chunks``:
the prompts a prefill launch carries), over the runs paired with their
launch. A run is paired with the dispatch of ``program`` whose span starts
last before the run does; a launch whose run the trace dropped pairs with
none and counts nothing, and a run whose dispatch is taken already (its own
was dropped) is left out. ``None`` where no run pairs."""

from __future__ import annotations

import bisect

from yardstick import program_spans
from yardstick import reduce as reduction


def read(facts: dict, *, program: str, stat: str):
    trace = facts["trace"]
    lo, hi = reduction.window_ns(trace)
    device = sorted(trace["devices"])[0]
    launches = [r for r in program_spans.rows(facts)
                if r[0] == "engine.dispatch"
                and r[3].get("program") == program and stat in r[3]]
    starts = [r[1] for r in launches]
    paired: set[int] = set()
    spent = count = 0.0
    for _, s, d in reduction.program_events(trace, device, program):
        i = bisect.bisect_left(starts, s) - 1
        if not lo <= s < hi or i < 0 or i in paired:
            continue
        paired.add(i)
        spent += d
        count += float(launches[i][3][stat])
    return spent / count / 1e6 if count else None
