"""The program's own spans, as the reduction's one parse of the trace keeps
them.

``obs/tracing.span`` writes every span of the program (``engine.tick`` and
its phases, ``loop.*``, ``prefetch.*``) into whatever profiler session is
running, as a host event named ``dtg.<name>`` whose stats are the span's
attributes (``tick``, ``step``, ``rows``, ...). ``reduce.load_xplane``
keeps them as ``facts["trace"]["program"]``, rows

    [name, start_ns, dur_ns, attrs, line]

without the prefix, on the clock of the device events, in order of start
with a parent before its children; ``line`` tells one host thread's events
from another's. A program that writes no such span (the
parent of the PR that brought them) gives no rows, and every reader then
returns ``None``.
"""

from __future__ import annotations

import bisect
from pathlib import Path

from yardstick import reduce as reduction


def host_spans(path: Path) -> list[list]:
    """The program's rows of the trace at ``path``."""
    return reduction.load_xplane(path)["program"]


def rows(facts: dict) -> list[list]:
    return facts["trace"].get("program", [])


def in_window(facts: dict) -> list[list]:
    """The spans that start inside the traced window."""
    lo, hi = reduction.window_ns(facts["trace"])
    return [r for r in rows(facts) if lo <= r[1] < hi]


def ident(row: list):
    """What the spans of one tick or one step share."""
    return row[3].get("tick", row[3].get("step"))


def programs_inside(trace: dict, a: float, b: float) -> list[list]:
    """The first device's program runs that start in ``[a, b)``."""
    device = sorted(trace["devices"])[0]
    programs = reduction.device_rows(trace, "programs", device)
    starts = [r[1] for r in programs]
    return programs[bisect.bisect_left(starts, a):
                    bisect.bisect_left(starts, b)]
