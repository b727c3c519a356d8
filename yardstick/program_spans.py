"""The program's own spans, out of the run's profiler trace.

``obs/tracing.span`` writes every span of the program (``engine.tick`` and
its phases, ``loop.*``, ``prefetch.*``) into whatever profiler session is
running, as a host event named ``dtg.<name>`` whose stats are the span's
attributes (``tick``, ``step``, ``rows``, ...). ``reduce.load_xplane``
keeps only the benchmark's own ``ys.`` host rows, so the readers of these
spans open the traced run's ``.xplane.pb`` themselves, through
:func:`load`, once a process. A row is

    [name, start_ns, dur_ns, attrs, line]

without the prefix, on the clock of ``facts["trace"]`` (the same file), in
order of start with a parent before its children; ``line`` tells one host
thread's events from another's. A program that writes no such span (the
parent of the PR that brought them) gives no rows, and every reader then
returns ``None``.
"""

from __future__ import annotations

import bisect
import functools
from pathlib import Path

from yardstick import harness
from yardstick import reduce as reduction

PREFIX = "dtg."


def host_spans(path: Path) -> list[list]:
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    rows, line_no = [], 0
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            line_no += 1
            rows.extend(
                [e.name[len(PREFIX):], float(e.start_ns),
                 float(e.duration_ns), dict(e.stats), line_no]
                for e in line.events if e.name.startswith(PREFIX))
    rows.sort(key=lambda r: (r[1], -r[2]))
    return rows


@functools.cache
def load(cell: str) -> list[list]:
    """The spans of the traced run of ``cell`` that this process made. A
    metric is read only after that run, so no trace there means the metric
    file names another cell than its entry's ``workloads``: that raises."""
    return host_spans(
        reduction.find_xplane(harness.HERE / ".traces" / cell))


def in_window(facts: dict, cell: str) -> list[list]:
    """The spans that start inside the traced window."""
    lo, hi = reduction.window_ns(facts["trace"])
    return [r for r in load(cell) if lo <= r[1] < hi]


def ident(row: list):
    """What the spans of one tick or one step share."""
    return row[3].get("tick", row[3].get("step"))


def leaves(rows: list[list]) -> list[list]:
    """The spans with no span inside them, thread by thread (``rows`` in
    :func:`load`'s order)."""
    out = []
    last: dict[int, list] = {}  # the newest span of each line
    for row in rows:
        before = last.get(row[4])
        if before is not None and row[1] >= before[1] + before[2]:
            out.append(before)  # the next one starts after it: a leaf
        last[row[4]] = row
    out.extend(last.values())
    out.sort(key=lambda r: r[1])
    return out


def programs_inside(trace: dict, a: float, b: float) -> list[list]:
    """The first device's program runs that start in ``[a, b)``."""
    device = sorted(trace["devices"])[0]
    programs = reduction.device_rows(trace, "programs", device)
    starts = [r[1] for r in programs]
    return programs[bisect.bisect_left(starts, a):
                    bisect.bisect_left(starts, b)]
