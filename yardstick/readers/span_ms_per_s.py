"""Milliseconds a second of the window under one of the program's own
spans (``obs/tracing.watch_stalls``: ``gc``, a generation-2 collection;
``compile``, a phase of a JAX compile or an executable's load): the union
of the span's rows, clipped to the window, over the window's seconds. A
union, because compile phases nest (an inner ``jit`` is traced inside the
outer one's trace) and a collection on one thread stalls them all.

``counter`` is the attribute that says the program watched for the span
(``gc_full``, ``compiles`` on ``engine.tick`` and ``loop.dispatch``): with
rows in the window that carry it and no row of the span there, the reading
is 0.0; with none that carry it (a program that does not watch), ``None``.
"""

from __future__ import annotations

from yardstick import program_spans
from yardstick import reduce as reduction


def read(facts: dict, *, span: str, counter: str):
    if not any(counter in r[3] for r in program_spans.in_window(facts)):
        return None
    lo, hi = reduction.window_ns(facts["trace"])
    under = reduction.union(reduction.clip(
        [(s, s + d) for name, s, d, _, _ in program_spans.rows(facts)
         if name == span], lo, hi))
    return reduction.total(under) / 1e6 / facts["window_s"]
