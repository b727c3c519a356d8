"""The share of their roofline of the layers under one of the program's
named scopes, in the launches of one program: the least time the chip
could take for what those layers had to do, over the device time of the
operations under the scope. ``readers/routed_roofline.py`` for any scope
and any configuration's counts.

What a launch had to do comes from the program's own spans of its tick:
``engine.build`` says how many rows it ran, and ``engine.apply`` carries
the numbers named in ``stats`` (a routed layer's census). ``work``, a
function of the module ``yardstick.<counts>``, turns ``(sizes, rows=,
**stats)`` into ``(operations, bytes, layers)``, the first two for one of
the launch's ``layers`` layers of the kind. It counts the work and not the
implementation, so a later kernel is read by the same yardstick, and the
share cannot pass 100% unless the time leaves work out. A tick counts if
its program run starts in the window and its spans carry every stat; only
such runs' operations are timed. ``also_named``: as in
``readers/scope_share.py`` (the compiler's grouped-product calls). ``None``
where nothing ran under the scope (a program without it)."""

from __future__ import annotations

import importlib

from yardstick import counts as roofline
from yardstick import program_spans, scoped_ops
from yardstick import reduce as reduction

BUILD, DISPATCH, APPLY, TICK = ("engine.build", "engine.dispatch",
                                "engine.apply", "engine.tick")


def read(facts: dict, *, cell: str, scope: str, counts: str, work: str,
         stats: tuple = (), program: str = "decode_step",
         also_named: tuple = ()):
    count = getattr(importlib.import_module(f"yardstick.{counts}"), work)
    by_tick: dict = {}
    for row in program_spans.load(cell):
        if row[0] in (BUILD, DISPATCH, APPLY, TICK):
            by_tick.setdefault(program_spans.ident(row), {})[row[0]] = row
    lo, hi = reduction.window_ns(facts["trace"])
    least, runs = 0.0, []
    for spans in by_tick.values():
        if len(spans) < 4 or spans[DISPATCH][3].get("program") != program:
            continue
        carried = spans[APPLY][3]
        if any(s not in carried for s in stats):
            continue
        tick, dispatch = spans[TICK], spans[DISPATCH]
        ran = [p for p in program_spans.programs_inside(
            facts["trace"], dispatch[1], tick[1] + tick[2])
            if p[0] == f"jit_{program}" and lo <= p[1] < hi]
        if not ran:
            continue
        runs.append((ran[0][1], ran[0][1] + ran[0][2]))
        flops, nbytes, layers = count(
            facts["sizes"], rows=int(spans[BUILD][3]["rows"]),
            **{s: float(carried[s]) for s in stats})
        least += layers * roofline.least_seconds(flops, nbytes,
                                                 facts["peaks"])
    rows = scoped_ops.rows_within(facts, cell, sorted(runs))
    spent = sum(r[2] for r in rows
                if scoped_ops.under(r, scope, also_named))
    return 100.0 * least / (spent / 1e9) if spent else None
