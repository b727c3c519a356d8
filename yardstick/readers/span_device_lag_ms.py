"""How long the host and the device wait for each other at the two ends of
a serving tick's launch, in mean milliseconds over the window's ticks that
launched. Both need a host span and a device event in one subtraction,
which is what the program's spans on the profiler's clock are for:

* ``edge: "launch"``: the start of the tick's device program (``XLA
  Modules``) less the start of its ``engine.dispatch`` span: the jitted
  call's way through its arguments to the device;
* ``edge: "fetch"``: the end of the tick's ``engine.fetch`` span less the
  end of its device program: the token's way back to the host.

The tick's program is the run of ``jit_<program>`` (the dispatch span's
``program`` attribute) that starts between the dispatch span's start and
the end of the ``engine.tick`` span with the same ``tick``."""

from __future__ import annotations

from yardstick import program_spans

TICK, DISPATCH, FETCH = "engine.tick", "engine.dispatch", "engine.fetch"


def read(facts: dict, *, cell: str, edge: str):
    if edge not in ("launch", "fetch"):
        raise ValueError(f"edge is launch or fetch, not {edge!r}")
    by_tick: dict = {}
    for row in program_spans.in_window(facts, cell):
        if row[0] in (TICK, DISPATCH, FETCH):
            by_tick.setdefault(program_spans.ident(row), {})[row[0]] = row
    lags = []
    for spans in by_tick.values():
        if len(spans) < 3:
            continue  # an idle tick, or one the window's edge cut
        tick, dispatch, fetch = spans[TICK], spans[DISPATCH], spans[FETCH]
        want = f"jit_{dispatch[3].get('program')}"
        runs = [p for p in program_spans.programs_inside(
            facts["trace"], dispatch[1], tick[1] + tick[2]) if p[0] == want]
        if not runs:
            continue
        _, start, dur = runs[0]
        lags.append(start - dispatch[1] if edge == "launch"
                    else fetch[1] + fetch[2] - (start + dur))
    return sum(lags) / len(lags) / 1e6 if lags else None
