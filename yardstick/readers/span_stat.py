"""The mean of a number one of the program's own spans carries as a stat
(``obs/tracing.span``'s attributes: ``experts_touched`` and ``load_ratio``
on ``engine.apply``), over the span's occurrences in the traced window that
carry it. ``program`` keeps the occurrences whose tick dispatched that
program (``decode_step``). ``None`` where no occurrence carries it."""

from __future__ import annotations

from yardstick import program_spans


def ticks_of(rows: list[list], program: str) -> set:
    """The ticks whose ``engine.dispatch`` span launched ``program``."""
    return {program_spans.ident(r) for r in rows
            if r[0] == "engine.dispatch" and r[3].get("program") == program}


def read(facts: dict, *, span: str, stat: str,
         program: str | None = None):
    rows = program_spans.in_window(facts)
    keep = ticks_of(rows, program) if program else None
    values = [float(r[3][stat]) for r in rows
              if r[0] == span and stat in r[3]
              and (keep is None or program_spans.ident(r) in keep)]
    return sum(values) / len(values) if values else None
