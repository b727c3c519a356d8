"""Mean milliseconds of one of the benchmark's spans, per ``per`` (a count
the driver reports, such as ``steps``) or per occurrence of the span."""

from __future__ import annotations


def read(facts: dict, *, span: str, per: str | None = None):
    rows = [b - a for n, a, b in facts["spans"]
            if n == span and facts["t_open"] <= a <= facts["t_close"]]
    if not rows:
        return None
    count = facts[per] if per else len(rows)
    return 1e3 * sum(rows) / count if count else None
