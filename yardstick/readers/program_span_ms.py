"""Milliseconds of one of the program's own spans (``obs/tracing.span``,
read from the traced run's profile by ``yardstick.program_spans``) over
its occurrences that start in the window: ``stat`` is ``mean`` (per
occurrence, or per ``per``, a count the driver reports such as ``steps``)
or ``max``. ``beside`` keeps only the occurrences whose tick or step also
has a span of that name (``engine.dispatch``: the ticks that launched).
``less_device`` takes from each occurrence the device time of the programs
that started inside it: what is left is the host's."""

from __future__ import annotations

from yardstick import program_spans


def read(facts: dict, *, span: str, stat: str = "mean",
         per: str | None = None, beside: str | None = None,
         less_device: bool = False):
    rows = program_spans.in_window(facts)
    picked = [r for r in rows if r[0] == span]
    if beside:
        ids = {program_spans.ident(r) for r in rows if r[0] == beside}
        picked = [r for r in picked if program_spans.ident(r) in ids]
    if not picked:
        return None
    durations = [d for _, _, d, _, _ in picked]
    if less_device:
        durations = [
            d - sum(p[2] for p in program_spans.programs_inside(
                facts["trace"], s, s + d))
            for _, s, d, _, _ in picked]
    if stat == "max":
        return max(durations) / 1e6
    if stat != "mean":
        raise ValueError(f"stat is mean or max, not {stat!r}")
    count = facts[per] if per else len(durations)
    return sum(durations) / count / 1e6 if count else None
