"""The routed layers' share of their roofline in decode launches: the
least time the chip could take for what the launches' routing had to do,
over the device time of the operations under the routed layer's scope.

What a launch had to do comes from the program's own spans of its tick:
``engine.build`` says how many rows decoded, ``engine.apply`` how many
distinct experts a routed layer read (``experts_touched``, the mean over
the layers, from the launch's census). ``yardstick.counts_lfm2
.routed_layer`` turns that into operations and bytes: the touched experts'
weights read once, the rows in and out, the router, ``k`` experts' products
a row. It counts the work and not the implementation, so a later kernel is
read by the same yardstick. A tick counts if its program run starts in the
window, and only such runs' operations are timed. ``also_named``: as in
``readers/scope_share.py`` (the compiler's grouped-product calls)."""

from __future__ import annotations

from yardstick import counts, counts_lfm2, program_spans, scoped_ops
from yardstick import reduce as reduction

BUILD, DISPATCH, APPLY, TICK = ("engine.build", "engine.dispatch",
                                "engine.apply", "engine.tick")


def read(facts: dict, *, cell: str, program: str = "decode_step",
         scope: str = "dtg.routed", also_named: tuple = ()):
    z = facts["sizes"]
    routed_layers = sum(ffn == "routed" for _, ffn in z["layers"])
    by_tick: dict = {}
    for row in program_spans.load(cell):
        if row[0] in (BUILD, DISPATCH, APPLY, TICK):
            by_tick.setdefault(program_spans.ident(row), {})[row[0]] = row
    lo, hi = reduction.window_ns(facts["trace"])
    least, runs = 0.0, []
    for spans in by_tick.values():
        if len(spans) < 4 or spans[DISPATCH][3].get("program") != program:
            continue
        touched = spans[APPLY][3].get("experts_touched")
        if touched is None:
            continue
        tick, dispatch = spans[TICK], spans[DISPATCH]
        ran = [p for p in program_spans.programs_inside(
            facts["trace"], dispatch[1], tick[1] + tick[2])
            if p[0] == f"jit_{program}" and lo <= p[1] < hi]
        if not ran:
            continue
        runs.append((ran[0][1], ran[0][1] + ran[0][2]))
        flops, nbytes = counts_lfm2.routed_layer(
            z, rows=int(spans[BUILD][3]["rows"]),
            experts_touched=float(touched))
        least += routed_layers * counts.least_seconds(
            flops, nbytes, facts["peaks"])
    rows = scoped_ops.rows_within(facts, cell, sorted(runs))
    spent = sum(r[2] for r in rows
                if scoped_ops.under(r, scope, also_named))
    return 100.0 * least / (spent / 1e9) if spent else None
