"""A group of kernels' share of their roofline: the least time the chip
could take for the work their calls had to do, over the summed device time
of their events. Each kernel is a file ``yardstick/kernels/<name>.py`` with
``matches(op_name)`` and ``least_seconds(facts, events)``; ``inside`` keeps
only events within runs of that program.

The program gives its kernels no names, so a kernel's file knows it by its
operands and results (``reduce.op_label``). A kernel taken off the path
leaves the share silent; one that only changed its operands would too, so
where a listed kernel matches nothing while Pallas calls did run, standard
error says which calls were there."""

from __future__ import annotations

import importlib

from yardstick import harness
from yardstick import reduce as reduction


def read(facts: dict, *, kernels: list[str], inside: str | None = None):
    trace = facts["trace"]
    device = sorted(trace["devices"])[0]
    lo, hi = reduction.window_ns(trace)
    if inside:
        ops = reduction.ops_within(trace, device, [
            (s, s + d) for _, s, d in reduction.program_events(
                trace, device, inside) if lo <= s < hi])
    else:
        ops = [r for r in reduction.device_rows(trace, "ops", device)
               if lo <= r[1] < hi]
    least = spent = 0.0
    for name in kernels:
        kernel = importlib.import_module(f"yardstick.kernels.{name}")
        events = [r for r in ops if kernel.matches(r[0])]
        if not events:
            unknown = sorted({reduction.family(r[0]) for r in ops
                              if " pallas:" in r[0]})
            if unknown:
                harness.say(warning=f"kernel {name!r} matches no event, "
                            "yet Pallas calls ran: its metric is left out",
                            pallas_calls=unknown)
            return None  # a kernel off the path leaves the share silent
        least += kernel.least_seconds(facts, events)
        spent += sum(d for _, _, d in events) / 1e9
    return 100.0 * least / spent
