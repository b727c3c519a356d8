"""Host milliseconds of a serving tick: the benchmark's span around each
``ServeEngine.step()`` less the device time of the programs that ran inside
it, mean over the ticks that launched something."""

from __future__ import annotations

from yardstick import reduce as reduction


def read(facts: dict, *, span: str = "engine_step"):
    trace = facts["trace"]
    device = sorted(trace["devices"])[0]
    lo, hi = reduction.window_ns(trace)
    ticks = [(s, s + d) for n, s, d in trace["host"]
             if n == span and lo <= s < hi]
    programs = reduction.device_rows(trace, "programs", device)
    host, launched, i = 0.0, 0, 0
    for a, b in ticks:
        while i < len(programs) and programs[i][1] < a:
            i += 1
        on_device, j = 0.0, i
        while j < len(programs) and programs[j][1] < b:
            on_device += programs[j][2]
            j += 1
        if j > i:
            host += (b - a) - on_device
            launched += 1
    return host / launched / 1e6 if launched else None
