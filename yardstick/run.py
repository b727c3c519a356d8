#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python3 yardstick/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

One process. Refuses anything but a TPU with the chips the cell asks for.
Set-up (building, compiling or loading from the cache, warming, the
program's first steps) is timed apart as ``setup_s``; then the window; then,
with the program's state freed, the comparison with the plain reference.
The last line of standard output is the result; facts go to standard error.
"""

from __future__ import annotations

import argparse
import shutil
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from yardstick import harness  # noqa: E402
from yardstick.spans import Spans  # noqa: E402

TRACE_DIR = harness.HERE / ".traces"


class Window:
    """The harness's side of a driver's window: where set-up ends, and the
    profiler around the window of a traced run."""

    def __init__(self, spans: Spans, trace_dir: Path | None):
        self.spans, self.trace_dir = spans, trace_dir
        self.setup_s = None
        self.t_open = self.t_close = None

    def open(self) -> None:
        if self.trace_dir is not None:
            import jax

            shutil.rmtree(self.trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # the benchmark's spans only
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=options)
            self.spans.tracing = True
            with self.spans.span("window_open"):
                pass
        self.t_open = time.perf_counter()
        self.setup_s = self.t_open - T0

    def close(self) -> None:
        self.t_close = time.perf_counter()
        if self.trace_dir is not None:
            import jax

            with self.spans.span("window_close"):
                pass
            self.spans.tracing = False
            jax.profiler.stop_trace()


def main(argv=None, *, devices=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    cache_dir = harness.setup_compile_cache()
    if devices is None:
        devices = harness.require_chips(cell.chips)
    import jax

    spans = Spans()
    trace_dir = TRACE_DIR / cell.name if args.trace else None
    window = Window(spans, trace_dir)
    driver = cell.driver.Driver(cell, args.seed, devices, spans)
    ran = driver.run(args.seconds, window)
    peak = harness.memory_peak_bytes(devices)
    driver.release()
    harness.say(workload=cell.name, seed=args.seed, cache_dir=cache_dir,
                setup_s=window.setup_s,
                **{k: v for k, v in ran["facts"].items()
                   if not isinstance(v, (list, dict)) or len(v) <= 8})

    t_check = time.perf_counter()
    compared = driver.check()
    harness.say(check_s=time.perf_counter() - t_check)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": peak}
    units = {m["name"]: m["unit"]
             for m in cell.end_to_end + cell.per_layer}
    breakdown = None
    if args.trace:
        from yardstick import reduce as reduction

        metrics, extra, breakdown = reduction.per_layer_metrics(
            cell, trace_dir, ran["facts"], devices, peak)
        device.update(extra)
    else:
        metrics = {"setup_s": window.setup_s, **ran["end_to_end"]}
        metrics = {m["name"]: metrics[m["name"]] for m in cell.end_to_end}
    correct = harness.verdict(compared)
    harness.say_compared(compared)
    print(harness.result_line(
        correct=correct, attempted=ran["attempted"], failed=ran["failed"],
        metrics=metrics, units=units, device=device, compared=compared,
        breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
