#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip at the cell's
own size, many seeds in one process (set-up is long).

    python3 yardstick/control.py --workload <name> --seeds 1,2,3 \\
        [--seconds s] [--out file.jsonl]

For each seed it prints one JSON line with three groups of numbers, every
group read by ``yardstick.compare`` and held to the cell's limits exactly as
a run's are, with the verdict that follows under ``correct``:

``program``   the timed path against the float32 reference: the lower
              readings.
``control``   the reference put in the program's place with int8 operands
              (the nearest precision below the configurations' bfloat16),
              against the float32 reference: it has to come out not correct.
``faults``    training only, planted in the reference put in the program's
              place: half of the batch left out and the mean taken over the
              rest; every step handing its state back unchanged.

It exits 1 unless the program came out correct on every seed and the control
and every fault not correct. The benchmark's own runs never call this;
``PERF.md`` records what it read and the limits that followed. ``tests/yardstick/test_control.py`` keeps the
same comparisons at a size the CPU holds.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from yardstick import compare, harness  # noqa: E402
from yardstick.run import Window  # noqa: E402
from yardstick.spans import Spans  # noqa: E402


def train_readings(cell, seed: int, devices, seconds: float) -> dict:
    spans = Spans()
    driver = cell.driver.Driver(cell, seed, devices, spans)
    driver.run(seconds, Window(spans, None))  # nobody times this window
    driver.release()
    ref = driver.reference_readings()
    low = driver.reference_readings("int8")
    batch = int(cell.traffic["batch"])
    half = driver.reference_readings(rows=slice(0, batch // 2))
    return {
        "program": compare.train_numbers(driver.got, ref),
        "control": compare.train_numbers(low, ref),
        "faults": {
            "half_batch": compare.train_numbers(half, ref),
            "unchanged_state": compare.train_numbers(
                driver.reference_readings(frozen=True), ref)},
        "losses": {"program": list(driver.got["losses"]),
                   "reference": [float(x) for x in ref["losses"]]},
    }


def serve_readings(cell, seed: int, devices, seconds: float) -> dict:
    spans = Spans()
    driver = cell.driver.Driver(cell, seed, devices, spans)
    ran = driver.run(seconds, Window(spans, None))
    driver.release()
    got = driver.gaps(control=True)
    return {
        "program": {"served_logit_gap": got["served_logit_gap"],
                    "served_logit_gap_mean": got["served_logit_gap_mean"]},
        "control": {"served_logit_gap": got["control_logit_gap"],
                    "served_logit_gap_mean": got["control_logit_gap_mean"]},
        "checked_tokens": got["checked_tokens"],
        "requests_finished": ran["facts"]["requests_finished"],
        "tokens_per_s": ran["end_to_end"]["serve_tokens_per_s"],
    }


READERS = {"train": train_readings, "serve": serve_readings}


def verdicts(row: dict, limits: dict) -> dict[str, bool]:
    """``correct`` as a run would decide it, for the program, the control
    and each fault of ``row``."""
    groups = {"program": row["program"], "control": row["control"],
              **row.get("faults", {})}
    return {who: harness.verdict(compare.against_limits(numbers, limits))
            for who, numbers in groups.items()}


def main(argv=None, *, devices=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    harness.setup_compile_cache()
    if devices is None:
        devices = harness.require_chips(cell.chips)
    read = READERS[cell.config["driver"]]
    limits = compare.load_limits(cell.name)
    as_expected = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        row = {"workload": cell.name, "seed": seed,
               **read(cell, seed, devices, args.seconds)}
        row["correct"] = verdicts(row, limits)
        as_expected &= all(ok == (who == "program")
                           for who, ok in row["correct"].items())
        row["seconds"] = time.perf_counter() - t0
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())
