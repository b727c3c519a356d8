#!/usr/bin/env python3
"""``yardstick/control.py`` for the cells of the ``serve_pangu`` driver.

    python3 yardstick/control_pangu.py --workload <name> --seeds 1,2,3 \\
        --seconds 40 [--faults 0|1] [--out file.jsonl]

``control.py`` finds a cell's readings by its driver's name and knows
``train`` and ``serve``; this file gives it ``serve_pangu`` and is otherwise
``control.main``: the same groups, limits, verdicts and exit code. Beside
the program and the int8 control the row holds three ``faults``, each
planted in the reference put in the program's place
(``reference/pangu_moe.py``): the rotary parts of the queries and the key
zeroed, the key-value latent left unnormalised, the sandwich's post-norms
skipped. Every one has to come out not correct. ``--faults 0`` reads the
program alone, for the seeds a limit's upper side needs (each trunk of the
reference is a pass over the sample; the program's reading needs one);
such a row holds no control and no fault. Pass ``--seconds``: the default
window of 0 s finishes nothing to check.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from yardstick import compare, control, harness  # noqa: E402
from yardstick.reference.pangu_moe import FAULTS  # noqa: E402
from yardstick.run import Window  # noqa: E402
from yardstick.spans import Spans  # noqa: E402

#: ``--faults``, taken out of the arguments before ``control.main`` reads
#: them
WITH_FAULTS = True


def readings(cell, seed: int, devices, seconds: float) -> dict:
    spans = Spans()
    driver = cell.driver.Driver(cell, seed, devices, spans)
    ran = driver.run(seconds, Window(spans, None))
    driver.release()
    got = driver.gaps(control=WITH_FAULTS,
                      faults=FAULTS if WITH_FAULTS else ())
    row = {
        "program": {"served_logit_gap": got["served_logit_gap"],
                    "served_logit_gap_mean": got["served_logit_gap_mean"]},
        "checked_tokens": got["checked_tokens"],
        "requests_finished": ran["facts"]["requests_finished"],
        "tokens_per_s": ran["end_to_end"]["serve_tokens_per_s"],
    }
    if WITH_FAULTS:
        row["control"] = {
            "served_logit_gap": got["control_logit_gap"],
            "served_logit_gap_mean": got["control_logit_gap_mean"]}
        row["faults"] = {fault: {
            "served_logit_gap": got[f"{fault}_gap"],
            "served_logit_gap_mean": got[f"{fault}_gap_mean"]}
            for fault in FAULTS}
        row["choices_moved"] = got["choices_moved"]
        row["choices_checked"] = got["choices_checked"]
    return row


def program_verdict(row: dict, limits: dict) -> dict[str, bool]:
    """``control.verdicts`` for a row that holds the program alone."""
    return {"program": harness.verdict(
        compare.against_limits(row["program"], limits))}


def main(argv=None, *, devices=None) -> int:
    global WITH_FAULTS
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--faults" in argv:
        at = argv.index("--faults")
        WITH_FAULTS = argv[at + 1] != "0"
        del argv[at:at + 2]
    control.READERS["serve_pangu"] = readings
    if not WITH_FAULTS:
        control.verdicts = program_verdict
    return control.main(argv, devices=devices)


if __name__ == "__main__":
    sys.exit(main())
