#!/usr/bin/env python3
"""``yardstick/control.py`` for the cells of the ``serve_phi4flash`` driver.

    python3 yardstick/control_phi4flash.py --workload <name> --seeds 1,2,3 \\
        --seconds 40 [--out file.jsonl]

``control.py`` finds a cell's readings by its driver's name and knows
``train`` and ``serve``; this file gives it ``serve_phi4flash`` and is
otherwise ``control.main``: the same groups, limits, verdicts and exit
code. Beside the program and the int8 control the row holds three
``faults``, each planted in the reference put in the program's place
(``reference/phi4flash.py``): the ``lambda A2`` term of differential
attention dropped, the window layers seeing the whole sequence, the gated
memory units handed zeros. Every one has to come out not correct. Pass
``--seconds``: the default window of 0 s finishes nothing to check.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from yardstick import control  # noqa: E402
from yardstick.reference.phi4flash import FAULTS  # noqa: E402
from yardstick.run import Window  # noqa: E402
from yardstick.spans import Spans  # noqa: E402


def readings(cell, seed: int, devices, seconds: float) -> dict:
    spans = Spans()
    driver = cell.driver.Driver(cell, seed, devices, spans)
    ran = driver.run(seconds, Window(spans, None))
    driver.release()
    got = driver.gaps(control=True, faults=FAULTS)
    return {
        "program": {"served_logit_gap": got["served_logit_gap"],
                    "served_logit_gap_mean": got["served_logit_gap_mean"]},
        "control": {"served_logit_gap": got["control_logit_gap"],
                    "served_logit_gap_mean": got["control_logit_gap_mean"]},
        "faults": {fault: {
            "served_logit_gap": got[f"{fault}_gap"],
            "served_logit_gap_mean": got[f"{fault}_gap_mean"]}
            for fault in FAULTS},
        "checked_tokens": got["checked_tokens"],
        "requests_finished": ran["facts"]["requests_finished"],
        "tokens_per_s": ran["end_to_end"]["serve_tokens_per_s"],
    }


if __name__ == "__main__":
    control.READERS["serve_phi4flash"] = readings
    sys.exit(control.main())
