#!/usr/bin/env python3
"""``yardstick/control.py`` for the cells of the ``serve_lfm2`` driver.

    python3 yardstick/control_lfm2.py --workload <name> --seeds 1,2,3 \\
        [--seconds s] [--out file.jsonl]

``control.py`` finds a cell's readings by its driver's name and knows
``train`` and ``serve``; this file gives it ``serve_lfm2`` and is otherwise
``control.main``: the same groups, limits, verdicts and exit code. The row
also says how many of the checked (token, layer) routing choices the
rounding to bfloat16 moves (``reference/lfm2.served_gaps``).
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from yardstick import control  # noqa: E402
from yardstick.run import Window  # noqa: E402
from yardstick.spans import Spans  # noqa: E402


def readings(cell, seed: int, devices, seconds: float) -> dict:
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
        "choices_moved": got["choices_moved"],
        "choices_checked": got["choices_checked"],
        "requests_finished": ran["facts"]["requests_finished"],
        "tokens_per_s": ran["end_to_end"]["serve_tokens_per_s"],
    }


if __name__ == "__main__":
    control.READERS["serve_lfm2"] = readings
    sys.exit(control.main())
