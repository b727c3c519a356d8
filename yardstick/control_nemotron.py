#!/usr/bin/env python3
"""``yardstick/control.py`` for the cells of the ``serve_nemotron`` driver.

    python3 yardstick/control_nemotron.py --workload <name> --seeds 1,2,3 \\
        --seconds 40 [--out file.jsonl]

``control.py`` finds a cell's readings by its driver's name and knows
``train`` and ``serve``; ``control_lfm2.readings`` reads any serving driver
whose ``gaps(control=True)`` also counts the routing choices that rounding
moves, which this driver's does, so this file gives ``control.main`` that
function under this driver's name: the same groups, limits, verdicts and
exit code. Pass ``--seconds``: the default window of 0 s finishes nothing
to check.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from yardstick import control, control_lfm2  # noqa: E402

readings = control_lfm2.readings

if __name__ == "__main__":
    control.READERS["serve_nemotron"] = readings
    sys.exit(control.main())
