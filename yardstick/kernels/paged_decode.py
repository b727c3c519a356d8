"""The paged decode-attention kernel of ``ops/decode_attention.py``. Its
work differs from launch to launch: the driver reports, for each decode
launch of the window, the rows that decoded and the live keys they had to
read (exact lengths, not rounded up to blocks), and every layer runs the
kernel once a launch."""

from __future__ import annotations

from yardstick import counts

NAMES = ("attn._paged_decode_attend",)


def matches(op_name: str) -> bool:
    return op_name.startswith(NAMES) and " pallas:" in op_name


def least_seconds(facts: dict, events: list) -> float:
    z = facts["sizes"]
    launches = facts["decode_launches"]
    # the trace may open or close mid-launch: count the launches whose
    # kernels the trace holds, the latest ones
    held = len(events) // z["L"]
    least = 0.0
    for rows, keys in launches[len(launches) - held:]:
        flops, nbytes = counts.paged_decode(
            live_keys=keys, rows=rows, heads=z["h"], head_dim=z["hd"])
        least += z["L"] * counts.least_seconds(flops, nbytes, facts["peaks"])
    return least
