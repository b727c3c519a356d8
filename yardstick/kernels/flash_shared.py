"""What the three flash-attention kernel files share: how a trace labels
them (``attn.<n> pallas:<operands>-><results>``, see ``reduce.op_label``)
and the least time of ``n`` calls at the cell's shapes."""

from __future__ import annotations

from yardstick import counts


def matches(op_name: str, signature: str) -> bool:
    return op_name.startswith("attn.") and op_name.endswith(signature)


def least_seconds(count, facts: dict, events: list) -> float:
    z = facts["sizes"]
    flops, nbytes = count(batch=facts["batch"], heads=z["h"],
                          seq=facts["seq"], head_dim=z["hd"])
    return len(events) * counts.least_seconds(flops, nbytes, facts["peaks"])
