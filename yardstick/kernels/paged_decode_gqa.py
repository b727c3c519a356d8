"""The paged decode-attention kernel under grouped heads: the same kernel
of ``ops/decode_attention.py`` as ``kernels/paged_decode.py`` reads, with a
pool of ``kv`` heads that ``h // kv`` query heads share. Keys and values
are counted over the pool's heads, queries and outputs over the query
heads (``counts_lfm2.paged_decode``), and the kernel runs once a launch in
every attention layer, not in every layer."""

from __future__ import annotations

from yardstick import counts, counts_lfm2


def matches(op_name: str) -> bool:
    return "_paged_decode_attend" in op_name and " pallas:" in op_name


def least_seconds(facts: dict, events: list) -> float:
    z = facts["sizes"]
    layers = sum(mixer == "attention" for mixer, _ in z["layers"])
    launches = facts["decode_launches"]
    # the trace may open or close mid-launch: count the launches whose
    # kernels the trace holds, the latest ones
    held = len(events) // layers
    least = 0.0
    for rows, keys in launches[len(launches) - held:]:
        flops, nbytes = counts_lfm2.paged_decode(z, live_keys=keys, rows=rows)
        least += layers * counts.least_seconds(flops, nbytes, facts["peaks"])
    return least
