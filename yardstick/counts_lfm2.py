"""Operations and bytes LFM2's algorithms need, from shapes alone: the
numerators of ``mfu.sharegpt``, ``routed_roofline.sharegpt`` and
``paged_decode_roofline.sharegpt``. As in ``yardstick/counts.py`` they
count what the mathematics needs and nothing the implementation adds: no
padding rows, no idle slots, no keys past a sequence's live length, the
experts a token was routed to and no others. ``z`` is
``weights_lfm2.sizes_of(config)``; two operations a multiply-add.
"""

from __future__ import annotations

from yardstick import counts


def conv_mixer_flops(z: dict) -> int:
    """One token: in (d x 3d), out (d x d), ``taps`` taps on d channels."""
    d = z["d"]
    return 2 * (3 * d * d + d * d + z["taps"] * d)


def attention_projection_flops(z: dict) -> int:
    """One token: q, k, v out of one kernel, and the output projection."""
    width = z["h"] * z["hd"]
    return 2 * (z["d"] * (z["h"] + 2 * z["kv"]) * z["hd"] + width * z["d"])


def dense_ffn_flops(z: dict) -> int:
    return 3 * 2 * z["d"] * z["ff"]


def expert_flops(z: dict) -> int:
    """One assignment: the gated form at the expert's width."""
    return 3 * 2 * z["d"] * z["eff"]


def routed_ffn_flops(z: dict) -> int:
    """One token: the router over all experts, ``k`` experts' products."""
    return 2 * z["d"] * z["E"] + z["k"] * expert_flops(z)


def trunk_flops(z: dict, *, tokens: int, keys: int) -> int:
    """Every layer's forward for ``tokens`` valid tokens whose queries
    attend ``keys`` live keys in all (summed over the tokens)."""
    total = 0
    for mixer, ffn in z["layers"]:
        if mixer == "attention":
            total += (tokens * attention_projection_flops(z)
                      + counts.attention_flops(z["h"] * z["hd"], keys))
        else:
            total += tokens * conv_mixer_flops(z)
        total += tokens * (routed_ffn_flops(z) if ffn == "routed"
                           else dense_ffn_flops(z))
    return total


def token_flops(z: dict, *, position: int) -> int:
    """The trunk for one token at ``position`` (0-based) through the
    cache: it attends ``position + 1`` keys."""
    return trunk_flops(z, tokens=1, keys=position + 1)


def span_flops(z: dict, *, start: int, stop: int) -> int:
    """The trunk for the tokens at positions ``[start, stop)``."""
    return trunk_flops(z, tokens=stop - start,
                       keys=counts.causal_keys(stop)
                       - counts.causal_keys(start))


def head_flops(z: dict, rows: int = 1) -> int:
    return counts.head_flops(d=z["d"], vocab=z["vocab"], rows=rows)


# ---- kernels ----------------------------------------------------------


def routed_layer(z: dict, *, rows: int, experts_touched: float,
                 itemsize: int = 2) -> tuple[float, float]:
    """One routed layer of one launch: ``rows`` live tokens, ``k``
    assignments each, over ``experts_touched`` distinct experts. The
    touched experts' three matrices read once, the router's read once
    (float32), the rows read and written once."""
    flops = rows * routed_ffn_flops(z)
    weights = experts_touched * 3 * z["d"] * z["eff"] * itemsize
    router = z["d"] * z["E"] * 4
    return flops, weights + router + 2 * rows * z["d"] * itemsize


def paged_decode(z: dict, *, live_keys: int, rows: int,
                 itemsize: int = 2) -> tuple[int, int]:
    """One attention layer's decode for ``rows`` one-token queries over
    ``live_keys`` keys in all: keys and values over the ``kv`` pool heads
    read once, queries and outputs over the ``h`` query heads."""
    flops = counts.attention_flops(z["h"] * z["hd"], live_keys)
    kv = 2 * live_keys * z["kv"] * z["hd"] * itemsize
    return flops, kv + 2 * rows * z["h"] * z["hd"] * itemsize
