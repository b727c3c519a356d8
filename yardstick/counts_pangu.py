"""Operations and bytes openPangu-Ultra-MoE's algorithms need, from shapes
alone: the numerators of ``mfu.pangu``, ``routed_roofline.pangu`` and
``latent_attn_roofline.pangu``. As in ``yardstick/counts.py`` they count
what the mathematics needs and nothing the implementation adds: no padding
rows, no idle slots, no keys past a sequence's live length, and of a token's
eight assignments only those to experts this chip holds (the others are
another chip's work, and nothing here computes them). ``z`` is
``weights_pangu.sizes_of(config)``; two operations a multiply-add.

Attention is counted in the absorbed form, the least work a query needs
over a cache of latent rows: a live key costs its row (``kv_rank + rope``
numbers, read once) against every head's absorbed query and the latent
(``kv_rank``) into every head's weighted sum, ``2 h (row + kv_rank)``
operations; a query its projections and the two absorptions.
"""

from __future__ import annotations

from yardstick import counts


def latent_row(z: dict) -> int:
    """What a position keeps a layer: the latent and the rotary key."""
    return z["kv_rank"] + z["rope"]


def latent_key_flops(z: dict) -> int:
    """One live key against one query position, all heads: the score off
    the row and the latent into the weighted sum."""
    return 2 * z["h"] * (latent_row(z) + z["kv_rank"])


def mla_token_flops(z: dict) -> int:
    """One token of a latent attention layer, its keys aside: q_a, q_b,
    kv_a, the key half of kv_b into the query, the value half out of the
    weighted latent, o."""
    h = z["h"]
    return 2 * (z["d"] * z["q_rank"]
                + z["q_rank"] * h * (z["nope"] + z["rope"])
                + z["d"] * latent_row(z)
                + h * z["nope"] * z["kv_rank"]
                + h * z["kv_rank"] * z["v"]
                + h * z["v"] * z["d"])


def gated_ffn_flops(z: dict, width: int) -> int:
    """down(silu(gate) * up) at ``width``: three products."""
    return 2 * 3 * z["d"] * width


def expert_flops(z: dict) -> int:
    """One assignment to a held expert."""
    return gated_ffn_flops(z, z["eff"])


def routed_fixed_flops(z: dict) -> int:
    """One token of a routed layer, its assignments aside: the router over
    all ``E`` experts and the shared expert."""
    return 2 * z["d"] * z["E"] + gated_ffn_flops(z, z["sff"])


def trunk_flops(z: dict, *, tokens: int, keys: int) -> int:
    """Every layer's forward for ``tokens`` valid tokens whose queries
    attend ``keys`` live keys in all, the routed experts' own products
    aside (:func:`expert_flops` times the assignments that fell to held
    experts, which the engine counts)."""
    total = 0
    for _, ffn in z["layers"]:
        total += tokens * mla_token_flops(z) + keys * latent_key_flops(z)
        if ffn == "routed":
            total += tokens * routed_fixed_flops(z)
        else:
            total += tokens * gated_ffn_flops(z, z["ff"])
    return total


def token_flops(z: dict, *, position: int) -> int:
    """The trunk for one token at ``position`` (0-based) through the cache:
    it attends ``position + 1`` keys."""
    return trunk_flops(z, tokens=1, keys=position + 1)


def span_flops(z: dict, *, start: int, stop: int) -> int:
    """The trunk for the tokens at positions ``[start, stop)``."""
    return trunk_flops(z, tokens=stop - start,
                       keys=counts.causal_keys(stop)
                       - counts.causal_keys(start))


def head_flops(z: dict, rows: int = 1) -> int:
    return counts.head_flops(d=z["d"], vocab=z["vocab"], rows=rows)


# ---- layers of a decode launch (readers/scope_roofline.py) ----------------


def layers_of(z: dict, ffn: str) -> int:
    return sum(f == ffn for _, f in z["layers"])


def latent_attn(z: dict, *, rows: int, live_keys: float, itemsize: int = 2):
    """The latent attention kernel of one decode launch, ``(operations,
    bytes, layers)`` with the first two for ONE layer: ``live_keys``, the
    launch's sum of its rows' live lengths, each key's row read once
    (``row * itemsize`` bytes, 1,152 at the published sizes) and worked by
    every head (:func:`latent_key_flops`, 278,528); the rows' absorbed
    queries read and their weighted latents written once. Every latent
    layer reads its own rows."""
    h = z["h"]
    flops = live_keys * latent_key_flops(z)
    nbytes = (live_keys * latent_row(z) * itemsize
              + rows * h * (latent_row(z) + z["kv_rank"]) * itemsize)
    return flops, nbytes, len(z["layers"])


def routed_layer(z: dict, *, rows: int, experts_touched: float,
                 held_assignments: float, itemsize: int = 2):
    """The routed layers of one launch, ``(operations, bytes, layers)``
    with the first two for ONE layer: ``rows`` live tokens score all ``E``
    experts, ``held_assignments`` (the launch's, over its routed layers)
    fell to held experts, over ``experts_touched`` distinct held experts a
    layer. The touched experts' three matrices read once, the router's read
    once (float32), the rows read and written once. The shared expert is
    not the routed layer's (its scope is its own)."""
    layers = layers_of(z, "routed")
    flops = (rows * 2 * z["d"] * z["E"]
             + held_assignments / layers * expert_flops(z))
    weights = experts_touched * 3 * z["d"] * z["eff"] * itemsize
    router = z["d"] * z["E"] * 4
    return flops, weights + router + 2 * rows * z["d"] * itemsize, layers
