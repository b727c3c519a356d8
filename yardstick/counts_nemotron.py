"""Operations and bytes Nemotron-H's algorithms need, from shapes alone:
the numerators of ``mfu.nemotron``, ``routed_roofline.nemotron``,
``ssm_roofline.nemotron`` and (through ``counts_lfm2.paged_decode``, which
reads the same keys) ``paged_decode_roofline.nemotron``. As in
``yardstick/counts.py`` they count what the mathematics needs and nothing
the implementation adds: no padding rows, no idle slots, no keys past a
sequence's live length, an expert's published width and not the width its
bank is stored at, and of a token's six assignments only those to experts
this chip holds (the others are another chip's work, and nothing here
computes them). ``z`` is ``weights_nemotron.sizes_of(config)``; two
operations a multiply-add.
"""

from __future__ import annotations

from yardstick import counts


def mamba_mixer_flops(z: dict) -> int:
    """One token: in (d x (2 HP + 2 GN + H)), the taps on HP + 2 GN
    channels, the state's update and its read-out (a multiply-add each an
    element of the H x P x N state), the skip, out (HP x d)."""
    state = z["H"] * z["P"] * z["N"]
    return 2 * (z["d"] * (z["inner"] + z["wide"] + z["H"])
                + z["taps"] * z["wide"] + 2 * state + z["inner"]
                + z["inner"] * z["d"])


def attention_projection_flops(z: dict) -> int:
    """One token: q, k, v out of one kernel, and the output projection."""
    width = z["h"] * z["hd"]
    return 2 * (z["d"] * (z["h"] + 2 * z["kv"]) * z["hd"] + width * z["d"])


def plain_ffn_flops(z: dict, width: int) -> int:
    """down(relu(up)^2) at ``width``: two products."""
    return 2 * 2 * z["d"] * width


def expert_flops(z: dict) -> int:
    """One assignment to a held expert."""
    return plain_ffn_flops(z, z["eff"])


def routed_fixed_flops(z: dict) -> int:
    """One token of a routed layer, its assignments aside: the router over
    all ``E`` experts and the shared expert."""
    return 2 * z["d"] * z["E"] + plain_ffn_flops(z, z["sff"])


def trunk_flops(z: dict, *, tokens: int, keys: int) -> int:
    """Every layer's forward for ``tokens`` valid tokens whose queries
    attend ``keys`` live keys in all, the routed experts' own products
    aside (:func:`expert_flops` times the assignments that fell to held
    experts, which the engine counts)."""
    total = 0
    for mixer, ffn in z["layers"]:
        if mixer == "attention":
            total += (tokens * attention_projection_flops(z)
                      + counts.attention_flops(z["h"] * z["hd"], keys))
        elif mixer == "mamba2":
            total += tokens * mamba_mixer_flops(z)
        if ffn == "routed":
            total += tokens * routed_fixed_flops(z)
        elif ffn == "dense":
            total += tokens * plain_ffn_flops(z, z["ff"])
    return total


def token_flops(z: dict, *, position: int) -> int:
    """The trunk for one token at ``position`` (0-based) through the cache
    and the state: it attends ``position + 1`` keys."""
    return trunk_flops(z, tokens=1, keys=position + 1)


def span_flops(z: dict, *, start: int, stop: int) -> int:
    """The trunk for the tokens at positions ``[start, stop)``."""
    return trunk_flops(z, tokens=stop - start,
                       keys=counts.causal_keys(stop)
                       - counts.causal_keys(start))


def head_flops(z: dict, rows: int = 1) -> int:
    return counts.head_flops(d=z["d"], vocab=z["vocab"], rows=rows)


# ---- layers of a decode launch (readers/scope_roofline.py) ----------------


def layers_of(z: dict, kind: str) -> int:
    return sum(kind in kinds for kinds in z["layers"])


def routed_layer(z: dict, *, rows: int, experts_touched: float,
                 held_assignments: float, itemsize: int = 2):
    """The routed layers of one launch, ``(operations, bytes, layers)``
    with the first two for ONE layer: ``rows`` live tokens score all ``E``
    experts, ``held_assignments`` (the launch's, over its routed layers)
    fell to held experts, over ``experts_touched`` distinct held experts a
    layer. The touched experts' two matrices read once at the published
    width, the router's read once (float32), the rows read and written
    once. The shared expert is not the routed layer's (its scope is its
    own)."""
    layers = layers_of(z, "routed")
    flops = (rows * 2 * z["d"] * z["E"]
             + held_assignments / layers * expert_flops(z))
    weights = experts_touched * 2 * z["d"] * z["eff"] * itemsize
    router = z["d"] * z["E"] * 4
    return flops, weights + router + 2 * rows * z["d"] * itemsize, layers


def ssm_layer(z: dict, *, rows: int, itemsize: int = 2):
    """The Mamba-2 layers of one decode launch, ``(operations, bytes,
    layers)`` with the first two for ONE layer: ``rows`` live rows' state
    matrices (float32) and convolution inputs read and written once, the
    mixer's weights read once, the rows in and out."""
    state = z["H"] * z["P"] * z["N"] * 4 + (z["taps"] - 1) * z[
        "wide"] * itemsize
    weights = (z["d"] * (z["inner"] + z["wide"] + z["H"])
               + z["inner"] * z["d"] + (z["taps"] + 1) * z["wide"]
               + z["inner"]) * itemsize + 3 * z["H"] * 4
    nbytes = rows * 2 * state + weights + 2 * rows * z["d"] * itemsize
    return rows * mamba_mixer_flops(z), nbytes, layers_of(z, "mamba2")
