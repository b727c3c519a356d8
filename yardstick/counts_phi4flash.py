"""Operations and bytes Phi-4-mini-flash's algorithms need, from shapes
alone: the numerators of ``mfu.phi4flash``, ``ssm_roofline.phi4flash``,
``window_attn_roofline.phi4flash`` and ``shared_kv_roofline.phi4flash``. As
in ``yardstick/counts.py`` they count what the mathematics needs and nothing
the implementation adds: no padding rows, no idle slots, no keys past a
sequence's live length or before a window, each key and value read once by
each layer that attends it (a pair's two heads are not read twice), and the
pair's weighted sum taken once, over ``A1 - lambda A2``. ``z`` is
``weights_phi4flash.sizes_of(config)``; two operations a multiply-add.

The full-attention layer's cache is counted once for EACH of the layers
that read it (the layer itself and every cross layer): a later program that
reads it fewer times a launch has done less work, not the same work
faster, and its share of this roofline may then pass what one pass allows.
"""

from __future__ import annotations

from yardstick import counts


def layers_of(z: dict, kind: str) -> int:
    return sum(mixer == kind for mixer, _ in z["layers"])


def mamba_mixer_flops(z: dict) -> int:
    """One token: in (d x 2D), the taps on D channels, ``x_proj`` (D x (R +
    2N)), ``dt_proj`` (R x D), the state's update and its read-out (a
    multiply-add each an element of the D x N state), the skip, out (D x
    d)."""
    D, N, R = z["inner"], z["N"], z["R"]
    return 2 * (z["d"] * 2 * D + z["taps"] * D + D * (R + 2 * N) + R * D
                + 2 * D * N + D + D * z["d"])


def projection_flops(z: dict, kind: str) -> int:
    """One token of an attention layer of ``kind``: its first projection
    (q, k, v out of one kernel; a cross layer's q alone) and the output
    projection."""
    width = z["h"] * z["hd"]
    first = z["h"] if kind == "cross_attention" else z["h"] + 2 * z["kv"]
    return 2 * (z["d"] * first * z["hd"] + width * z["d"])


def gmu_flops(z: dict) -> int:
    """One token: in (d x D), the gate by the memory, out (D x d)."""
    return 2 * (2 * z["d"] * z["inner"] + z["inner"])


def ffn_flops(z: dict) -> int:
    """down(silu(gate) * up): three products."""
    return 2 * 3 * z["d"] * z["ff"]


def window_keys(z: dict, *, start: int, stop: int) -> int:
    """Keys the queries at positions ``[start, stop)`` see in a window
    layer: ``min(t + 1, window)`` each."""
    w = z["window"]
    short = range(start, min(stop, w))  # queries with fewer than w keys
    return sum(t + 1 for t in short) + w * max(0, stop - max(start, w))


def trunk_flops(z: dict, *, tokens: int, keys: int, in_window: int) -> int:
    """Every layer's forward for ``tokens`` valid tokens whose queries
    attend ``keys`` live keys in all in the full layer (and in each layer
    that reads its cache) and ``in_window`` in a window layer."""
    width = z["h"] * z["hd"]
    total = tokens * z["L"] * ffn_flops(z)
    for mixer, _ in z["layers"]:
        if mixer == "mamba1":
            total += tokens * mamba_mixer_flops(z)
        elif mixer == "gmu":
            total += tokens * gmu_flops(z)
        else:
            seen = in_window if mixer == "window_attention" else keys
            total += (tokens * projection_flops(z, mixer)
                      + counts.attention_flops(width, seen))
    return total


def token_flops(z: dict, *, position: int) -> int:
    """The trunk for one token at ``position`` (0-based) through the
    cache, the rings and the state."""
    return trunk_flops(z, tokens=1, keys=position + 1,
                       in_window=min(position + 1, z["window"]))


def span_flops(z: dict, *, start: int, stop: int) -> int:
    """The trunk for the tokens at positions ``[start, stop)``."""
    return trunk_flops(z, tokens=stop - start,
                       keys=counts.causal_keys(stop)
                       - counts.causal_keys(start),
                       in_window=window_keys(z, start=start, stop=stop))


def head_flops(z: dict, rows: int = 1) -> int:
    return counts.head_flops(d=z["d"], vocab=z["vocab"], rows=rows)


# ---- layers of a decode launch (readers/scope_roofline.py) ----------------


def key_bytes(z: dict, itemsize: int = 2) -> int:
    """One position of one attention layer's cache: a key and a value of
    every key head, each once."""
    return 2 * z["kv"] * z["hd"] * itemsize


def ssm_layer(z: dict, *, rows: int, itemsize: int = 2):
    """The Mamba-1 layers of one decode launch, ``(operations, bytes,
    layers)`` with the first two for ONE layer: ``rows`` live rows' state
    (float32) and convolution inputs read and written once, the mixer's
    weights read once (the decay rates, the skip and the step bias in
    float32), the rows in and out."""
    D, N, R = z["inner"], z["N"], z["R"]
    state = D * N * 4 + (z["taps"] - 1) * D * itemsize
    weights = (z["d"] * 2 * D + D * (R + 2 * N) + R * D + D * z["d"]
               + (z["taps"] + 1) * D) * itemsize + (D * N + 2 * D) * 4
    nbytes = rows * 2 * state + weights + 2 * rows * z["d"] * itemsize
    return rows * mamba_mixer_flops(z), nbytes, layers_of(z, "mamba1")


def _attention_layer(z: dict, kind: str, *, rows: int, seen: float,
                     itemsize: int):
    """One attention layer of ``kind`` of one decode launch: its
    projections' weights read once, ``seen`` keys and values in all read
    once, the rows' new key and value written (not a cross layer's), the
    rows in and out."""
    width = z["h"] * z["hd"]
    flops = rows * projection_flops(z, kind) + counts.attention_flops(
        width, seen)
    weights = projection_flops(z, kind) // 2 * itemsize
    wrote = 0 if kind == "cross_attention" else rows * key_bytes(z, itemsize)
    return flops, (weights + seen * key_bytes(z, itemsize) + wrote
                   + 2 * rows * z["d"] * itemsize)


def window_layer(z: dict, *, rows: int, window_keys: float,
                 itemsize: int = 2):
    """The window layers of one decode launch, ``(operations, bytes,
    layers)`` with the first two for ONE layer: ``window_keys`` is the
    launch's sum over its rows of ``min(length, window)``."""
    flops, nbytes = _attention_layer(z, "window_attention", rows=rows,
                                     seen=window_keys, itemsize=itemsize)
    return flops, nbytes, layers_of(z, "window_attention")


def shared_kv_layers(z: dict, *, rows: int, live_keys: float,
                     itemsize: int = 2):
    """The full-attention layer and the cross layers that read its cache,
    of one decode launch, ``(operations, bytes, layers)``: ``live_keys``
    is the launch's sum of its rows' live lengths, read once by EACH of the
    layers. The first two are the mean over the layers (a cross layer has
    no key and value projection and writes nothing): every one of them is
    bound by its bytes at a decode launch's size, where the mean of the
    least times is the least time of the means."""
    kinds = ["attention"] * layers_of(z, "attention") + [
        "cross_attention"] * layers_of(z, "cross_attention")
    each = [_attention_layer(z, kind, rows=rows, seen=live_keys,
                             itemsize=itemsize) for kind in kinds]
    n = len(each)
    return (sum(f for f, _ in each) / n, sum(b for _, b in each) / n, n)
