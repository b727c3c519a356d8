"""LFM2 (``model_type`` ``lfm2_moe``) in plain ``jax.numpy`` and float32: the
reference. No kernels, no cache, no batching of requests, nothing imported
from the program under test and nothing taken from it: the weights come
from ``yardstick.weights_lfm2`` and the seed, and the routing is the
reference's own. Matrix products run at ``Precision.HIGHEST`` (a TPU
multiplies float32 in bfloat16 passes unless told otherwise).

The equations, from the published ``config.json``'s keys:

* layer: ``h = x + mixer(RMSNorm(x))``, ``y = h + ffn(RMSNorm(h))``; after
  the last layer one RMSNorm, then the output head; no learned positions;
* ``conv`` mixer: ``[B, C, u] = split3(W_in x)``, ``z = B * u``, ``c_t =
  sum_j w[:, j] * z_{t - (K - 1) + j}`` (depthwise, causal, zeros before
  position 0), ``out = W_out (C * c)``;
* ``full_attention`` mixer: grouped heads, RMSNorm over the head size on
  every query and key head, then rotary positions over the whole head
  (halves ``[x1, x2]`` turn as ``x cos + [-x2, x1] sin``), causal softmax
  at ``1 / sqrt(head size)``, no biases;
* dense feed-forward: ``W_2 (silu(W_1 h) * W_3 h)``;
* routed feed-forward: ``s = sigmoid(W_r h)`` in float32; the experts
  chosen are the top ``k`` of ``s + b`` (``b`` takes no part in the
  weights); weights ``s[chosen] / (sum s[chosen] + 1e-6)``; **every expert
  is computed for every token** and weighted by the routing mask (zero off
  the chosen): the plain way, and why the reference is slow.

Departures from the published model, stated in the configuration's file:
the output head is a matrix of its own (not the embedding transposed).

``operands`` chooses the precision the projections multiply in:
``"float32"`` is the reference; ``"int8"`` rounds both operands of every
projection product (the mixers', the feed-forwards', every expert's) to 8
bits with one scale a tensor: the control, the nearest precision below the
bfloat16 the configuration states; ``"bfloat16"`` rounds them to bfloat16,
which is how the program multiplies, and is used only to count how many
routing choices that rounding alone moves. The router is float32 in all.

At the cell's size the float32 weights are 21 GB: :func:`trunk` draws one
layer's at a time, inside the compiled layer, and sends all the sampled
requests through that layer together.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from yardstick import weights_lfm2 as W

HIGHEST = lax.Precision.HIGHEST


def _int8(t):
    """``t`` rounded to 8 bits, one scale for the tensor."""
    amax = jnp.max(jnp.abs(t))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(t / scale), -127, 127) * scale


def _dot(x, w, operands: str):
    if operands == "int8":
        x, w = _int8(x), _int8(w)
    elif operands == "bfloat16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    elif operands != "float32":
        raise ValueError(f"operands {operands!r}: float32, bfloat16 or int8")
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * gain


def conv_mixer(x, p, *, operands: str):
    """(S, d) -> (S, d)."""
    S, taps = x.shape[0], p["conv_w"].shape[1]
    gate_b, gate_c, u = jnp.split(_dot(x, p["in_w"], operands), 3, axis=-1)
    z = jnp.pad(gate_b * u, ((taps - 1, 0), (0, 0)))
    c = sum(p["conv_w"][:, j] * z[j:j + S] for j in range(taps))
    return _dot(gate_c * c, p["out_w"], operands)


def rotate(x, theta: float):
    """(S, H, hd) at positions 0..S-1."""
    S, _, hd = x.shape
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention_mixer(x, p, *, eps: float, theta: float, operands: str):
    """(S, d) -> (S, d)."""
    S, d = x.shape
    h, hd, _ = p["proj_w"].shape
    kv = (p["qkv_w"].shape[1] - h) // 2
    qkv = _dot(x, p["qkv_w"].reshape(d, -1), operands).reshape(S, -1, hd)
    q, k, v = qkv[:, :h], qkv[:, h:h + kv], qkv[:, h + kv:]
    q = rotate(rms_norm(q, p["qn_g"], eps), theta)
    k = rotate(rms_norm(k, p["kn_g"], eps), theta)
    k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / hd ** 0.5
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], scores,
                       -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v,
                     precision=HIGHEST)
    return _dot(att.reshape(S, h * hd), p["proj_w"].reshape(h * hd, d),
                operands)


def gated(x, w_gate, w_up, w_down, operands: str):
    return _dot(jax.nn.silu(_dot(x, w_gate, operands))
                * _dot(x, w_up, operands), w_down, operands)


def routing(x, p, *, k: int):
    """(T, d) -> the mask of weights (T, E), zero off the k chosen."""
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router_w"], precision=HIGHEST))
    _, chosen = lax.top_k(scores + p["bias"], k)
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    picked = picked / (jnp.sum(picked, axis=1, keepdims=True) + 1e-6)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(picked)


def routed_ffn(x, p, *, k: int, operands: str, first: int = 0,
               count: int | None = None):
    """(T, d) -> the part of the layer's output that the experts ``[first,
    first + count)`` give (all of them by default), and the routing mask.
    Every one of those experts is computed for every token."""
    mask = routing(x, p, k=k)
    count = p["e_gate"].shape[0] - first if count is None else count
    banks = tuple(lax.dynamic_slice_in_dim(p[n], first, count, axis=0)
                  for n in ("e_gate", "e_up", "e_down"))
    weights = lax.dynamic_slice_in_dim(mask, first, count, axis=1)

    def one(y, expert):
        w_gate, w_up, w_down, weight = expert
        return y + weight[:, None] * gated(x, w_gate, w_up, w_down,
                                           operands), None

    y, _ = lax.scan(one, jnp.zeros_like(x), (*banks, weights.T))
    return y, mask


def layer(x, p, kinds: tuple[str, str], sizes: dict, operands: str):
    """One layer on (R, S, d): every request through it, one at a time
    through the mixer, all tokens together through the feed-forward.
    Returns the output and the routing mask (R * S, E), or None."""
    mixer, ffn = kinds
    eps = sizes["eps"]
    a = rms_norm(x, p["ln1_g"], eps)
    if mixer == "attention":
        mixed = lax.map(lambda s: attention_mixer(
            s, p, eps=eps, theta=sizes["theta"], operands=operands), a)
    else:
        mixed = lax.map(lambda s: conv_mixer(s, p, operands=operands), a)
    x = x + mixed
    m = rms_norm(x, p["ln2_g"], eps).reshape(-1, x.shape[-1])
    if ffn == "routed":
        out, mask = routed_ffn(m, p, k=sizes["k"], operands=operands)
    else:
        out, mask = gated(m, p["gate_w"], p["up_w"], p["down_w"],
                          operands), None
    return x + out.reshape(x.shape), mask


def make_trunk(sizes: dict, operands: str = "float32"):
    """``trunk(seed, tokens (R, S)) -> (hidden (R, S, d) after the last
    RMSNorm, [routing mask (R * S, E) of each routed layer])``, the weights
    of one layer at a time drawn inside that layer's compiled program."""
    @jax.jit
    def embed(seed, tokens):
        return W.top_leaves(W.seed_key(seed), sizes, ("wte",))["wte"][tokens]

    @jax.jit
    def last_norm(seed, x):
        gain = W.top_leaves(W.seed_key(seed), sizes, ("lnf_g",))["lnf_g"]
        return rms_norm(x, gain, sizes["eps"])

    steps = {kinds: jax.jit(
        lambda seed, i, x, kinds=kinds: layer(
            x, W.layer_leaves(W.seed_key(seed), sizes, i, kinds), kinds,
            sizes, operands)) for kinds in set(sizes["layers"])}

    def trunk(seed, tokens):
        seed = W.seed_arg(seed)
        x, masks = embed(seed, tokens), []
        for i, kinds in enumerate(sizes["layers"]):
            x, mask = steps[kinds](seed, jnp.int32(i), x)
            if mask is not None:
                masks.append(mask)
        return last_norm(seed, x), masks

    return trunk


def make_head(sizes: dict):
    """``head(seed, hid (R, S, d)) -> fn over requests``: float32 logits of
    one request at a time (a batch of them would be gigabytes)."""
    @jax.jit
    def logits(seed, hid):
        w = W.top_leaves(W.seed_key(seed), sizes, ("head_w",))["head_w"]
        return jnp.matmul(hid, w, precision=HIGHEST)

    return lambda seed, hid: logits(W.seed_arg(seed), hid)


def forward(seed, tokens, sizes: dict, operands: str = "float32"):
    """(S,) token ids -> (S, vocab) float32 logits: one request's full
    forward pass (what the tests compare the engine with)."""
    hid, _ = make_trunk(sizes, operands)(seed, jnp.asarray(tokens)[None])
    return make_head(sizes)(seed, hid[0])


def served_gaps(seed, tokens, lengths, firsts, sizes: dict, *,
                control: bool = False):
    """The sampled requests against the reference. ``tokens`` (R, S): each
    row a prompt followed by what was served, padded; ``lengths`` counts
    the real ones; ``firsts`` is the index of each row's first served
    token. Returns ``gap`` (R, S - 1): for every served position how far
    the served token's logit lies below the reference's best (0 where it is
    the reference's own choice; positions not served read 0). With
    ``control`` also ``control_gap``, the same for the token that int8
    operands would put first there, and ``choices_moved`` /
    ``choices_checked``: over the served positions and the routed layers,
    how many (token, layer) sets of chosen experts differ between bfloat16
    operands and float32 (what rounding as the program rounds moves; the
    program's own choices are not handed out of the engine)."""
    tokens = jnp.asarray(tokens)
    S = tokens.shape[1]
    pos = jnp.arange(1, S)
    live = ((pos[None] >= jnp.asarray(firsts)[:, None])
            & (pos[None] < jnp.asarray(lengths)[:, None]))
    head = make_head(sizes)
    hid, masks = make_trunk(sizes)(seed, tokens)
    low = None
    if control:
        low, _ = make_trunk(sizes, "int8")(seed, tokens)
        _, rounded = make_trunk(sizes, "bfloat16")(seed, tokens)

    @jax.jit
    def below_best(ref, chosen, keep):
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
        return jnp.where(keep, best - got, 0.0)

    out = {"gap": [], "control_gap": []}
    for r in range(tokens.shape[0]):
        ref = head(seed, hid[r, :-1])  # row i predicts tokens[i + 1]
        out["gap"].append(below_best(ref, tokens[r, 1:], live[r]))
        if control:
            first = jnp.argmax(head(seed, low[r, :-1]), axis=-1)
            out["control_gap"].append(below_best(
                ref, first.astype(tokens.dtype), live[r]))
    got = {"gap": jnp.stack(out["gap"])}
    if control:
        got["control_gap"] = jnp.stack(out["control_gap"])
        # row i of a mask routes the token at position i: served positions
        # are 1..S-1 of each request
        served = jnp.pad(live, ((0, 0), (1, 0))).reshape(-1)
        moved = sum(jnp.sum(served & jnp.any((a > 0) != (b > 0), axis=1))
                    for a, b in zip(masks, rounded))
        got["choices_moved"] = moved
        got["choices_checked"] = jnp.sum(served) * len(masks)
    return got
