"""openPangu-Ultra-MoE (``model_type`` ``pangu_ultra_moe``) in plain
``jax.numpy`` and float32: the reference. No kernels, no cache, no chunks,
no batching of requests, nothing imported from the program under test and
nothing taken from it: the weights come from ``yardstick.weights_pangu`` and
the seed, and the routing is the reference's own. Matrix products run at
``Precision.HIGHEST`` (a TPU multiplies float32 in bfloat16 passes unless
told otherwise), a weight's written out as the passes that are not zero
(:func:`_dot`).

The equations, from the published ``config.json``'s keys and DeepSeek-V2's
multi-head latent attention (arXiv:2405.04434, section 2.1), which this
family follows. Every layer is two halves, each between two RMSNorms at
``rms_norm_eps`` (``sandwich_norm``): ``x <- x + N_post(f(N_pre(x)))``,
first the attention, then the feed-forward; after the last layer one
RMSNorm, then the output head (not tied).

* attention, in the EXPANDED form (each head's keys and values made from
  the latent, as the equations write it; the program attends over the
  latents themselves): ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` split a
  head into ``q_nope`` (``qk_nope_head_dim``) and ``q_rope``
  (``qk_rope_head_dim``); ``[c_kv | k_r] = x W_kva``, ``c_kv =
  RMSNorm(c_kv)``; ``[k_nope | v] = c_kv W_kvb`` a head; ``q_rope`` and
  ``k_r`` turned by their positions at ``rope_theta`` (the halves form),
  ``k_r`` one key for all heads; ``softmax(([q_nope | q_rope] . [k_nope |
  k_r]) / sqrt(nope + rope))`` causal over the keys, weighing ``v``;
  ``out = concat_h(o_h) W_o``. Queries go in blocks of ``QUERY_BLOCK``;
* the leading dense layer: ``W_down (silu(W_gate h) * W_up h)``;
* routed: ``s = sigmoid(W_r h)`` in float32; the experts chosen are the
  top ``k`` of ``s + b`` (``b`` takes no part in the weights; no group
  limit); weights ``s[chosen] / (sum s[chosen] + 1e-20) *
  routed_scaling_factor``; an expert is the gated form at
  ``moe_intermediate_size``; **every held expert is computed for every
  token** and weighted by the routing mask (zero off the chosen). Plus the
  shared expert of the same form for every token. Of the layer's experts
  this chip holds ``[first, first + held)``: assignments to the others add
  nothing, here as in the program, and nothing stands in for them.

``operands`` chooses the precision the products multiply in: ``"float32"``
is the reference; ``"int8"`` rounds both operands of every projection
product (the five of the attention, the feed-forwards', every expert's) to
8 bits with one scale a tensor: the control, the nearest precision below
the bfloat16 the configuration states; ``"bfloat16"`` rounds them to
bfloat16, which is how the program multiplies, and is used only to count
how many routing choices that rounding alone moves. The router and the
softmax are float32 in all three.

``fault`` plants one error in the reference, put in the program's place to
show that the limit would catch it: ``"no_rope"`` (``q_rope`` and ``k_r``
zeroed), ``"no_kv_norm"`` (``c_kv`` left unnormalised), ``"no_post_norm"``
(the sandwich's two post-norms skipped).

At the cell's size the float32 weights are 13.6 GB: :func:`make_trunk`
draws one layer's at a time, inside the compiled layer, and sends the
sampled requests through that layer a request at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from yardstick import weights_pangu as W

HIGHEST = lax.Precision.HIGHEST
FAULTS = ("no_rope", "no_kv_norm", "no_post_norm")
QUERY_BLOCK = 64


def _int8(t):
    """``t`` rounded to 8 bits, one scale for the tensor."""
    amax = jnp.max(jnp.abs(t))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(t / scale), -127, 127) * scale


def _rounded(t, operands: str):
    if operands == "int8":
        return _int8(t)
    if operands == "bfloat16":
        return t.astype(jnp.bfloat16).astype(jnp.float32)
    if operands != "float32":
        raise ValueError(f"operands {operands!r}: float32, bfloat16 or int8")
    return t


def _dot(x, w, operands: str):
    """``x @ w`` for a weight ``w``. In float32 the weight's numbers are
    bfloat16's (``weights_pangu``), so the product is written out as what
    ``Precision.HIGHEST`` makes of it on a TPU less the passes that multiply
    the weight's zero low parts: ``x`` split into three bfloat16 parts,
    each against the weight, accumulated in float32 (every pairwise
    product exact). Elsewhere both operands are rounded and multiplied at
    ``HIGHEST``."""
    if operands != "float32":
        return jnp.matmul(_rounded(x, operands), _rounded(w, operands),
                          precision=HIGHEST)
    weight, rest, out = w.astype(jnp.bfloat16), x, 0.0
    for _ in range(3):
        part = rest.astype(jnp.bfloat16)
        rest = rest - part.astype(jnp.float32)
        out = out + jnp.matmul(part, weight,
                               preferred_element_type=jnp.float32)
    return out


def rms_norm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * gain


def rope(x, positions, theta):
    """``x`` (..., S, n, r) turned by ``positions`` (S,): the halves ``[x1,
    x2]`` go to ``[x1 cos - x2 sin, x2 cos + x1 sin]`` at angles ``position *
    theta ** (-2i / r)``."""
    r = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def latent_attention(x, p, *, sizes: dict, operands: str, fault=None):
    """(S, d) -> (S, d), the expanded form; each block of queries is
    projected where it is attended."""
    S, d = x.shape
    h, nope, rope_w, vd = sizes["h"], sizes["nope"], sizes["rope"], sizes["v"]
    rank, eps = sizes["kv_rank"], sizes["eps"]
    c_q = rms_norm(_dot(x, p["q_a_w"], operands), p["q_a_norm_g"], eps)
    kv = _dot(x, p["kv_a_w"], operands)
    c_kv, k_r = kv[:, :rank], kv[:, rank:]
    if fault != "no_kv_norm":
        c_kv = rms_norm(c_kv, p["kv_a_norm_g"], eps)
    pos = jnp.arange(S)
    k_r = rope(k_r[:, None], pos, sizes["theta"])[:, 0]
    up = _dot(c_kv, p["kv_b_w"].reshape(rank, -1),
              operands).reshape(S, h, nope + vd)
    k_nope, values = up[:, :, :nope], up[:, :, nope:]
    scale = (nope + rope_w) ** -0.5
    blocks = -(-S // QUERY_BLOCK)
    c_q = jnp.pad(c_q, ((0, blocks * QUERY_BLOCK - S), (0, 0)))

    def block(i):
        q = _dot(lax.dynamic_slice_in_dim(c_q, i * QUERY_BLOCK, QUERY_BLOCK),
                 p["q_b_w"].reshape(sizes["q_rank"], -1),
                 operands).reshape(QUERY_BLOCK, h, nope + rope_w)
        q_pos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        q_rope = rope(q[:, :, nope:], q_pos, sizes["theta"])
        k_rope = k_r
        if fault == "no_rope":
            q_rope, k_rope = jnp.zeros_like(q_rope), jnp.zeros_like(k_r)
        # a score is [q_nope | q_rope] . [k_nope | k_r], the two parts
        # summed (the rotary key is every head's)
        scores = (jnp.einsum("qhd,khd->hqk", q[:, :, :nope], k_nope,
                             precision=HIGHEST)
                  + jnp.einsum("qhd,kd->hqk", q_rope, k_rope,
                               precision=HIGHEST)) * scale
        scores = jnp.where(pos[None, None, :] <= q_pos[None, :, None],
                           scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                          values, precision=HIGHEST)

    att = lax.map(block, jnp.arange(blocks)).reshape(-1, h, vd)[:S]
    return _dot(att.reshape(S, h * vd), p["o_w"].reshape(h * vd, d),
                operands)


def gated(x, w_gate, w_up, w_down, operands: str):
    return _dot(jax.nn.silu(_dot(x, w_gate, operands))
                * _dot(x, w_up, operands), w_down, operands)


def routing(x, p, *, k: int, scale: float):
    """(T, d) -> the mask of weights (T, E), zero off the k chosen."""
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router_w"], precision=HIGHEST))
    _, chosen = lax.top_k(scores + p["bias"], k)
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    picked = picked / (jnp.sum(picked, axis=1, keepdims=True) + 1e-20) * scale
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(picked)


def routed_ffn(x, p, *, k: int, scale: float, first: int, operands: str):
    """(T, d) -> what the held experts give (``p``'s banks hold the experts
    ``[first, first + held)`` of the router's ``E``), and the routing mask
    (T, E). Every held expert is computed for every token. The shared
    expert is not in it."""
    mask = routing(x, p, k=k, scale=scale)
    held = p["e_up"].shape[0]
    weights = lax.dynamic_slice_in_dim(mask, first, held, axis=1)

    def one(y, expert):
        w_gate, w_up, w_down, weight = expert
        return y + weight[:, None] * gated(x, w_gate, w_up, w_down,
                                           operands), None

    y, _ = lax.scan(one, jnp.zeros_like(x),
                    (p["e_gate"], p["e_up"], p["e_down"], weights.T))
    return y, mask


def layer(x, p, kinds: tuple, sizes: dict, operands: str, fault=None):
    """One layer on (R, S, d), a request at a time. Returns the output and
    the routing mask (R * S, E), or None."""
    eps = sizes["eps"]

    def post(out, gain):
        return out if fault == "no_post_norm" else rms_norm(out, gain, eps)

    def one(s):
        a = rms_norm(s, p["ln1_g"], eps)
        s = s + post(latent_attention(a, p, sizes=sizes, operands=operands,
                                      fault=fault), p["ln1_post_g"])
        b = rms_norm(s, p["ln2_g"], eps)
        if kinds[1] == "routed":
            out, mask = routed_ffn(b, p, k=sizes["k"], scale=sizes["scale"],
                                   first=sizes["first"], operands=operands)
            out = out + gated(b, p["s_gate"], p["s_up"], p["s_down"],
                              operands)
        else:
            out = gated(b, p["gate_w"], p["up_w"], p["down_w"], operands)
            mask = jnp.zeros((s.shape[0], 0), jnp.float32)
        return s + post(out, p["ln2_post_g"]), mask

    def request(r, carry):
        # a request's rows updated where they lie: no second copy of the
        # stream (3 GB at the cell's size)
        x, masks = carry
        out, mask = one(x[r])
        return x.at[r].set(out), masks.at[r].set(mask)

    R, S, _ = x.shape
    width = sizes["E"] if kinds[1] == "routed" else 0
    x, masks = lax.fori_loop(
        0, R, request, (x, jnp.zeros((R, S, width), jnp.float32)))
    if kinds[1] != "routed":
        return x, None
    return x, masks.reshape(-1, width)


def make_trunk(sizes: dict, operands: str = "float32", fault=None):
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

    # the residual stream goes into each layer in place where the device
    # can alias it (at the cell's size 3 GB in and 3 GB out otherwise)
    donate = (2,) if jax.default_backend() != "cpu" else ()
    steps = {kinds: jax.jit(
        lambda seed, i, x, kinds=kinds: layer(
            x, W.layer_leaves(W.seed_key(seed), sizes, i, kinds), kinds,
            sizes, operands, fault), donate_argnums=donate)
        for kinds in set(sizes["layers"])}

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
    """``head(seed, hid (S, d))``: float32 logits of one request at a
    time."""
    @jax.jit
    def logits(seed, hid):
        w = W.top_leaves(W.seed_key(seed), sizes, ("head_w",))["head_w"]
        return jnp.matmul(hid, w, precision=HIGHEST)

    return lambda seed, hid: logits(W.seed_arg(seed), hid)


def forward(seed, tokens, sizes: dict, operands: str = "float32",
            fault=None):
    """(S,) token ids -> (S, vocab) float32 logits: one request's full
    forward pass (what the tests compare the engine with)."""
    hid, _ = make_trunk(sizes, operands, fault)(seed,
                                                jnp.asarray(tokens)[None])
    return make_head(sizes)(seed, hid[0])


def served_gaps(seed, tokens, lengths, firsts, sizes: dict, *,
                control: bool = False, faults: tuple = ()):
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
    operands and float32. For each of ``faults`` the same as the control's
    for the token that faulty reference puts first, under the fault's name.
    Padding after a row's real tokens changes nothing before it: attention
    is causal."""
    tokens = jnp.asarray(tokens)
    R, S = tokens.shape
    pos = jnp.arange(1, S)
    live = ((pos[None] >= jnp.asarray(firsts)[:, None])
            & (pos[None] < jnp.asarray(lengths)[:, None]))
    head = make_head(sizes)

    def picks(hid):
        """The token each position of each row puts first."""
        return [jnp.argmax(head(seed, hid[r, :-1]), axis=-1).astype(
            tokens.dtype) for r in range(R)]

    # every other trunk first, its picks kept and its hidden states
    # dropped, so that one trunk's float32 states at a time are on the
    # device (at the cell's size 2.7 GB each)
    picked, rounded = {}, []
    if control:
        picked["control_gap"] = picks(make_trunk(sizes, "int8")(
            seed, tokens)[0])
        rounded = make_trunk(sizes, "bfloat16")(seed, tokens)[1]
    for fault in faults:
        picked[fault] = picks(make_trunk(sizes, fault=fault)(seed,
                                                             tokens)[0])
    hid, masks = make_trunk(sizes)(seed, tokens)

    @jax.jit
    def below_best(ref, chosen, keep):
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
        return jnp.where(keep, best - got, 0.0)

    out = {"gap": [], **{name: [] for name in picked}}
    for r in range(R):
        ref = head(seed, hid[r, :-1])  # row i predicts tokens[i + 1]
        out["gap"].append(below_best(ref, tokens[r, 1:], live[r]))
        for name, first in picked.items():
            out[name].append(below_best(ref, first[r], live[r]))
    got = {name: jnp.stack(rows) for name, rows in out.items()}
    if control:
        # row i of a mask routes the token at position i: served positions
        # are 1..S-1 of each request
        served = jnp.pad(live, ((0, 0), (1, 0))).reshape(-1)
        moved = sum(jnp.sum(served & jnp.any((a > 0) != (b > 0), axis=1))
                    for a, b in zip(masks, rounded))
        got["choices_moved"] = moved
        got["choices_checked"] = jnp.sum(served) * len(masks)
    return got
