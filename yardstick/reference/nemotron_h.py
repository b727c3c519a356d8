"""Nemotron-H (``model_type`` ``nemotron_h``) in plain ``jax.numpy`` and
float32: the reference. No kernels, no cache, no chunks, no batching of
requests, nothing imported from the program under test and nothing taken
from it: the weights come from ``yardstick.weights_nemotron`` and the seed,
and the routing is the reference's own. Matrix products run at
``Precision.HIGHEST`` (a TPU multiplies float32 in bfloat16 passes unless
told otherwise).

The equations, from the published ``config.json``'s keys. Every layer is
ONE sub-block, ``x <- x + f(RMSNorm(x))`` at ``layer_norm_epsilon``, ``f``
by the layer's letter in ``hybrid_override_pattern``; after the last layer
one RMSNorm, then the output head (not tied). The model has no positions
of any kind: no table, no rotation.

* ``M``, Mamba-2: ``H = mamba_num_heads`` heads of ``P = mamba_head_dim``,
  ``G = n_groups`` groups, state ``N = ssm_state_size``. ``[z | xBC | dt] =
  W_in u`` (``HP | HP + 2GN | H``); ``xBC = silu(conv(xBC) + b)``, depthwise
  and causal over ``conv_kernel`` taps, zeros before position 0; ``x, B, C =
  split(xBC)``, head ``h`` reading group ``h // (H / G)`` of ``B`` and ``C``;
  ``dt_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)``; the recurrence,
  a plain ``lax.scan`` over positions from a zero state,

      S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,   y_t = S_t C_t + D x_t;

  then gate and norm: ``y = y * silu(z)``, RMSNorm within each of the ``G``
  groups of ``HP / G`` channels, times a gain; ``out = W_out y``;
* ``*``, attention: grouped heads of ``head_dim``, causal softmax at ``1 /
  sqrt(head_dim)``, no biases, no per-head norm, no rotation;
* ``E``, routed: ``s = sigmoid(W_r h)`` in float32; the experts chosen are
  the top ``k`` of ``s + b`` (``b`` takes no part in the weights; ``n_group``
  and ``topk_group`` of 1 limit nothing); weights ``s[chosen] / (sum
  s[chosen] + 1e-20) * routed_scaling_factor``; an expert is ``W_down
  relu(W_up h)^2``; **every held expert is computed for every token** and
  weighted by the routing mask (zero off the chosen): the plain way. Plus
  the shared expert of the same form for every token. Of the layer's
  experts this chip holds ``[first, first + held)``: assignments to the
  others add nothing, here as in the program, and nothing stands in for
  them;
* ``-``, dense: ``W_down relu(W_up h)^2`` (no layer of the cut is one).

``operands`` chooses the precision the products multiply in: ``"float32"``
is the reference; ``"int8"`` rounds both operands of every projection
product and expert product, and the scan's inputs ``x``, ``B``, ``C``, to 8
bits with one scale a tensor: the control, the nearest precision below the
bfloat16 the configuration states; ``"bfloat16"`` rounds them to bfloat16,
which is how the program multiplies, and is used only to count how many
routing choices that rounding alone moves. The router, the step sizes, the
decays and the state are float32 in all three.

At the cell's size the float32 weights are 15.7 GB: :func:`make_trunk`
draws one layer's at a time, inside the compiled layer, and sends all the
sampled requests through that layer together.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from yardstick import weights_nemotron as W

HIGHEST = lax.Precision.HIGHEST


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
    return jnp.matmul(_rounded(x, operands), _rounded(w, operands),
                      precision=HIGHEST)


def rms_norm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * gain


def recurrence(x, dt, a, b, c):
    """The state-space recurrence a position at a time: ``x`` (S, H, P),
    ``dt`` (S, H), ``a`` (H,), ``b`` and ``c`` (S, H, N), from a zero state.
    Returns ``S_t C_t``, (S, H, P)."""
    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + dt_t[:, None, None] * x_t[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    zero = jnp.zeros((x.shape[1], x.shape[2], b.shape[2]), jnp.float32)
    return lax.scan(step, zero, (x, dt, b, c))[1]


def mamba_mixer(u, p, *, sizes: dict, operands: str):
    """(S, d) -> (S, d)."""
    S = u.shape[0]
    H, P, G, N = sizes["H"], sizes["P"], sizes["G"], sizes["N"]
    inner, taps = H * P, p["conv_w"].shape[1]
    z, xbc, dt = jnp.split(_dot(u, p["in_w"], operands),
                           [inner, inner + sizes["wide"]], axis=-1)
    ext = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(p["conv_w"][:, j] * ext[j:j + S]
                          for j in range(taps)) + p["conv_b"])
    x, b, c = jnp.split(xbc, [inner, inner + G * N], axis=-1)
    x = _rounded(x, operands).reshape(S, H, P)
    b, c = (jnp.repeat(_rounded(v, operands).reshape(S, G, N), H // G, axis=1)
            for v in (b, c))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p["a_log"]), b, c)
    y = (y + p["skip_d"][:, None] * x).reshape(S, inner) * jax.nn.silu(z)
    grouped = y.reshape(S, G, inner // G)
    grouped = grouped * lax.rsqrt(
        jnp.mean(jnp.square(grouped), -1, keepdims=True) + sizes["eps"])
    return _dot(grouped.reshape(S, inner) * p["norm_g"], p["out_w"],
                operands)


def attention_mixer(x, p, *, operands: str):
    """(S, d) -> (S, d)."""
    S, d = x.shape
    h, hd, _ = p["proj_w"].shape
    kv = (p["qkv_w"].shape[1] - h) // 2
    qkv = _dot(x, p["qkv_w"].reshape(d, -1), operands).reshape(S, -1, hd)
    q, k, v = qkv[:, :h], qkv[:, h:h + kv], qkv[:, h + kv:]
    k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / hd ** 0.5
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], scores,
                       -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v,
                     precision=HIGHEST)
    return _dot(att.reshape(S, h * hd), p["proj_w"].reshape(h * hd, d),
                operands)


def relu2(x, w_up, w_down, operands: str):
    return _dot(jnp.square(jax.nn.relu(_dot(x, w_up, operands))), w_down,
                operands)


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
        w_up, w_down, weight = expert
        return y + weight[:, None] * relu2(x, w_up, w_down, operands), None

    y, _ = lax.scan(one, jnp.zeros_like(x),
                    (p["e_up"], p["e_down"], weights.T))
    return y, mask


def layer(x, p, kinds: tuple, sizes: dict, operands: str):
    """One layer on (R, S, d): every request through it, one at a time
    through a mixer, all tokens together through a feed-forward. Returns
    the output and the routing mask (R * S, E), or None."""
    mixer, ffn = kinds
    a = rms_norm(x, p["ln_g"], sizes["eps"])
    mask = None
    if mixer == "mamba2":
        out = lax.map(lambda s: mamba_mixer(s, p, sizes=sizes,
                                            operands=operands), a)
    elif mixer == "attention":
        out = lax.map(lambda s: attention_mixer(s, p, operands=operands), a)
    else:
        m = a.reshape(-1, a.shape[-1])
        if ffn == "routed":
            out, mask = routed_ffn(m, p, k=sizes["k"], scale=sizes["scale"],
                                   first=sizes["first"], operands=operands)
            out = out + relu2(m, p["s_up"], p["s_down"], operands)
        else:
            out = relu2(m, p["up_w"], p["down_w"], operands)
        out = out.reshape(x.shape)
    return x + out, mask


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
    """``head(seed, hid (S, d))``: float32 logits of one request at a time
    (a batch of them would be gigabytes)."""
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
    program's own choices are not handed out of the engine). Padding after
    a row's real tokens changes nothing before it: every mixer is causal."""
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
