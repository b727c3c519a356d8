"""Phi-4-mini-flash (``model_type`` ``phi4flash``, the "SambaY"
decoder-hybrid-decoder of arXiv:2507.06607) in plain ``jax.numpy`` and
float32: the reference. No kernels, no cache, no ring, no chunks, nothing
imported from the program under test and nothing taken from it: the weights
come from ``yardstick.weights_phi4flash`` and the seed. Matrix products are
what ``Precision.HIGHEST`` makes of float32 on a TPU, six passes of
bfloat16 parts accumulated in float32, written out (:func:`_product`).

The equations. ``d = hidden_size``; LayerNorm (gain and bias, ``eps =
layer_norm_eps``) everywhere; no positions of any kind. Layer ``i``: ``x = x
+ mixer_i(LN1(x))``, then ``x = x + W_down(silu(W_gate LN2(x)) * W_up
LN2(x))`` (no biases). After the last layer one LayerNorm, and the logits
are ``h E^T``, ``E`` the embedding (tied, no bias). The mixer by the layer's
kind (``weights_phi4flash.layer_kinds``):

* ``mamba1`` (``D`` channels, state ``N``, step rank ``R``, ``K`` taps):
  ``[u | z] = W_in x``; ``u = silu(conv_K(u) + b_c)``, depthwise and causal,
  zeros before position 0; ``[r | B_t | C_t] = W_x u``; ``dt = softplus(W_dt r
  + b_dt)``; ``A = -exp(A_log)`` (D, N); a plain ``lax.scan`` over positions
  from a zero state,

      h_t[c, :] = exp(dt_t[c] A[c, :]) h_{t-1}[c, :] + dt_t[c] u_t[c] B_t
      y_t[c]    = h_t[c, :] . C_t + D[c] u_t[c];

  ``out = W_out (y * silu(z))``. The last such layer's ``y`` (before the
  gate) is the memory ``m`` of the layers after it;
* ``gmu``: ``out = W_2 (silu(W_1 x) * m_t)``, ``m_t`` that ``y`` at the same
  position of the same sequence;
* ``attention`` and ``window_attention``: ``[q | k | v] = W_qkv x + b`` (``h``,
  ``kv``, ``kv`` heads of ``hd``). Differential: query heads pair as ``(2p, 2p
  + 1)``, key heads as ``(2g, 2g + 1)``; pair ``p`` reads key pair ``g = p //
  (h / kv)``; the pair's value is ``V_g = [v_2g | v_2g+1]``. ``A1 =
  softmax(q_2p k_2g^T / sqrt(hd) + mask)``, ``A2 = softmax(q_2p+1 k_2g+1^T /
  sqrt(hd) + mask)``; ``o_p = (A1 - lambda A2) V_g``; ``o_p = RMSNorm(o_p;
  gain, eps) (1 - lambda_init)``; ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2)
  + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 i)``; ``out = W_o
  concat_p(o_p) + b_o``. The mask is causal, and in a window layer query
  ``t`` sees key ``j`` iff ``0 <= t - j < sliding_window``;
* ``cross_attention``: ``q = W_q x + b`` only; keys and values are the one
  ``attention`` layer's, every position up to and including ``t``; then the
  differential form with this layer's own lambdas, sub-norm and ``W_o``.

``operands`` chooses the precision the products multiply in: ``"float32"``
is the reference; ``"int8"`` rounds both operands of every projection
product, and the scan's inputs ``u``, ``B``, ``C``, to 8 bits with one scale
a tensor: the control, the nearest precision below the bfloat16 the
configuration states. The step sizes, the decays, the state, the lambdas
and every softmax are float32 in both. ``fault`` plants one of three
mistakes a program could make and still serve fluent tokens: ``"no_lambda"``
drops the ``lambda A2`` term, ``"no_window"`` lets a window layer see the
whole sequence, ``"no_memory"`` hands the memory units zeros.

At the cell's size the float32 weights are 15.4 GB: :func:`make_trunk`
has one layer's drawn at a time and sends all the sampled requests through
that layer, one request at a time;
attention takes its queries ``QUERY_BLOCK`` at a time and the head its
positions ``HEAD_BLOCK`` at a time (whole, a request's scores would be 10
GB and its logits 6 GB).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from yardstick import weights_phi4flash as W

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 256
HEAD_BLOCK = 512
FAULTS = ("no_lambda", "no_window", "no_memory")
#: a masked score: finite, so that a padded query row, which sees no key of
#: a window, gives a number and not NaN (such rows are cut off)
MASKED = float(jnp.finfo(jnp.float32).min)


def _int8(t):
    """``t`` rounded to 8 bits, one scale for the tensor."""
    amax = jnp.max(jnp.abs(t))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(t / scale), -127, 127) * scale


def _rounded(t, operands: str):
    if operands == "int8":
        return _int8(t)
    if operands != "float32":
        raise ValueError(f"operands {operands!r}: float32 or int8")
    return t


def _parts(t, n: int = 3) -> list:
    """Float32 ``t`` as ``n`` bfloat16 arrays whose sum it is up to its
    last ``24 - 8 n`` bits: its leading eight bits, the next eight, the
    last."""
    parts = []
    for _ in range(n):
        parts.append(t.astype(jnp.bfloat16))
        t = t - parts[-1].astype(jnp.float32)
    return parts


def _product(spec: str, a, b, b_parts: int = 3):
    """``einsum(spec, a, b)`` of float32 arrays as the TPU multiplies them
    at ``Precision.HIGHEST``, written out: bfloat16 products of their
    parts, accumulated in float32, for every pair of parts but the three
    smallest (six passes), the small ones added first. Written out
    because the TPU's compiler takes 7 s for each shape of its own six
    passes and 2 s for these, a run has a time limit, and the compiler
    gets one core when the host is busy; and because a ``b`` whose numbers
    are bfloat16's, as a weight's are, is its first part (``b_parts=1``):
    three passes, every product exact, at half the chip's time."""
    terms = [jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)
             for i, x in enumerate(_parts(a))
             for j, y in enumerate(_parts(b, b_parts)) if i + j < 3]
    total = terms[-1]
    for term in terms[-2::-1]:
        total = total + term
    return total


def _dot(x, w, operands: str):
    """``x @ w`` for a weight: every leaf of ``weights_phi4flash`` that is
    multiplied is rounded to bfloat16. The control's int8 operands are
    not bfloat16's once scaled, and take the compiler's ``HIGHEST``."""
    if operands == "int8":
        return jnp.matmul(_int8(x), _int8(w), precision=HIGHEST)
    if operands != "float32":
        raise ValueError(f"operands {operands!r}: float32 or int8")
    return _product("...k,kn->...n", x, w, b_parts=1)


def layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * gain + bias


def recurrence(u, dt, a, b, c):
    """The selective scan a position at a time: ``u`` and ``dt`` (S, D),
    ``a`` (D, N), ``b`` and ``c`` (S, N), from a zero state. Returns ``h_t .
    C_t``, (S, D)."""
    def step(h, at):
        u_t, dt_t, b_t, c_t = at
        h = (jnp.exp(dt_t[:, None] * a) * h
             + (dt_t * u_t)[:, None] * b_t[None, :])
        return h, jnp.sum(h * c_t[None, :], axis=-1)

    return lax.scan(step, jnp.zeros(a.shape, jnp.float32), (u, dt, b, c))[1]


def mamba_mixer(x, p, *, sizes: dict, operands: str):
    """(S, d) -> (out (S, d), y (S, D) before the gate)."""
    S = x.shape[0]
    D, N, R, taps = sizes["inner"], sizes["N"], sizes["R"], sizes["taps"]
    u, z = jnp.split(_dot(x, p["in_w"], operands), 2, axis=-1)
    ext = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(p["conv_w"][:, j] * ext[j:j + S]
                        for j in range(taps)) + p["conv_b"])
    r, b, c = jnp.split(_dot(u, p["x_w"], operands), [R, R + N], axis=-1)
    dt = jax.nn.softplus(_dot(r, p["dt_w"], operands) + p["dt_b"])
    u_in, b, c = (_rounded(v, operands) for v in (u, b, c))
    y = recurrence(u_in, dt, -jnp.exp(p["a_log"]), b, c) + p["skip_d"] * u_in
    return _dot(y * jax.nn.silu(z), p["out_w"], operands), y


def differential_attention(q, k, v, p, *, layer, window, sizes: dict,
                           operands: str, fault):
    """``q`` (S, h, hd) over ``k``, ``v`` (S, kv, hd) -> (S, d)."""
    S, h, hd = q.shape
    kv = k.shape[1]
    group = h // kv  # pairs of query heads to a pair of key heads
    start = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))
    lam = (jnp.exp(jnp.sum(p["lq1"] * p["lk1"]))
           - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + start)
    if fault == "no_lambda":
        lam = 0.0
    # pair p's two query heads beside the key heads they read, and the
    # pair's value: both value heads side by side
    k_of = jnp.repeat(k.reshape(S, kv // 2, 2, hd), group, axis=1)
    k_of = k_of.reshape(S, h, hd)  # query head j reads k_of[:, j]
    v_of = jnp.repeat(v.reshape(S, kv // 2, 2 * hd), group, axis=1)
    pos = jnp.arange(S)

    def block(q_blk, first):
        """``QUERY_BLOCK`` queries from position ``first``."""
        t = first + jnp.arange(q_blk.shape[0])
        seen = pos[None, :] <= t[:, None]
        if window is not None and fault != "no_window":
            seen &= pos[None, :] > t[:, None] - window
        scores = _product("qhd,khd->hqk", q_blk, k_of) / math.sqrt(hd)
        att = jax.nn.softmax(jnp.where(seen[None], scores, MASKED), -1)
        att = att.reshape(h // 2, 2, *att.shape[1:])
        mixed = att[:, 0] - lam * att[:, 1]  # (pairs, q, S)
        return _product("pqk,kpd->qpd", mixed, v_of)

    n = -(-S // QUERY_BLOCK)
    padded = jnp.pad(q, ((0, n * QUERY_BLOCK - S), (0, 0), (0, 0)))
    o = lax.map(lambda at: block(*at), (
        padded.reshape(n, QUERY_BLOCK, h, hd),
        jnp.arange(n) * QUERY_BLOCK)).reshape(n * QUERY_BLOCK, h // 2,
                                              2 * hd)[:S]
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                      + sizes["eps"]) * p["subln_g"] * (1.0 - start)
    return _dot(o.reshape(S, h * hd), p["proj_w"].reshape(h * hd, -1),
                operands) + p["proj_b"]


def projected(x, w, b, operands: str):
    """``x`` (S, d) through heads ``w`` (d, n, hd) with bias ``b``."""
    d, n, hd = w.shape
    return (_dot(x, w.reshape(d, n * hd), operands)
            + b.reshape(n * hd)).reshape(-1, n, hd)


def mixer(x, p, kind: str, carried: dict, *, layer, sizes: dict,
          operands: str, fault):
    """One request's mixer, (S, d) -> (S, d), and what it hands on."""
    h, kv = sizes["h"], sizes["kv"]
    if kind == "mamba1":
        out, y = mamba_mixer(x, p, sizes=sizes, operands=operands)
        return out, {**carried, "memory": y}
    if kind == "gmu":
        memory = carried["memory"]
        if fault == "no_memory":
            memory = jnp.zeros_like(memory)
        gate = jax.nn.silu(_dot(x, p["in_w"], operands))
        return _dot(gate * memory, p["out_w"], operands), carried
    common = dict(layer=layer, sizes=sizes, operands=operands, fault=fault)
    if kind == "cross_attention":
        q = projected(x, p["q_w"], p["q_b"], operands)
        k, v = carried["kv"]
        return differential_attention(q, k, v, p, window=None,
                                      **common), carried
    qkv = projected(x, p["qkv_w"], p["qkv_b"], operands)
    q, k, v = qkv[:, :h], qkv[:, h:h + kv], qkv[:, h + kv:]
    if kind == "attention":
        return differential_attention(q, k, v, p, window=None, **common), {
            **carried, "kv": (k, v)}
    return differential_attention(q, k, v, p, window=sizes["window"],
                                  **common), carried


def mixer_half(x, carried, p, kind: str, layer, sizes: dict, operands: str,
               fault):
    """One request's ``x + mixer(LN1(x))``, (S, d) -> (S, d), and what the
    request carries on: the memory (S, D), zeros until a layer has handed
    its own on, and the full layer's keys and values (S, kv, hd), once
    that layer has."""
    a = layer_norm(x, p["ln1_g"], p["ln1_b"], sizes["eps"])
    out, carried = mixer(a, p, kind, carried, layer=layer, sizes=sizes,
                         operands=operands, fault=fault)
    return x + out, carried


def dense_half(x, p, sizes: dict, operands: str):
    """One request's ``x + W_down(silu(W_gate LN2(x)) * W_up LN2(x))``."""
    a = layer_norm(x, p["ln2_g"], p["ln2_b"], sizes["eps"])
    gated = jax.nn.silu(_dot(a, p["gate_w"], operands)) * _dot(
        a, p["up_w"], operands)
    return x + _dot(gated, p["down_w"], operands)


_TRUNKS: dict = {}


def make_trunk(sizes: dict, operands: str = "float32", fault=None):
    """``trunk(seed, tokens (R, S)) -> (hidden (R, S, d)`` after the last
    LayerNorm, the embedding ``)``: a layer at a time, its weights from
    ``weights_phi4flash.drawn`` (one layer's float32 weights exist at a
    time), and in it a request at a time. A compiled program a kind of
    half, for one request; the draws are the programs the benchmark's
    set-up has compiled in this process already. So the comparison
    compiles little: a run has a time limit, and on a busy host the
    compiler gets one core, where a program of a whole layer over all the
    requests with its draws inside took it 35-45 s, five kinds of them.
    One trunk a (sizes, operands, fault): a second call finds the first's
    compiled halves."""
    key = (tuple(sorted(sizes.items())), operands, fault)
    if key not in _TRUNKS:
        _TRUNKS[key] = _make_trunk(sizes, operands, fault)
    return _TRUNKS[key]


def _make_trunk(sizes: dict, operands: str, fault):
    embed = jax.jit(lambda wte, tokens: wte[tokens])
    last_norm = jax.jit(lambda x, g, b: layer_norm(x, g, b, sizes["eps"]))
    mixers = {kind: jax.jit(
        lambda x, carried, p, layer, kind=kind: mixer_half(
            x, carried, p, kind, layer, sizes, operands, fault))
        for kind, _ in set(sizes["layers"])}
    dense = jax.jit(lambda x, p: dense_half(x, p, sizes, operands))

    def trunk(seed, tokens):
        top = W.drawn(sizes)(seed)
        # zeros until a layer hands its own on, so that a kind of mixer
        # sees one shape of what is carried, and compiles once
        memory = jnp.zeros((tokens.shape[1], sizes["inner"]), jnp.float32)
        rows = [(embed(top["wte"], row), {"memory": memory})
                for row in tokens]
        for i, (kind, ffn) in enumerate(sizes["layers"]):
            p, q = (W.drawn(sizes, half)(seed, i) for half in (kind, ffn))
            for r, (x, carried) in enumerate(rows):
                x, carried = mixers[kind](x, carried, p, jnp.int32(i))
                rows[r] = dense(x, q), carried
            # a layer's weights are freed before the next one's are drawn
            # (the calls return before the chip has run them)
            jax.block_until_ready(rows)
        return jnp.stack([last_norm(x, top["lnf_g"], top["lnf_b"])
                          for x, _ in rows]), top["wte"]

    return trunk


def make_head(sizes: dict):
    """``head(wte, hid (S, d), chosen (S,)) -> (best, got, first)``, each
    (S,): at every position the reference's best logit, the logit of
    ``chosen`` and the token the logits put first, ``HEAD_BLOCK`` positions
    at a time against the embedding ``wte`` (the head is tied to it)."""
    @jax.jit
    def stats(wte, hid, chosen):
        S = hid.shape[0]
        n = -(-S // HEAD_BLOCK)
        hid = jnp.pad(hid, ((0, n * HEAD_BLOCK - S), (0, 0)))
        chosen = jnp.pad(chosen, (0, n * HEAD_BLOCK - S))
        table = wte.T

        def block(at):
            h, tok = at
            logits = _dot(h, table, "float32")
            got = jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
            return jnp.max(logits, -1), got, jnp.argmax(logits, -1)

        out = lax.map(block, (hid.reshape(n, HEAD_BLOCK, -1),
                              chosen.reshape(n, HEAD_BLOCK)))
        return tuple(v.reshape(-1)[:S] for v in out)

    return stats


def forward(seed, tokens, sizes: dict, operands: str = "float32",
            fault=None):
    """(S,) token ids -> (S, vocab) float32 logits: one request's full
    forward pass, whole (what the tests compare the engine with, at sizes
    where that is small)."""
    hid, wte = make_trunk(sizes, operands, fault)(
        seed, jnp.asarray(tokens)[None])
    return _dot(hid[0], wte.T, "float32")


def served_gaps(seed, tokens, lengths, firsts, sizes: dict, *,
                control: bool = False, faults: tuple = ()):
    """The sampled requests against the reference. ``tokens`` (R, S): each
    row a prompt followed by what was served, padded; ``lengths`` counts
    the real ones; ``firsts`` is the index of each row's first served
    token. Returns ``gap`` (R, S - 1): for every served position how far
    the served token's logit lies below the reference's best (0 where it is
    the reference's own choice; positions not served read 0). With
    ``control`` also ``control_gap``, the same for the token that int8
    operands would put first there, and for each of ``faults`` the same
    under its name, for the token the faulty reference would put first.
    Padding after a row's real tokens changes nothing before it: every
    mixer is causal."""
    tokens = jnp.asarray(tokens)
    S = tokens.shape[1]
    pos = jnp.arange(1, S)
    live = ((pos[None] >= jnp.asarray(firsts)[:, None])
            & (pos[None] < jnp.asarray(lengths)[:, None]))
    head = make_head(sizes)
    hid, wte = make_trunk(sizes)(seed, tokens)
    others = {}
    if control:
        others["control_gap"] = make_trunk(sizes, "int8")(seed, tokens)[0]
    for fault in faults:
        others[fault] = make_trunk(sizes, fault=fault)(seed, tokens)[0]
    out = {name: [] for name in ("gap", *others)}
    for r in range(tokens.shape[0]):
        # row i predicts tokens[i + 1]
        best, got, _ = head(wte, hid[r, :-1], tokens[r, 1:])
        out["gap"].append(jnp.where(live[r], best - got, 0.0))
        for name, low in others.items():
            _, _, first = head(wte, low[r, :-1], tokens[r, 1:])
            _, got, _ = head(wte, hid[r, :-1], first.astype(tokens.dtype))
            out[name].append(jnp.where(live[r], best - got, 0.0))
    return {name: jnp.stack(rows) for name, rows in out.items()}
