"""GPT-2 as published, in plain ``jax.numpy`` and float32: the reference.

No kernels, no cache, no batching tricks, nothing imported from the program
under test and nothing taken from it: the weights come from
``yardstick.weights`` and the seed. Matrix products run at
``jax.default_matmul_precision("highest")`` (a TPU multiplies float32 in
bfloat16 passes unless told otherwise).

Departures from the published model, all of them this repository's
``Transformer`` and stated in the configuration files under
``departures_forced_by_the_program``: the output head is a matrix of its
own (not the embedding transposed), the vocabulary is padded, only the
feed-forward's first projection has a bias, there is no dropout, and
LayerNorm's epsilon is the one the program runs.

``operands`` chooses the precision the four projections of a block multiply
in: ``"float32"`` is the reference; ``"int8"`` rounds both operands of every
such product to 8 bits with one scale a tensor, made anew at every use: in
the forward pass the activations and the weights, in the backward pass the
incoming gradient with the weights (for dx) and with the activations (for
dW). That is the control: the model computed in the nearest precision below
the bfloat16 the configurations state. (Rounding the forward operands alone
and differentiating in float32 is *closer* to the reference than bfloat16 is,
by every number compared: my chip run, PR 25.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from yardstick import weights

HIGHEST = lax.Precision.HIGHEST


def _int8(t):
    """``t`` rounded to 8 bits, one scale for the tensor."""
    amax = jnp.max(jnp.abs(t))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(t / scale), -127, 127) * scale


@jax.custom_vjp
def _int8_dot(x, w):
    """(S, k) x (k, n) with both operands in 8 bits, forward and backward."""
    return jnp.matmul(_int8(x), _int8(w), precision=HIGHEST)


def _int8_dot_fwd(x, w):
    return _int8_dot(x, w), (x, w)


def _int8_dot_bwd(res, g):
    x, w = res
    g8 = _int8(g)
    return (jnp.matmul(g8, _int8(w).T, precision=HIGHEST),
            jnp.matmul(_int8(x).T, g8, precision=HIGHEST))


_int8_dot.defvjp(_int8_dot_fwd, _int8_dot_bwd)


def _dot(x, w, operands: str):
    if operands == "int8":
        return _int8_dot(x, w)
    if operands != "float32":
        raise ValueError(f"operands {operands!r}: float32 or int8")
    return jnp.matmul(x, w, precision=HIGHEST)


def layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * gain + bias


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def block(x, p, *, eps: float, operands: str):
    """One pre-LayerNorm block on (S, d); ``p`` one layer's leaves."""
    S, d = x.shape
    _, _, h, hd = p["qkv_w"].shape
    a = layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
    qkv = _dot(a, p["qkv_w"].reshape(d, 3 * h * hd), operands)
    q, k, v = jnp.moveaxis(qkv.reshape(S, 3, h, hd), 1, 0)  # (S, h, hd)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / hd ** 0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)
    x = x + _dot(att.reshape(S, h * hd), p["proj_w"].reshape(h * hd, d),
                 operands)
    m = layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
    up = gelu_new(_dot(m, p["up_w"], operands) + p["up_b"])
    return x + _dot(up, p["down_w"], operands)


def hidden(params, tokens, *, eps: float, operands: str = "float32",
           remat: bool = False):
    """(S,) token ids -> (S, d) after the final LayerNorm."""
    S = tokens.shape[0]
    x = params["wte"][tokens] + params["wpe"][:S]
    body = functools.partial(block, eps=eps, operands=operands)
    if remat:
        body = jax.checkpoint(body)
    x, _ = lax.scan(lambda c, p: (body(c, p), None), x, params["layers"])
    return layer_norm(x, params["lnf_g"], params["lnf_b"], eps)


def logits_at(params, hid):
    """(N, d) hidden rows -> (N, vocab) float32 logits."""
    return jnp.matmul(hid, params["head_w"], precision=HIGHEST)


def row_loss(params, tokens, *, eps: float, operands: str = "float32"):
    """Summed next-token cross-entropy of one row and its count."""
    hid = hidden(params, tokens, eps=eps, operands=operands, remat=True)
    logp = jax.nn.log_softmax(logits_at(params, hid[:-1]), axis=-1)
    ll = jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]
    return -jnp.sum(ll), tokens.shape[0] - 1


def loss_and_grad(params, batch, *, eps: float, operands: str = "float32"):
    """Mean next-token loss of a (B, S) batch and its gradient, one row at
    a time so that float32 activations of a full-size model fit."""
    def one(carry, row):
        (s, n), g = jax.value_and_grad(
            lambda p: row_loss(p, row, eps=eps, operands=operands),
            has_aux=True)(params)
        loss_sum, grad_sum = carry
        return (loss_sum + s, jax.tree.map(jnp.add, grad_sum, g)), n

    zero = jax.tree.map(jnp.zeros_like, params)
    (loss_sum, grad_sum), counts = lax.scan(
        one, (jnp.zeros((), jnp.float32), zero), batch)
    n = jnp.sum(counts).astype(jnp.float32)
    return loss_sum / n, jax.tree.map(lambda g: g / n, grad_sum)


def adamw_step(params, mu, nu, grads, t, opt: dict):
    """One AdamW update written out (Loshchilov & Hutter, decoupled decay
    on every leaf), ``t`` counted from 1."""
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

    def new(p, m, v):
        m_hat = m / (1 - b1 ** t)
        v_hat = v / (1 - b2 ** t)
        step = m_hat / (jnp.sqrt(v_hat) + opt["eps"])
        return p - opt["learning_rate"] * (step + opt["weight_decay"] * p)

    return jax.tree.map(new, params, mu, nu), mu, nu


def train_readings(seed, batches, sizes: dict, *, eps: float, opt: dict,
                   operands: str = "float32", frozen: bool = False) -> dict:
    """Follow ``len(batches)`` steps from the seed's weights. Returns each
    step's loss, the first gradient's per-leaf norms and the per-leaf norms
    of the parameters' change over all the steps, leaves in
    ``weights.flat_names`` order. Jit it with ``sizes``, ``eps``, ``opt``,
    ``operands`` and ``frozen`` static. ``frozen`` plants a fault: every step
    hands its state back unchanged."""
    p0 = weights.stacked_tree(seed, sizes)
    params = p0
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for t, batch in enumerate(batches, start=1):
        loss, grads = loss_and_grad(params, batch, eps=eps,
                                    operands=operands)
        if grad_norms is None:
            grad_norms = weights.norms_of_stacked(grads)
        if not frozen:
            params, mu, nu = adamw_step(params, mu, nu, grads, t, opt)
        losses.append(loss)
    change = jax.tree.map(jnp.subtract, params, p0)
    return {"losses": jnp.stack(losses), "grad_norms": grad_norms,
            "change_norms": weights.norms_of_stacked(change)}


def served_gaps(params, tokens, length, first, *, eps: float,
                control: bool = False):
    """One served request against the reference. ``tokens`` (S,) is the
    prompt followed by what was served, padded; ``length`` counts the real
    ones; ``first`` is the index of the first served token. For every
    served position, how far the served token's logit lies below the
    reference's best (0 where the served token is the reference's own
    choice). With ``control``, the same for the token that the int8
    operands would put first there, which is how the control is read
    without decoding. Positions that are not served read 0."""
    def logits(operands):
        hid = hidden(params, tokens, eps=eps, operands=operands)
        return logits_at(params, hid[:-1])  # row i predicts tokens[i + 1]

    ref = logits("float32")
    pos = jnp.arange(1, tokens.shape[0])
    live = (pos >= first) & (pos < length)
    best = jnp.max(ref, axis=-1)

    def below_best(chosen):
        got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
        return jnp.where(live, best - got, 0.0)

    out = {"gap": below_best(tokens[1:])}
    if control:
        low = jnp.argmax(logits("int8"), axis=-1).astype(tokens.dtype)
        out["control_gap"] = below_best(low)
    return out
