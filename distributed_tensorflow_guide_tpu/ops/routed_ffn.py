"""A dropless top-k routed feed-forward over the experts one program holds.

Every token scores all ``E`` experts; its ``k`` assignments are the top ``k``
of ``score + bias`` and its weights the chosen scores, normalised. The
program holds the experts ``[first, first + count)``: the ``T * k``
assignments are sorted by held expert (those to experts held elsewhere, and
those of rows that are padding, go to the end and add nothing), the sorted
rows run through three grouped products over the experts held
(``jax.lax.ragged_dot``: on a TPU one native grouped-matmul call whose
operations are the rows' and not ``count`` times them; compiled for the v5e,
PR 28), and the weighted outputs are summed back per token. No capacity, so
no token is dropped, and the cost follows the rows, not the experts.

The parts that ``E / count`` programs give for the same tokens add up to
the whole layer: routing is computed in full by each (it is small, and in
float32 whatever the activations are), the experts' work is split.

``MoEMLP`` (top-1, one-hot dispatch into a capacity buffer) and
``parallel/expert.moe_ffn`` are the two older routed layers; this is the
one they retire onto (``ROADMAP.md`` D2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def route(x, router_w, bias, *, top_k: int):
    """``x`` (T, d) -> the chosen experts (T, k) int32 and their weights
    (T, k) float32, which sum to one. Scores are ``sigmoid(x W_r)`` in
    float32 at full product precision; ``bias`` (E,) moves the choice and
    takes no part in the weights."""
    logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, chosen = lax.top_k(scores + bias.astype(jnp.float32), top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=1)
    weights = weights / (jnp.sum(weights, axis=1, keepdims=True) + 1e-6)
    return chosen.astype(jnp.int32), weights


def routed_ffn(x, router_w, bias, w_gate, w_up, w_down, *, top_k: int,
               first: int = 0, live=None):
    """``x`` (T, d) through the routed layer. ``w_gate``/``w_up`` are
    (count, d, ff) and ``w_down`` (count, ff, d): the experts ``[first,
    first + count)`` of ``router_w``'s (d, E). ``live`` (T,) bool marks the
    rows that are tokens (None: all); the others route nowhere.

    Returns ``(y, load)``: ``y`` (T, d) in ``x``'s dtype, the held experts'
    part of the layer's output, and ``load`` (E,) int32, the assignments of
    live rows by expert over ALL experts (the router's census, the same in
    every program that shares the layer)."""
    T, d = x.shape
    count, n_experts = w_gate.shape[0], router_w.shape[1]
    with jax.named_scope("dtg.routed.route"):
        chosen, weights = route(x, router_w, bias, top_k=top_k)
        flat = chosen.reshape(-1)  # (T * k,), a token's k side by side
        alive = (jnp.ones((T * top_k,), bool) if live is None
                 else jnp.repeat(live.astype(bool), top_k))
        load = jnp.zeros((n_experts,), jnp.int32).at[flat].add(
            alive.astype(jnp.int32))
        local = flat - first
        held = alive & (local >= 0) & (local < count)
        key = jnp.where(held, local, count)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
        rows = x[order // top_k]  # (T * k, d) sorted by held expert
    with jax.named_scope("dtg.routed.experts"):
        dtype = x.dtype
        gate = lax.ragged_dot(rows, w_gate.astype(dtype), sizes)
        up = lax.ragged_dot(rows, w_up.astype(dtype), sizes)
        out = lax.ragged_dot(jax.nn.silu(gate) * up, w_down.astype(dtype),
                             sizes)
    with jax.named_scope("dtg.routed.combine"):
        # rows past the last group are not the grouped product's to define
        out = jnp.where(held[order][:, None], out.astype(jnp.float32), 0.0)
        out = out * weights.reshape(-1)[order][:, None]
        back = jnp.argsort(order)  # where each assignment went
        y = jnp.sum(out[back].reshape(T, top_k, d), axis=1)
    return y.astype(x.dtype), load
