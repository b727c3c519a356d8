"""A dropless top-k routed feed-forward over the experts one program holds.

Every token scores all ``E`` experts; its ``k`` assignments are the top ``k``
of ``score + bias`` and its weights the chosen scores, normalised and
scaled. An expert is the gated form ``down(silu(gate x) * up x)`` or, with
no ``gate`` bank, the plain ``down(relu(up x)^2)``. The
program holds the experts ``[first, first + count)``: the ``T * k``
assignments are sorted by held expert (those to experts held elsewhere, and
those of rows that are padding, go to the end and add nothing), the sorted
rows run through the form's three or two grouped products over the experts
held (:func:`grouped_product`), and the weighted outputs are summed back per
token. No capacity, so no token is dropped, and the cost follows the rows,
not the experts.

A grouped product is ``jax.lax.ragged_dot``: on a TPU one native
grouped-matmul call whose operations are the rows' and not ``count`` times
them (compiled for the v5e, PR 28). The compiler tiles that call by the
largest power of two, up to 512, that divides each of the bank's two sizes.
A bank of 2688 x 1920 (21 and 15 lanes of 128) gets tiles of 128 x 128: 20,000
grid steps a call at a third of a microsecond each, 6.5 ms for 660 MB of
weights that the memory delivers in 0.8 (my chip run, PR 33). For such a
bank, on a TPU, the product is JAX's Pallas grouped matmul
(``pallas.ops.tpu.megablox``) with tiles this file derives: the whole
contraction and as many whole lanes of the output as fit the kernel's share
of VMEM, so a step moves megabytes of weights and the call is bound by
their bytes. A bank whose sizes are multiples of 512 keeps the native call.

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

from distributed_tensorflow_guide_tpu.ops import autotune

LANE = 128
NATIVE_TILE = 512  # the largest edge the compiler's own tiling takes
ROW_TILE = 128  # rows of a step of the Pallas product


def grouped_tiles(k: int, n: int, itemsize: int, *,
                  budget: int | None = None) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` for the Pallas grouped product of rows (m, k) with
    banks (g, k, n), both sizes whole lanes: the longest contraction tile
    (all of ``k`` first) and, for it, the widest whole-lane divisor of
    ``n`` whose working set fits ``budget`` (``autotune.VMEM_BUDGET_BYTES``
    unless given): the bank's tile, the rows' and the output's, each
    double-buffered by the pipeline, and the float32 accumulator."""
    budget = autotune.VMEM_BUDGET_BYTES if budget is None else budget
    tm = ROW_TILE

    def fits(tk, tn):
        return (2 * (tk * tn + tm * tk + tm * tn) * itemsize
                + tm * tn * 4) <= budget

    widths = [t for t in range(LANE, n + 1, LANE) if n % t == 0]
    for tk in [k] + [t for t in (2048, 1024, 512, 256) if t < k]:
        fitting = [tn for tn in widths if fits(tk, tn)]
        if fitting:
            return tm, tk, max(fitting)
    return tm, LANE, LANE


def grouped_impl(k: int, n: int) -> str:
    """``"native"`` (``lax.ragged_dot``) or ``"pallas"``: the latter on a
    TPU for a bank of whole lanes that the compiler's own tiling would cut
    finer than ``NATIVE_TILE`` on either side."""
    def native(x):
        return min(x & -x, NATIVE_TILE)

    fine = native(k) < NATIVE_TILE or native(n) < NATIVE_TILE
    lanes = k % LANE == 0 and n % LANE == 0
    on_tpu = jax.default_backend() == "tpu"
    return "pallas" if on_tpu and fine and lanes else "native"


def grouped_product(rows, bank, sizes, *, impl: str | None = None,
                    interpret: bool = False):
    """``rows`` (m, k), sorted by group, times ``bank`` (g, k, n): rows
    ``[sum(sizes[:i]), sum(sizes[:i + 1]))`` meet ``bank[i]``. ``sizes``
    (g,) int32 may sum to less than ``m``: what the rows past the last
    group come out as is not defined (the caller masks them). ``impl`` None
    is :func:`grouped_impl`'s choice."""
    k, n = bank.shape[1:]
    if (impl or grouped_impl(k, n)) == "native":
        return lax.ragged_dot(rows, bank, sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m = rows.shape[0]
    tiles = grouped_tiles(k, n, jnp.dtype(bank.dtype).itemsize)
    padded = jnp.pad(rows, ((0, -m % tiles[0]), (0, 0)))
    out = gmm(padded, bank, sizes, preferred_element_type=rows.dtype,
              tiling=tiles, interpret=interpret)
    return out[:m]


def route(x, router_w, bias, *, top_k: int, scale: float = 1.0,
          norm_eps: float = 1e-6):
    """``x`` (T, d) -> the chosen experts (T, k) int32 and their weights
    (T, k) float32, which sum to ``scale``. Scores are ``sigmoid(x W_r)``
    in float32 at full product precision; ``bias`` (E,) moves the choice
    and takes no part in the weights; ``norm_eps`` stands beside the sum
    the chosen scores are divided by."""
    logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, chosen = lax.top_k(scores + bias.astype(jnp.float32), top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=1)
    weights = weights / (jnp.sum(weights, axis=1, keepdims=True) + norm_eps)
    if scale != 1.0:
        weights = weights * scale
    return chosen.astype(jnp.int32), weights


def routed_ffn(x, router_w, bias, w_gate, w_up, w_down, *, top_k: int,
               first: int = 0, live=None, scale: float = 1.0,
               norm_eps: float = 1e-6):
    """``x`` (T, d) through the routed layer. ``w_gate``/``w_up`` are
    (count, d, ff) and ``w_down`` (count, ff, d): the experts ``[first,
    first + count)`` of ``router_w``'s (d, E); ``w_gate`` None is the
    plain form, ``relu(up)^2`` in the gated product's place. ``live`` (T,)
    bool marks the rows that are tokens (None: all); the others route
    nowhere. ``scale`` and ``norm_eps`` are :func:`route`'s.

    Returns ``(y, load)``: ``y`` (T, d) in ``x``'s dtype, the held experts'
    part of the layer's output, and ``load`` (E,) int32, the assignments of
    live rows by expert over ALL experts (the router's census, the same in
    every program that shares the layer)."""
    T, d = x.shape
    count, n_experts = w_up.shape[0], router_w.shape[1]
    with jax.named_scope("dtg.routed.route"):
        chosen, weights = route(x, router_w, bias, top_k=top_k, scale=scale,
                                norm_eps=norm_eps)
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
        if w_gate is None:
            hidden = jnp.square(jax.nn.relu(
                grouped_product(rows, w_up.astype(dtype), sizes)))
        else:
            gate = grouped_product(rows, w_gate.astype(dtype), sizes)
            up = grouped_product(rows, w_up.astype(dtype), sizes)
            hidden = jax.nn.silu(gate) * up
        out = grouped_product(hidden, w_down.astype(dtype), sizes)
    with jax.named_scope("dtg.routed.combine"):
        # rows past the last group are not the grouped product's to define
        out = jnp.where(held[order][:, None], out.astype(jnp.float32), 0.0)
        out = out * weights.reshape(-1)[order][:, None]
        back = jnp.argsort(order)  # where each assignment went
        y = jnp.sum(out[back].reshape(T, top_k, d), axis=1)
    return y.astype(x.dtype), load
