"""Phi-4-mini-flash's weights from a seed, the benchmark's own, a layer at a
time.

As ``weights_nemotron.py``: one leaf is one call of :func:`leaf`, a draw
keyed by (seed, leaf name, layer) or the constant it starts from, rounded to
bfloat16 (the type the configuration states) and kept in float32: the
seed's model *is* those bfloat16 numbers. The program's tree holds them as
bfloat16 and the plain reference multiplies the same numbers in float32;
neither side is handed anything the other has made. What the model computes
in float32 is never rounded: a Mamba-1 mixer's step bias, decay rates and
skip, differential attention's four lambda vectors (``_FLOAT32``).

At the cell's size a float32 tree is 15.4 GB, so nothing here makes a whole
tree at once (:func:`half_leaves`, ``layer`` may be traced;
:func:`flax_tree` fills the program's tree layer by layer). The head is the
embedding: there is no head leaf.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from yardstick import weights

seed_arg, seed_key = weights.seed_arg, weights.seed_key


def layer_kinds(config: dict) -> tuple:
    """Layer ``i``'s mixer, from ``num_hidden_layers``, ``mb_per_layer`` and
    the two layers ``assumed.layout`` names: a Mamba-1 mixer every
    ``mb_per_layer`` layers and window attention between them, up to the
    layer that hands its scan output on; the next is the one full-attention
    layer; after it the Mamba slots are gated memory units and the
    attention slots read the full layer's cache."""
    layout = config["assumed"]["layout"]
    memory, full = int(layout["memory_layer"]), int(layout["full_layer"])
    period = int(config["mb_per_layer"])
    out = []
    for i in range(int(config["num_hidden_layers"])):
        if i % period == 0:
            out.append("mamba1" if i <= memory else "gmu")
        elif i == full:
            out.append("attention")
        else:
            out.append("window_attention" if i < full else "cross_attention")
    return tuple((mixer, "dense") for mixer in out)


def sizes_of(config: dict) -> dict:
    """The sizes the weights and the counts need, from a configuration
    file's keys (the published names), what it lists as ``assumed`` and its
    ``deployment``."""
    dep, assumed = config["deployment"], config["assumed"]
    mamba, drawn = assumed["mamba"], assumed["drawn"]
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    if d % h:
        raise ValueError(f"hidden_size {d} is not a multiple of the {h} "
                         "heads")
    kinds = layer_kinds(config)
    if not config["tie_word_embeddings"] or config["mlp_bias"]:
        raise ValueError("the reference and the tree are the tied head's "
                         "and a feed-forward without biases")
    return {
        "d": d, "h": h, "kv": int(config["num_key_value_heads"]),
        "hd": d // h, "ff": int(config["intermediate_size"]),
        "window": int(config["sliding_window"]),
        "inner": int(mamba["expand"]) * d, "N": int(mamba["d_state"]),
        "R": int(mamba["dt_rank"]), "taps": int(mamba["d_conv"]),
        "vocab": int(config["vocab_size"]), "layers": kinds,
        "L": len(kinds), "eps": float(config["layer_norm_eps"]),
        "positions": int(dep["max_positions"]),
        "std": float(drawn["initializer_range"]),
        "bias_std": float(drawn["bias_std"]),
        "lambda_std": float(drawn["lambda_std"]),
        "conv_std": float(drawn["conv_std"]),
        "dt_std": float(drawn["dt_std"]),
        "dt_min": float(drawn["dt_min"]), "dt_max": float(drawn["dt_max"]),
        "dt_floor": float(drawn["dt_floor"]),
    }


def _attention(first: str, heads) -> dict:
    """An attention layer's leaves: its first projection (``qkv`` of h + 2
    kv heads, or a cross layer's ``q`` of h), differential attention's
    lambdas and sub-norm, the output projection over the pairs."""
    return {
        f"{first}_w": (lambda z: (z["d"], heads(z), z["hd"]), "std"),
        f"{first}_b": (lambda z: (heads(z), z["hd"]), "bias_std"),
        "lq1": (lambda z: (z["hd"],), "lambda_std"),
        "lk1": (lambda z: (z["hd"],), "lambda_std"),
        "lq2": (lambda z: (z["hd"],), "lambda_std"),
        "lk2": (lambda z: (z["hd"],), "lambda_std"),
        "subln_g": (lambda z: (2 * z["hd"],), "ones"),
        "proj_w": (lambda z: (z["h"] // 2, 2 * z["hd"], z["d"]), "std"),
        "proj_b": (lambda z: (z["d"],), "bias_std")}


#: leaf name -> (shape from sizes, how it is drawn: the name of a standard
#: deviation in the sizes, or "ones", "zeros", "dt_bias", "a_log")
_MIXER = {
    "mamba1": {
        "in_w": (lambda z: (z["d"], 2 * z["inner"]), "std"),
        "conv_w": (lambda z: (z["inner"], z["taps"]), "conv_std"),
        "conv_b": (lambda z: (z["inner"],), "conv_std"),
        "x_w": (lambda z: (z["inner"], z["R"] + 2 * z["N"]), "std"),
        "dt_w": (lambda z: (z["R"], z["inner"]), "dt_std"),
        "dt_b": (lambda z: (z["inner"],), "dt_bias"),
        "a_log": (lambda z: (z["inner"], z["N"]), "a_log"),
        "skip_d": (lambda z: (z["inner"],), "ones"),
        "out_w": (lambda z: (z["inner"], z["d"]), "std")},
    "window_attention": _attention("qkv", lambda z: z["h"] + 2 * z["kv"]),
    "attention": _attention("qkv", lambda z: z["h"] + 2 * z["kv"]),
    "cross_attention": _attention("q", lambda z: z["h"]),
    "gmu": {
        "in_w": (lambda z: (z["d"], z["inner"]), "std"),
        "out_w": (lambda z: (z["inner"], z["d"]), "std")},
}
_NORM = {"g": (lambda z: (z["d"],), "ones"),
         "b": (lambda z: (z["d"],), "zeros")}
_DENSE = {
    "gate_w": (lambda z: (z["d"], z["ff"]), "std"),
    "up_w": (lambda z: (z["d"], z["ff"]), "std"),
    "down_w": (lambda z: (z["ff"], z["d"]), "std")}
_TOP = {"wte": (lambda z: (z["vocab"], z["d"]), "std"),
        "lnf_g": (lambda z: (z["d"],), "ones"),
        "lnf_b": (lambda z: (z["d"],), "zeros")}
#: computed in float32 by the model: never rounded to bfloat16
_FLOAT32 = ("dt_b", "a_log", "skip_d", "lq1", "lk1", "lq2", "lk2")


def _name_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def leaf(key, name: str, layer, shape, kind: str, sizes: dict):
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "a_log":
        # Mamba-1's own (S4D-real): A[c, n] = -(n + 1) for every channel
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
    k = jax.random.fold_in(jax.random.fold_in(key, _name_id(name)), layer)
    if kind == "dt_bias":
        # Mamba's own: a step size log-uniform in [dt_min, dt_max], floored,
        # through the inverse of the softplus
        lo, hi = np.log(sizes["dt_min"]), np.log(sizes["dt_max"])
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, lo, hi)), sizes["dt_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))
    drawn = sizes[kind] * jax.random.normal(k, shape, jnp.float32)
    if name in _FLOAT32:
        return drawn
    return drawn.astype(jnp.bfloat16).astype(jnp.float32)


def half_spec(half: str) -> dict:
    """A layer is two halves, ``x + f(LayerNorm(x))`` each: a mixer (the
    half's name is the mixer's kind; its norm's leaves are ``ln1_*``) over
    the gated feed-forward (``"dense"``; ``ln2_*``)."""
    if half == "dense":
        return {"ln2_g": _NORM["g"], "ln2_b": _NORM["b"], **_DENSE}
    return {"ln1_g": _NORM["g"], "ln1_b": _NORM["b"], **_MIXER[half]}


def layer_spec(kinds: tuple) -> dict:
    return {k: v for half in kinds for k, v in half_spec(half).items()}


def half_leaves(key, sizes: dict, layer, half: str) -> dict:
    """The leaves of one half of one layer, float32; ``layer`` may be
    traced."""
    return {n: leaf(key, n, layer, shp(sizes), kind, sizes)
            for n, (shp, kind) in half_spec(half).items()}


def top_leaves(key, sizes: dict, names=tuple(_TOP)) -> dict:
    return {n: leaf(key, n, 0, _TOP[n][0](sizes), _TOP[n][1], sizes)
            for n in names}


_DRAWN: dict = {}


def drawn(sizes: dict, half: str | None = None):
    """The compiled draw of one half of a layer (``draw(seed, layer)``) or,
    without ``half``, of the top's leaves (``draw(seed)``), float32. One a
    (sizes, half) a process: :func:`flax_tree` and the plain reference run
    the same compiled draws, so a run compiles each once, in its
    set-up."""
    key = (tuple(sorted(sizes.items())), half)
    if key not in _DRAWN:
        _DRAWN[key] = jax.jit(
            (lambda s: top_leaves(seed_key(s), sizes)) if half is None
            else (lambda s, layer: half_leaves(seed_key(s), sizes, layer,
                                               half)))
    draw = _DRAWN[key]
    if half is None:
        return lambda seed: draw(seed_arg(seed))
    return lambda seed, layer: draw(seed_arg(seed), np.int32(layer))


#: where the package's ``Transformer`` keeps each leaf
_FLAX_TOP = {"wte": ("tok_emb", "embedding"), "lnf_g": ("ln_f", "scale"),
             "lnf_b": ("ln_f", "bias")}
_FLAX_LAYER = {
    "ln1_g": ("ln1", "scale"), "ln1_b": ("ln1", "bias"),
    "ln2_g": ("ln2", "scale"), "ln2_b": ("ln2", "bias"),
    "gate_w": ("mlp", "gate", "kernel"), "up_w": ("mlp", "up", "kernel"),
    "down_w": ("mlp", "down", "kernel"),
    "conv_w": ("ssm", "conv_w"), "conv_b": ("ssm", "conv_b"),
    "x_w": ("ssm", "x_proj", "kernel"), "dt_w": ("ssm", "dt_proj", "kernel"),
    "dt_b": ("ssm", "dt_proj", "bias"), "a_log": ("ssm", "A_log"),
    "skip_d": ("ssm", "D"),
    "qkv_w": ("attn", "qkv", "kernel"), "qkv_b": ("attn", "qkv", "bias"),
    "q_w": ("attn", "q", "kernel"), "q_b": ("attn", "q", "bias"),
    "lq1": ("attn", "lambda_q1"), "lk1": ("attn", "lambda_k1"),
    "lq2": ("attn", "lambda_q2"), "lk2": ("attn", "lambda_k2"),
    "subln_g": ("attn", "subln"),
    "proj_w": ("attn", "proj", "kernel"), "proj_b": ("attn", "proj", "bias"),
}


def flax_path(name: str, kinds: tuple) -> tuple:
    if name in ("in_w", "out_w"):  # a Mamba-1 mixer's, or a memory unit's
        module = "ssm" if kinds[0] == "mamba1" else "gmu"
        return (module, "in_proj" if name == "in_w" else "out_proj",
                "kernel")
    return _FLAX_LAYER[name]


def _held(name: str, value):
    """As the program holds it: bfloat16, ``_FLOAT32``'s in float32 (and
    the norms' leaves and the convolution's, which the program declares
    float32)."""
    keep = (name in _FLOAT32 + ("conv_w", "conv_b", "subln_g")
            or name.startswith("ln"))
    return value if keep else value.astype(jnp.bfloat16)


def flax_tree(seed: int, sizes: dict) -> dict:
    """The program's parameter tree (plain nested dicts), filled a layer at
    a time from :func:`drawn`'s draws, on the default device."""
    out: dict = {}
    held = jax.jit(lambda leaves: {n: _held(n, v)
                                   for n, v in leaves.items()})

    def put(path, value):
        node = out
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = value

    for name, value in held(drawn(sizes)(seed)).items():
        put(_FLAX_TOP[name], value)
    for i, kinds in enumerate(sizes["layers"]):
        for half in kinds:
            for name, value in held(drawn(sizes, half)(seed, i)).items():
                put((f"block_{i}",) + flax_path(name, kinds), value)
        # one layer's float32 draws at a time beside the tree (the calls
        # return before the chip has run them)
        jax.block_until_ready(out[f"block_{i}"])
    return out
