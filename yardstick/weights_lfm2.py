"""LFM2's weights from a seed, the benchmark's own, a layer at a time.

One leaf is one call of :func:`leaf`: a normal draw keyed by (seed, leaf
name, layer), or the constant a gain starts from. The draw is rounded to
bfloat16, the type the configuration publishes its weights in, and kept in
float32: the seed's model *is* those bfloat16 numbers, as a checkpoint's
would be. The program's tree holds them as bfloat16 (the router and its
selection bias as float32, which the configuration computes them in) and
the plain reference multiplies the same numbers in float32; neither side is
handed anything the other has made.

At the cell's size a float32 tree is 21 GB, more than the chip has, so
nothing here makes a whole tree at once: :func:`layer_leaves` draws one
layer (``layer`` may be traced, so layers of one kind share a compiled
program), and :func:`flax_tree` fills the program's tree layer by layer.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from yardstick import weights

seed_arg, seed_key = weights.seed_arg, weights.seed_key

MIXER_OF = {"conv": "short_conv", "full_attention": "attention"}


def sizes_of(config: dict) -> dict:
    """The sizes the weights and the counts need, from a configuration
    file's keys (the published names) and its ``assumed`` draws."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    if d % h:
        raise ValueError(f"hidden_size {d} is not a multiple of {h} heads")
    kinds = tuple(
        (MIXER_OF[t], "dense" if i < int(config["num_dense_layers"])
         else "routed") for i, t in enumerate(config["layer_types"]))
    drawn = config["assumed"]["drawn"]
    return {
        "d": d, "h": h, "kv": int(config["num_key_value_heads"]),
        "hd": d // h, "ff": int(config["intermediate_size"]),
        "eff": int(config["moe_intermediate_size"]),
        "E": int(config["num_experts"]),
        "k": int(config["num_experts_per_tok"]),
        "taps": int(config["conv_L_cache"]),
        "vocab": int(config["vocab_size"]), "layers": kinds,
        "L": len(kinds), "eps": float(config["norm_eps"]),
        "theta": float(config["rope_parameters"]["rope_theta"]),
        "positions": int(config["deployment"]["max_positions"]),
        "std": float(drawn["initializer_range"]),
        "router_std": float(drawn["router_std"]),
        "bias_std": float(drawn["expert_bias_std"]),
        "conv_std": float(drawn["conv_std"]),
    }


#: leaf name -> (shape from sizes, which standard deviation, or "ones")
_NORMS = {"ln1_g": (lambda z: (z["d"],), "ones"),
          "ln2_g": (lambda z: (z["d"],), "ones")}
_MIXER = {
    "short_conv": {
        "in_w": (lambda z: (z["d"], 3 * z["d"]), "std"),
        "conv_w": (lambda z: (z["d"], z["taps"]), "conv_std"),
        "out_w": (lambda z: (z["d"], z["d"]), "std")},
    "attention": {
        "qkv_w": (lambda z: (z["d"], z["h"] + 2 * z["kv"], z["hd"]), "std"),
        "proj_w": (lambda z: (z["h"], z["hd"], z["d"]), "std"),
        "qn_g": (lambda z: (z["hd"],), "ones"),
        "kn_g": (lambda z: (z["hd"],), "ones")},
}
_FFN = {
    "dense": {
        "gate_w": (lambda z: (z["d"], z["ff"]), "std"),
        "up_w": (lambda z: (z["d"], z["ff"]), "std"),
        "down_w": (lambda z: (z["ff"], z["d"]), "std")},
    "routed": {
        "router_w": (lambda z: (z["d"], z["E"]), "router_std"),
        "bias": (lambda z: (z["E"],), "bias_std"),
        "e_gate": (lambda z: (z["E"], z["d"], z["eff"]), "std"),
        "e_up": (lambda z: (z["E"], z["d"], z["eff"]), "std"),
        "e_down": (lambda z: (z["E"], z["eff"], z["d"]), "std")},
}
_TOP = {"wte": (lambda z: (z["vocab"], z["d"]), "std"),
        "lnf_g": (lambda z: (z["d"],), "ones"),
        "head_w": (lambda z: (z["d"], z["vocab"]), "std")}
#: computed in float32 by the configuration: never rounded to bfloat16
_FLOAT32 = ("router_w", "bias")


def _name_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def leaf(key, name: str, layer, shape, kind: str, sizes: dict):
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(jax.random.fold_in(key, _name_id(name)), layer)
    drawn = sizes[kind] * jax.random.normal(k, shape, jnp.float32)
    if name in _FLOAT32:
        return drawn
    return drawn.astype(jnp.bfloat16).astype(jnp.float32)


def layer_spec(kinds: tuple[str, str]) -> dict:
    mixer, ffn = kinds
    return {**_NORMS, **_MIXER[mixer], **_FFN[ffn]}


def layer_leaves(key, sizes: dict, layer, kinds: tuple[str, str]) -> dict:
    """One layer's leaves, float32; ``layer`` may be traced."""
    return {n: leaf(key, n, layer, shp(sizes), kind, sizes)
            for n, (shp, kind) in layer_spec(kinds).items()}


def top_leaves(key, sizes: dict, names=tuple(_TOP)) -> dict:
    return {n: leaf(key, n, 0, _TOP[n][0](sizes), _TOP[n][1], sizes)
            for n in names}


#: where the package's ``Transformer`` keeps each leaf
_FLAX_TOP = {"wte": ("tok_emb", "embedding"), "lnf_g": ("ln_f", "scale"),
             "head_w": ("lm_head", "kernel")}
_FLAX_LAYER = {
    "ln1_g": ("ln1", "scale"), "ln2_g": ("ln2", "scale"),
    "in_w": ("conv", "in_proj", "kernel"), "conv_w": ("conv", "conv_w"),
    "out_w": ("conv", "out_proj", "kernel"),
    "qkv_w": ("attn", "qkv", "kernel"), "proj_w": ("attn", "proj", "kernel"),
    "qn_g": ("attn", "q_norm", "scale"), "kn_g": ("attn", "k_norm", "scale"),
    "gate_w": ("mlp", "gate", "kernel"), "up_w": ("mlp", "up", "kernel"),
    "down_w": ("mlp", "down", "kernel"),
    "router_w": ("mlp", "router"), "bias": ("mlp", "expert_bias"),
    "e_gate": ("mlp", "w_gate", "kernel"), "e_up": ("mlp", "w_up", "kernel"),
    "e_down": ("mlp", "w_down", "kernel"),
}


def _held(name: str, value):
    """As the program holds it: bfloat16, the router's two in float32."""
    return value if name in _FLOAT32 else value.astype(jnp.bfloat16)


def flax_tree(seed: int, sizes: dict) -> dict:
    """The program's parameter tree (plain nested dicts), filled a layer at
    a time by one compiled draw a kind of layer, on the default device."""
    seed = seed_arg(seed)
    out: dict = {}

    def put(path, value):
        node = out
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = value

    @jax.jit
    def top(s):
        return {n: _held(n, v)
                for n, v in top_leaves(seed_key(s), sizes).items()}

    for name, value in top(seed).items():
        put(_FLAX_TOP[name], value)
    draw = {}
    for i, kinds in enumerate(sizes["layers"]):
        if kinds not in draw:
            draw[kinds] = jax.jit(
                lambda s, layer, kinds=kinds: {
                    n: _held(n, v) for n, v in layer_leaves(
                        seed_key(s), sizes, layer, kinds).items()})
        for name, value in draw[kinds](seed, np.int32(i)).items():
            put((f"block_{i}",) + _FLAX_LAYER[name], value)
    return out
