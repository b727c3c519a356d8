"""Nemotron-H's weights from a seed, the benchmark's own, a layer at a time.

As ``weights_lfm2.py``: one leaf is one call of :func:`leaf`, a draw keyed
by (seed, leaf name, layer) or the constant a gain starts from, rounded to
bfloat16 (the type the configuration publishes its weights in) and kept in
float32: the seed's model *is* those bfloat16 numbers. The program's tree
holds them as bfloat16 and the plain reference multiplies the same numbers
in float32; neither side is handed anything the other has made. What the
model computes in float32 is never rounded: the router and its selection
bias, and a Mamba-2 mixer's step bias, decay rates and skip (``_FLOAT32``).

An expert's two matrices are drawn by the expert's own number, so that a
program holding experts ``[first, first + held)`` of the layer gets the
numbers any other share would get for them. At the cell's size a float32
tree is 15.7 GB, so nothing here makes a whole tree at once
(:func:`layer_leaves`, ``layer`` may be traced; :func:`flax_tree` fills the
program's tree layer by layer).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from yardstick import weights

seed_arg, seed_key = weights.seed_arg, weights.seed_key

#: a letter of ``hybrid_override_pattern`` -> the layer's (mixer, ffn)
KINDS = {"M": ("mamba2", None), "*": ("attention", None),
         "E": (None, "routed"), "-": (None, "dense")}


def sizes_of(config: dict) -> dict:
    """The sizes the weights and the counts need, from a configuration
    file's keys (the published names), its ``assumed`` draws and, for what
    this chip holds of a layer, its ``deployment``."""
    dep, drawn = config["deployment"], config["assumed"]["drawn"]
    kinds = tuple(KINDS[c] for c in config["hybrid_override_pattern"])
    H, P = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    G, N = int(config["n_groups"]), int(config["ssm_state_size"])
    first, held = (int(x) for x in dep["experts_held"])
    if first + held > int(config["published"]["n_routed_experts"][
            "published"]) or held != int(config["n_routed_experts"]):
        raise ValueError("deployment.experts_held and n_routed_experts "
                         "disagree")
    return {
        "d": int(config["hidden_size"]),
        "h": int(config["num_attention_heads"]),
        "kv": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]),
        "ff": int(config["intermediate_size"]),
        "eff": int(config["moe_intermediate_size"]),
        "eff_stored": int(dep.get("expert_width_stored",
                                  config["moe_intermediate_size"])),
        "sff": int(config["moe_shared_expert_intermediate_size"]),
        "E": int(config["published"]["n_routed_experts"]["published"]),
        "first": first, "held": held,
        "k": int(config["num_experts_per_tok"]),
        "scale": float(config["routed_scaling_factor"]),
        "H": H, "P": P, "G": G, "N": N, "inner": H * P,
        "wide": H * P + 2 * G * N, "taps": int(config["conv_kernel"]),
        "chunk": int(config["chunk_size"]),
        "vocab": int(config["vocab_size"]), "layers": kinds,
        "L": len(kinds), "eps": float(config["layer_norm_epsilon"]),
        "positions": int(dep["max_positions"]),
        "std": float(drawn["initializer_range"]),
        "router_std": float(drawn["router_std"]),
        "bias_std": float(drawn["expert_bias_std"]),
        "conv_std": float(drawn["conv_std"]),
        "dt_min": float(config["time_step_min"]),
        "dt_max": float(config["time_step_max"]),
        "dt_floor": float(config["time_step_floor"]),
        "a_max": float(drawn["a_max"]),
    }


#: leaf name -> (shape from sizes, how it is drawn: the name of a standard
#: deviation in the sizes, or "ones", "dt_bias", "a_log")
_MIXER = {
    "mamba2": {
        "in_w": (lambda z: (z["d"], z["inner"] + z["wide"] + z["H"]), "std"),
        "conv_w": (lambda z: (z["wide"], z["taps"]), "conv_std"),
        "conv_b": (lambda z: (z["wide"],), "conv_std"),
        "dt_bias": (lambda z: (z["H"],), "dt_bias"),
        "a_log": (lambda z: (z["H"],), "a_log"),
        "skip_d": (lambda z: (z["H"],), "ones"),
        "norm_g": (lambda z: (z["inner"],), "ones"),
        "out_w": (lambda z: (z["inner"], z["d"]), "std")},
    "attention": {
        "qkv_w": (lambda z: (z["d"], z["h"] + 2 * z["kv"], z["hd"]), "std"),
        "proj_w": (lambda z: (z["h"], z["hd"], z["d"]), "std")},
}
_FFN = {
    "dense": {
        "up_w": (lambda z: (z["d"], z["ff"]), "std"),
        "down_w": (lambda z: (z["ff"], z["d"]), "std")},
    "routed": {
        "router_w": (lambda z: (z["d"], z["E"]), "router_std"),
        "bias": (lambda z: (z["E"],), "bias_std"),
        "e_up": (lambda z: (z["held"], z["d"], z["eff"]), "std"),
        "e_down": (lambda z: (z["held"], z["eff"], z["d"]), "std"),
        "s_up": (lambda z: (z["d"], z["sff"]), "std"),
        "s_down": (lambda z: (z["sff"], z["d"]), "std")},
}
_TOP = {"wte": (lambda z: (z["vocab"], z["d"]), "std"),
        "lnf_g": (lambda z: (z["d"],), "ones"),
        "head_w": (lambda z: (z["d"], z["vocab"]), "std")}
#: computed in float32 by the configuration: never rounded to bfloat16
_FLOAT32 = ("router_w", "bias", "dt_bias", "a_log", "skip_d")
#: a bank of experts: its first axis is drawn an expert at a time
_BANKS = ("e_up", "e_down")


def _name_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def leaf(key, name: str, layer, shape, kind: str, sizes: dict):
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(jax.random.fold_in(key, _name_id(name)), layer)
    if kind == "dt_bias":
        # Mamba's own: a step size log-uniform in [dt_min, dt_max], floored,
        # through the inverse of the softplus
        lo, hi = np.log(sizes["dt_min"]), np.log(sizes["dt_max"])
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, lo, hi)), sizes["dt_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))
    if kind == "a_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0,
                                          sizes["a_max"]))
    if name in _BANKS:
        experts = sizes["first"] + jnp.arange(shape[0])
        drawn = sizes[kind] * jax.vmap(lambda e: jax.random.normal(
            jax.random.fold_in(k, e), shape[1:], jnp.float32))(experts)
    else:
        drawn = sizes[kind] * jax.random.normal(k, shape, jnp.float32)
    if name in _FLOAT32:
        return drawn
    return drawn.astype(jnp.bfloat16).astype(jnp.float32)


def layer_spec(kinds: tuple) -> dict:
    """A layer is one half: its norm's gain and the half's leaves."""
    mixer, ffn = kinds
    half = _MIXER[mixer] if mixer is not None else _FFN[ffn]
    return {"ln_g": (lambda z: (z["d"],), "ones"), **half}


def layer_leaves(key, sizes: dict, layer, kinds: tuple) -> dict:
    """One layer's leaves, float32; ``layer`` may be traced."""
    return {n: leaf(key, n, layer, shp(sizes), kind, sizes)
            for n, (shp, kind) in layer_spec(kinds).items()}


def top_leaves(key, sizes: dict, names=tuple(_TOP)) -> dict:
    return {n: leaf(key, n, 0, _TOP[n][0](sizes), _TOP[n][1], sizes)
            for n in names}


#: where the package's ``Transformer`` keeps each leaf (a layer's one norm
#: is ``ln1`` over a mixer and ``ln2`` over a feed-forward)
_FLAX_TOP = {"wte": ("tok_emb", "embedding"), "lnf_g": ("ln_f", "scale"),
             "head_w": ("lm_head", "kernel")}
_FLAX_LAYER = {
    "in_w": ("ssm", "in_proj", "kernel"), "conv_w": ("ssm", "conv_w"),
    "conv_b": ("ssm", "conv_b"), "dt_bias": ("ssm", "dt_bias"),
    "a_log": ("ssm", "A_log"), "skip_d": ("ssm", "D"),
    "norm_g": ("ssm", "norm_g"), "out_w": ("ssm", "out_proj", "kernel"),
    "qkv_w": ("attn", "qkv", "kernel"), "proj_w": ("attn", "proj", "kernel"),
    "up_w": ("mlp", "up", "kernel"), "down_w": ("mlp", "down", "kernel"),
    "router_w": ("mlp", "router"), "bias": ("mlp", "expert_bias"),
    "e_up": ("mlp", "w_up", "kernel"), "e_down": ("mlp", "w_down", "kernel"),
    "s_up": ("shared", "up", "kernel"), "s_down": ("shared", "down", "kernel"),
}


def flax_path(name: str, kinds: tuple) -> tuple:
    if name == "ln_g":
        return ("ln1" if kinds[0] is not None else "ln2", "scale")
    return _FLAX_LAYER[name]


def _held(name: str, value, sizes: dict):
    """As the program holds it: bfloat16, ``_FLOAT32``'s in float32 (and
    the gains and the convolution, which the program declares float32);
    the experts' banks with zeros past the expert's width up to the width
    the deployment stores them at (``expert_width_stored``)."""
    keep = name in _FLOAT32 + ("ln_g", "lnf_g", "norm_g", "conv_w", "conv_b")
    if name in _BANKS:
        axis = 2 if name == "e_up" else 1
        pad = [(0, 0)] * 3
        pad[axis] = (0, sizes["eff_stored"] - sizes["eff"])
        value = jnp.pad(value, pad)
    return value if keep else value.astype(jnp.bfloat16)


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
        return {n: _held(n, v, sizes)
                for n, v in top_leaves(seed_key(s), sizes).items()}

    for name, value in top(seed).items():
        put(_FLAX_TOP[name], value)
    draw = {}
    for i, kinds in enumerate(sizes["layers"]):
        if kinds not in draw:
            draw[kinds] = jax.jit(
                lambda s, layer, kinds=kinds: {
                    n: _held(n, v, sizes) for n, v in layer_leaves(
                        seed_key(s), sizes, layer, kinds).items()})
        for name, value in draw[kinds](seed, np.int32(i)).items():
            put((f"block_{i}",) + flax_path(name, kinds), value)
    return out
