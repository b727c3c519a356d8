"""openPangu-Ultra-MoE's weights from a seed, the benchmark's own, a layer at
a time.

As ``weights_nemotron.py``: one leaf is one call of :func:`leaf`, a draw
keyed by (seed, leaf name, layer) or the constant a gain starts from,
rounded to bfloat16 (the type the configuration publishes its weights in)
and kept in float32: the seed's model *is* those bfloat16 numbers. The
program's tree holds them as bfloat16 and the plain reference multiplies
the same numbers in float32; neither side is handed anything the other has
made. What the model computes in float32 is never rounded: the router and
its selection bias (``_FLOAT32``), and the norms' gains.

An expert's three matrices are drawn by the expert's own number, so that a
program holding experts ``[first, first + held)`` of the layer gets the
numbers any other share would get for them. At the cell's size a float32
tree is 13.6 GB, so nothing here makes a whole tree at once
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

MIXER = "latent_attention"


def sizes_of(config: dict) -> dict:
    """The sizes the weights and the counts need, from a configuration
    file's keys (the published names), its ``assumed`` draws and, for what
    this chip holds of a layer, its ``deployment``."""
    dep, drawn = config["deployment"], config["assumed"]["drawn"]
    first, held = (int(x) for x in dep["experts_held"])
    if first + held > int(config["published"]["n_routed_experts"][
            "published"]) or held != int(config["n_routed_experts"]):
        raise ValueError("deployment.experts_held and n_routed_experts "
                         "disagree")
    dense = int(config["first_k_dense_replace"])
    depth = len(dep["layers_held"])
    if not 0 <= dense <= depth:
        raise ValueError("first_k_dense_replace lies past the layers held")
    layers = ((MIXER, "dense"),) * dense + ((MIXER, "routed"),) * (
        depth - dense)
    return {
        "d": int(config["hidden_size"]),
        "h": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v": int(config["v_head_dim"]),
        "ff": int(config["intermediate_size"]),
        "eff": int(config["moe_intermediate_size"]),
        "sff": int(config["n_shared_experts"])
        * int(config["moe_intermediate_size"]),
        "E": int(config["published"]["n_routed_experts"]["published"]),
        "first": first, "held": held,
        "k": int(config["num_experts_per_tok"]),
        "scale": float(config["routed_scaling_factor"]),
        "vocab": int(config["vocab_size"]), "layers": layers,
        "L": depth, "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]),
        "positions": int(dep["max_positions"]),
        "std": float(drawn["initializer_range"]),
        "router_std": float(drawn["router_std"]),
        "bias_std": float(drawn["expert_bias_std"]),
        "post": float(drawn["post_norm_gain"]),
    }


#: leaf name -> (shape from sizes, how it is drawn: the name of a standard
#: deviation in the sizes, or "ones", or "post" for the post-norms' gain)
_MIXER = {
    "ln1_g": (lambda z: (z["d"],), "ones"),
    "ln1_post_g": (lambda z: (z["d"],), "post"),
    "q_a_w": (lambda z: (z["d"], z["q_rank"]), "std"),
    "q_a_norm_g": (lambda z: (z["q_rank"],), "ones"),
    "q_b_w": (lambda z: (z["q_rank"], z["h"], z["nope"] + z["rope"]), "std"),
    "kv_a_w": (lambda z: (z["d"], z["kv_rank"] + z["rope"]), "std"),
    "kv_a_norm_g": (lambda z: (z["kv_rank"],), "ones"),
    "kv_b_w": (lambda z: (z["kv_rank"], z["h"], z["nope"] + z["v"]), "std"),
    "o_w": (lambda z: (z["h"], z["v"], z["d"]), "std"),
}
_FFN = {
    "dense": {
        "gate_w": (lambda z: (z["d"], z["ff"]), "std"),
        "up_w": (lambda z: (z["d"], z["ff"]), "std"),
        "down_w": (lambda z: (z["ff"], z["d"]), "std")},
    "routed": {
        "router_w": (lambda z: (z["d"], z["E"]), "router_std"),
        "bias": (lambda z: (z["E"],), "bias_std"),
        "e_gate": (lambda z: (z["held"], z["d"], z["eff"]), "std"),
        "e_up": (lambda z: (z["held"], z["d"], z["eff"]), "std"),
        "e_down": (lambda z: (z["held"], z["eff"], z["d"]), "std"),
        "s_gate": (lambda z: (z["d"], z["sff"]), "std"),
        "s_up": (lambda z: (z["d"], z["sff"]), "std"),
        "s_down": (lambda z: (z["sff"], z["d"]), "std")},
}
_NORMS = {"ln2_g": (lambda z: (z["d"],), "ones"),
          "ln2_post_g": (lambda z: (z["d"],), "post")}
_TOP = {"wte": (lambda z: (z["vocab"], z["d"]), "std"),
        "lnf_g": (lambda z: (z["d"],), "ones"),
        "head_w": (lambda z: (z["d"], z["vocab"]), "std")}
#: computed in float32 by the configuration: never rounded to bfloat16
_FLOAT32 = ("router_w", "bias")
#: the gains, which the program declares float32
_GAINS = ("ln1_g", "ln1_post_g", "q_a_norm_g", "kv_a_norm_g", "ln2_g",
          "ln2_post_g", "lnf_g")
#: a bank of experts: its first axis is drawn an expert at a time
_BANKS = ("e_gate", "e_up", "e_down")


def _name_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def leaf(key, name: str, layer, shape, kind: str, sizes: dict):
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "post":
        return jnp.full(shape, sizes["post"], jnp.float32)
    k = jax.random.fold_in(jax.random.fold_in(key, _name_id(name)), layer)
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
    """A layer is the latent attention and a feed-forward, each between
    its two norms."""
    return {**_MIXER, **_NORMS, **_FFN[kinds[1]]}


def layer_leaves(key, sizes: dict, layer, kinds: tuple) -> dict:
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
    "ln1_g": ("ln1", "scale"), "ln1_post_g": ("ln1_post", "scale"),
    "ln2_g": ("ln2", "scale"), "ln2_post_g": ("ln2_post", "scale"),
    "q_a_w": ("attn", "q_a_proj", "kernel"),
    "q_a_norm_g": ("attn", "q_a_norm", "scale"),
    "q_b_w": ("attn", "q_b_proj", "kernel"),
    "kv_a_w": ("attn", "kv_a_proj", "kernel"),
    "kv_a_norm_g": ("attn", "kv_a_norm", "scale"),
    "kv_b_w": ("attn", "kv_b_proj", "kernel"),
    "o_w": ("attn", "o_proj", "kernel"),
    "gate_w": ("mlp", "gate", "kernel"), "up_w": ("mlp", "up", "kernel"),
    "down_w": ("mlp", "down", "kernel"),
    "router_w": ("mlp", "router"), "bias": ("mlp", "expert_bias"),
    "e_gate": ("mlp", "w_gate", "kernel"), "e_up": ("mlp", "w_up", "kernel"),
    "e_down": ("mlp", "w_down", "kernel"),
    "s_gate": ("shared", "gate", "kernel"), "s_up": ("shared", "up", "kernel"),
    "s_down": ("shared", "down", "kernel"),
}


def _held(name: str, value):
    """As the program holds it: bfloat16, but the router, its bias and the
    gains in float32."""
    return value if name in _FLOAT32 + _GAINS else value.astype(jnp.bfloat16)


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


def parameters(sizes: dict) -> dict:
    """The parameters this chip holds, by part: ``embedding``, ``head``,
    ``mla`` (a layer's latent attention), ``dense`` (the leading layer's
    feed-forward), ``routed`` (a routed layer's router, shared expert and
    held experts), ``norms`` (every gain), ``total``."""
    z = sizes

    def count(spec):
        return sum(int(np.prod(shp(z))) for shp, _ in spec.values())

    gains = {n: s for n, s in {**_MIXER, **_NORMS}.items() if n in _GAINS}
    mla = count(_MIXER) - count(gains)
    parts = {"embedding": z["vocab"] * z["d"], "head": z["d"] * z["vocab"],
             "mla": mla, "dense": count(_FFN["dense"]),
             "routed": count(_FFN["routed"]),
             "norms": z["L"] * count(gains) + z["d"]}
    kinds = [f for _, f in z["layers"]]
    parts["total"] = (parts["embedding"] + parts["head"] + parts["norms"]
                      + z["L"] * mla + sum(parts[f] for f in kinds))
    return parts
