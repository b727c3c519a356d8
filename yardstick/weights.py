"""GPT-2 weights from a seed, the benchmark's own.

One leaf is one call of :func:`leaf`: a normal draw of standard deviation
0.02 keyed by (seed, leaf name, layer), or the constant GPT-2 starts a gain
or a bias from. The program's tree and the plain reference's stacked tree
are both filled from this function and from nothing else, so neither side
is handed anything the other has made. Everything is float32, the type the
program trains and serves, and a tree is made on the device in one jitted
call whose seed is an argument: the compiled program is the same for every
seed.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

STDDEV = 0.02

#: leaf name -> (shape from sizes, kind). ``L`` leaves exist once a layer.
_BLOCK = {
    "ln1_g": (lambda z: (z["d"],), "ones"),
    "ln1_b": (lambda z: (z["d"],), "zeros"),
    "qkv_w": (lambda z: (z["d"], 3, z["h"], z["hd"]), "normal"),
    "proj_w": (lambda z: (z["h"], z["hd"], z["d"]), "normal"),
    "ln2_g": (lambda z: (z["d"],), "ones"),
    "ln2_b": (lambda z: (z["d"],), "zeros"),
    "up_w": (lambda z: (z["d"], z["ff"]), "normal"),
    "up_b": (lambda z: (z["ff"],), "zeros"),
    "down_w": (lambda z: (z["ff"], z["d"]), "normal"),
}
_TOP = {
    "wte": (lambda z: (z["vocab"], z["d"]), "normal"),
    "wpe": (lambda z: (z["positions"], z["d"]), "normal"),
    "lnf_g": (lambda z: (z["d"],), "ones"),
    "lnf_b": (lambda z: (z["d"],), "zeros"),
    "head_w": (lambda z: (z["d"], z["vocab"]), "normal"),
}


def as_run(config: dict, key: str):
    """What the program runs for ``key``: the configuration's published
    value or, where the program cannot be set to it, the departure's."""
    departures = config.get("departures_forced_by_the_program", {})
    return departures[key]["run"] if key in departures else config[key]


def sizes_of(config: dict) -> dict:
    """The sizes the weights need, from a configuration file's keys."""
    d, h = int(config["n_embd"]), int(config["n_head"])
    if d % h:
        raise ValueError(f"n_embd {d} is not a multiple of n_head {h}")
    return {"d": d, "h": h, "hd": d // h, "ff": int(config["n_inner"]),
            "L": int(config["n_layer"]),
            "vocab": int(as_run(config, "vocab_size")),
            "positions": int(config["n_positions"])}


def seed_key(seed):
    """A key from any whole seed; ``seed`` may be traced (uint32)."""
    return jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32))


def seed_arg(seed: int) -> np.uint32:
    return np.uint32(int(seed) % 2 ** 32)


def _name_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def leaf(key, name: str, layer, shape, kind: str):
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    k = jax.random.fold_in(jax.random.fold_in(key, _name_id(name)), layer)
    return STDDEV * jax.random.normal(k, shape, jnp.float32)


def top_leaves(key, sizes: dict) -> dict:
    return {n: leaf(key, n, 0, shp(sizes), kind)
            for n, (shp, kind) in _TOP.items()}


def layer_leaves(key, sizes: dict, layer) -> dict:
    """One layer's leaves; ``layer`` may be traced (the reference vmaps it
    to get the stacked tree it scans over)."""
    return {n: leaf(key, n, layer, shp(sizes), kind)
            for n, (shp, kind) in _BLOCK.items()}


def stacked_tree(seed, sizes: dict) -> dict:
    """The plain reference's tree: top leaves, and ``layers`` with a
    leading axis of ``L``."""
    key = seed_key(seed)
    out = top_leaves(key, sizes)
    out["layers"] = jax.vmap(lambda i: layer_leaves(key, sizes, i))(
        jnp.arange(sizes["L"]))
    return out


#: where the package's ``Transformer`` keeps each leaf: module path under
#: the tree's root, or under ``block_<i>``
_FLAX_TOP = {"wte": ("tok_emb", "embedding"), "wpe": ("pos_emb", "embedding"),
             "lnf_g": ("ln_f", "scale"), "lnf_b": ("ln_f", "bias"),
             "head_w": ("lm_head", "kernel")}
_FLAX_BLOCK = {"ln1_g": ("ln1", "scale"), "ln1_b": ("ln1", "bias"),
               "qkv_w": ("attn", "qkv", "kernel"),
               "proj_w": ("attn", "proj", "kernel"),
               "ln2_g": ("ln2", "scale"), "ln2_b": ("ln2", "bias"),
               "up_w": ("mlp", "up", "kernel"), "up_b": ("mlp", "up", "bias"),
               "down_w": ("mlp", "down", "kernel")}


def _put(tree: dict, path: tuple, value) -> None:
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = value


def _get(tree: dict, path: tuple):
    for key in path:
        tree = tree[key]
    return tree


def flax_tree(seed, sizes: dict) -> dict:
    """The same leaves under the names the package's ``Transformer`` gives
    its parameters (plain nested dicts, no partitioning boxes)."""
    key = seed_key(seed)
    out: dict = {}
    for name, value in top_leaves(key, sizes).items():
        _put(out, _FLAX_TOP[name], value)
    for i in range(sizes["L"]):
        for name, value in layer_leaves(key, sizes, i).items():
            _put(out, (f"block_{i}",) + _FLAX_BLOCK[name], value)
    return out


def flat_names(sizes: dict) -> list[str]:
    """Leaf names of :func:`flat_of_flax`, in its order."""
    names = list(_TOP)
    for i in range(sizes["L"]):
        names.extend(f"{n}.{i}" for n in _BLOCK)
    return names


def flat_of_flax(tree: dict, sizes: dict) -> list:
    """The leaves of a tree shaped like :func:`flax_tree`, in
    :func:`flat_names` order: how the program's per-leaf norms are lined up
    with the reference's."""
    out = [_get(tree, _FLAX_TOP[n]) for n in _TOP]
    for i in range(sizes["L"]):
        out.extend(_get(tree[f"block_{i}"], _FLAX_BLOCK[n]) for n in _BLOCK)
    return out


def norms_of_stacked(tree: dict) -> jax.Array:
    """Per-leaf Euclidean norms of a stacked tree, in :func:`flat_names`
    order."""
    top = jnp.stack([jnp.linalg.norm(tree[n].ravel()) for n in _TOP])
    per_layer = jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(tree["layers"][n]).reshape(
            tree["layers"][n].shape[0], -1), axis=1)) for n in _BLOCK],
        axis=1)
    return jnp.concatenate([top, per_layer.reshape(-1)])


def norms_of_flax(tree: dict, sizes: dict) -> jax.Array:
    return jnp.stack([jnp.linalg.norm(x.astype(jnp.float32).ravel())
                      for x in flat_of_flax(tree, sizes)])
