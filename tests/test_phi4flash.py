"""A decoder-hybrid-decoder (Mamba-1 mixers, window attention over per-slot
rings, one full-attention layer whose paged cache the cross-attention
layers below it read, gated memory units, differential attention, a head
tied to the embedding) through the same ``ServeEngine`` as the other
models: chunked prefill and decode through the block pool, the rings and
the per-slot state against the plain reference's full forward
(``yardstick/reference/phi4flash.py``, which imports nothing from the
package), at a size the CPU holds, on seeded weights. The published order
of layers at a depth of 8: the window is 8 keys, a chunk 4, a block 4, so a
slot's ring is 12 positions and every prompt here wraps it."""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_guide_tpu.models import transformer as T
from distributed_tensorflow_guide_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
)
from distributed_tensorflow_guide_tpu.obs import events as obs_events
from distributed_tensorflow_guide_tpu.ops import decode_attention as DA
from distributed_tensorflow_guide_tpu.ops.ssm_scan import (
    selective_scan,
    selective_step,
)
from distributed_tensorflow_guide_tpu.serve import engine as E
from distributed_tensorflow_guide_tpu.serve.engine import Request, ServeEngine
from yardstick import weights_phi4flash
from yardstick.reference import phi4flash

SEED = 2 ** 31 + 35
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 4,
    "intermediate_size": 96, "sliding_window": 8, "mb_per_layer": 2,
    "vocab_size": 256, "tie_word_embeddings": True, "mlp_bias": False,
    "num_hidden_layers": 8, "layer_norm_eps": 1e-5,
    "assumed": {
        # the published order: the memory at L / 2, the full layer after it
        "layout": {"memory_layer": 4, "full_layer": 5},
        "mamba": {"expand": 2, "d_state": 16, "dt_rank": 4, "d_conv": 4},
        "drawn": {"initializer_range": 0.1, "bias_std": 0.1,
                  "lambda_std": 0.3, "conv_std": 0.29, "dt_std": 0.5,
                  "dt_min": 0.001, "dt_max": 0.1, "dt_floor": 1e-4}},
    "deployment": {"max_positions": 64},
}
Z = weights_phi4flash.sizes_of(CONFIG)
W, CHUNK, BLOCK = 8, 4, 4
RING = 12  # W - 1 + CHUNK = 11, in whole blocks and chunks
GEOMETRY = dict(slots=3, num_blocks=49, block_size=BLOCK,
                prefill_chunk=CHUNK)
#: float32 against float32 at HIGHEST: what is left is the order of the
#: sums (a chunk's softmax over a gathered view, the scan's steps fused)
TOL = dict(rtol=2e-4, atol=2e-4)


def config(dtype=jnp.float32, z=Z, **kw) -> TransformerConfig:
    return TransformerConfig(**{**dict(
        vocab_size=z["vocab"], num_layers=z["L"], num_heads=z["h"],
        d_model=z["d"], d_ff=z["ff"], max_len=z["positions"], dtype=dtype,
        layers=z["layers"], norm="layernorm", norm_eps=z["eps"],
        ffn_gate="silu", positions="none", num_kv_heads=z["kv"],
        conv_kernel=z["taps"], ssm_inner=z["inner"], ssm_state=z["N"],
        ssm_dt_rank=z["R"], window=z["window"], differential=True,
        attn_bias=True, tie_embeddings=True), **kw})


@pytest.fixture(scope="module")
def params():
    """The seed's tree as float32 (the bfloat16 numbers, widened): what
    both sides multiply, so that float32 runs agree to rounding."""
    return jax.tree.map(lambda x: x.astype(jnp.float32),
                        weights_phi4flash.flax_tree(SEED, Z))


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, Z["vocab"], n).astype(np.int32)
            for n in lengths]


def serve(cfg, tree, reqs, max_new=6, **kw):
    eng = ServeEngine(cfg, tree, temperature=0.0, **{**GEOMETRY, **kw})
    for i, p in enumerate(reqs):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=max_new,
                           rng=np.zeros((2,), np.uint32)))
    eng.run()
    eng.sched.pool.check_leaks()
    return eng


def reference_logits(tokens, fault=None):
    """The reference's logits at every position of ``tokens``, computed at
    one padded length so that every test finds the same compiled layers
    (padding after a token changes nothing before it: every mixer is
    causal)."""
    padded = np.zeros((Z["positions"],), np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(phi4flash.forward(SEED, padded, Z, fault=fault))[
        :len(tokens)]


def served_gaps(eng, reqs):
    """Per request, how far each served token's logit lies below the
    reference's best at its position."""
    out = []
    for i, prompt in enumerate(reqs):
        toks = np.concatenate(
            [prompt, np.asarray(eng.completions()[i], np.int32)])
        ref = reference_logits(toks)[:-1]
        at = np.arange(len(prompt) - 1, len(toks) - 1)
        out.append(ref[at].max(-1) - ref[at, toks[at + 1]])
    return out


def paged_logits(cfg, tree, tokens, chunk, slot=1, state=None, pool=None):
    """Logits at every position of ``tokens`` as the engine computes them:
    the prompt in chunks of ``chunk`` (the last one padded), through the
    block pool, the ring and the state leaves of slot ``slot``."""
    fns = E.build_step_fns(cfg, temperature=0.0, **GEOMETRY)
    if pool is None:
        pool = E.paged_cache_pool(fns.cfg, GEOMETRY["slots"])
    if state is None:
        state = E.slot_state(fns.cfg, GEOMETRY["slots"])
    tables = jnp.arange(1, 1 + fns.n_blk, dtype=jnp.int32)[None]
    step = _chunk_step(fns.model)
    out = []
    for start in range(0, len(tokens), chunk):
        piece = np.zeros((1, chunk), np.int32)
        valid = min(chunk, len(tokens) - start)
        piece[0, :valid] = tokens[start:start + valid]
        logits, mut = step(
            tree, pool, state, piece, jnp.full((1,), start, jnp.int32),
            tables, jnp.full((1,), slot, jnp.int32),
            jnp.full((1,), valid, jnp.int32))
        pool, state = mut["cache"], mut["state"]
        out.append(logits[0, :valid])
    return jnp.concatenate(out), state, pool


_STEPS: dict = {}


def _chunk_step(model):
    """``model.apply`` on one chunk of one slot, compiled once a model."""
    if model not in _STEPS:
        _STEPS[model] = jax.jit(
            lambda tree, pool, state, piece, start, tables, slot, valid:
            model.apply(
                {"params": tree, "cache": pool, "state": state}, piece,
                start, block_tables=tables, state_rows=slot, valid=valid,
                mutable=["cache", "state"]))
    return _STEPS[model]


# ---- the model against the reference ---------------------------------------


def test_the_tree_is_the_one_the_model_declares():
    shapes = jax.eval_shape(Transformer(config()).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    declared = nn.meta.unbox(shapes["params"])
    made = weights_phi4flash.flax_tree(SEED, Z)
    assert jax.tree.structure(declared) == jax.tree.structure(made)
    assert ([a.shape for a in jax.tree.leaves(declared)]
            == [a.shape for a in jax.tree.leaves(made)])
    # no positions of any kind, and the head is the embedding
    assert "pos_emb" not in made and "lm_head" not in made
    assert [m for m, _ in Z["layers"]] == [
        "mamba1", "window_attention", "mamba1", "window_attention", "mamba1",
        "attention", "gmu", "cross_attention"]
    assert set(made["block_0"]) == {"ln1", "ssm", "ln2", "mlp"}
    assert set(made["block_0"]["ssm"]) == {
        "in_proj", "conv_w", "conv_b", "x_proj", "dt_proj", "A_log", "D",
        "out_proj"}
    assert made["block_0"]["ssm"]["A_log"].shape == (128, 16)  # a channel
    assert set(made["block_0"]["ssm"]["dt_proj"]) == {"kernel", "bias"}
    assert set(made["block_0"]["ssm"]["in_proj"]) == {"kernel"}
    differential = {"lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2",
                    "subln", "proj"}
    assert set(made["block_1"]["attn"]) == differential | {"qkv"}
    assert set(made["block_5"]["attn"]) == differential | {"qkv"}
    assert set(made["block_7"]["attn"]) == differential | {"q"}  # no k, v
    assert set(made["block_6"]["gmu"]) == {"in_proj", "out_proj"}
    assert made["block_1"]["attn"]["qkv"]["bias"].shape == (16, 8)
    assert made["block_1"]["attn"]["proj"]["kernel"].shape == (4, 16, 64)
    assert made["block_1"]["attn"]["subln"].shape == (16,)  # a pair's value
    assert set(made["block_3"]["mlp"]) == {"gate", "up", "down"}
    assert set(made["ln_f"]) == {"scale", "bias"}
    ssm = made["block_0"]["ssm"]
    assert ssm["A_log"].dtype == ssm["dt_proj"]["bias"].dtype == jnp.float32
    assert ssm["in_proj"]["kernel"].dtype == jnp.bfloat16


def test_training_view_agrees_with_the_reference(params):
    (tokens,) = prompts([37], seed=1)
    got = jax.jit(Transformer(config()).apply)({"params": params},
                                               tokens[None])[0]
    np.testing.assert_allclose(np.asarray(got), reference_logits(tokens),
                               **TOL)


@pytest.mark.parametrize("chunk", [CHUNK, 1])
def test_chunked_prefill_and_decode_agree_with_the_full_forward(params,
                                                                chunk):
    """37 tokens, more than three rings: in chunks of 4 (the prefill
    program's path: nine whole chunks and one that is mostly padding, each
    from the state and the ring the one before left) and a token at a time
    (the decode program's path: the scan's single step, one slot of the
    ring a call), logits against the reference's at every position."""
    (tokens,) = prompts([37], seed=2)
    assert len(tokens) > 3 * RING > W + CHUNK
    got, _, _ = paged_logits(config(), params, tokens, chunk)
    np.testing.assert_allclose(np.asarray(got), reference_logits(tokens),
                               **TOL)


def test_the_engine_serves_what_the_reference_puts_first(params):
    # more requests than slots, every one longer than the ring when done
    reqs = prompts([5, 21, 14, 30, 9], seed=4)
    eng = serve(config(), params, reqs, max_new=12)
    assert eng.steps["prefill"] >= 20 and eng.steps["decode"] >= 12
    for i, gap in enumerate(served_gaps(eng, reqs)):
        # float32: a served token is the reference's own choice unless two
        # logits lie within rounding of each other
        assert gap.max() < 1e-3, (i, gap)


def test_bfloat16_serving_stays_near_the_reference():
    tree = weights_phi4flash.flax_tree(SEED, Z)
    reqs = prompts([13, 21, 9], seed=5)
    eng = serve(config(jnp.bfloat16), tree, reqs, max_new=10)
    gaps = np.concatenate(served_gaps(eng, reqs))
    assert np.mean(gaps) < 0.05, gaps
    state = eng.state
    assert state["block_0"]["ssm"]["ssm"].dtype == jnp.float32
    assert state["block_0"]["ssm"]["conv"].dtype == jnp.bfloat16
    assert state["block_1"]["attn"]["win_key"].dtype == jnp.bfloat16


# ---- the three kinds of storage ---------------------------------------------


def test_the_pool_holds_one_layers_leaves_each_key_and_value_once(params):
    eng = serve(config(), params, prompts([9], seed=6))
    assert set(eng.pool) == {"block_5"}  # the full layer's, and no other's
    leaves = eng.pool["block_5"]["attn"]
    # a pair of key heads is one head twice as wide: 2 x 16 = 4 x 8 numbers
    # a position, each key and value once
    assert {k: v.shape for k, v in leaves.items()} == {
        "cached_key": (49, 2, 16, BLOCK), "cached_value": (49, 2, 16, BLOCK)}
    health = eng.health()
    per_position = 2 * Z["kv"] * Z["hd"] * 4  # float32 here
    assert health["pool_bytes"] == 49 * BLOCK * per_position
    # the state beside it: 3 Mamba-1 layers' (channels x state, float32,
    # and 3 inputs of the convolution), 2 window layers' rings of 12
    # positions a slot and a block that takes idle rows' writes
    assert health["state_bytes"] == 3 * 3 * (128 * 16 * 4 + 3 * 128 * 4)
    assert health["window_bytes"] == 2 * (3 * RING + BLOCK) * per_position
    assert set(eng.state["block_1"]["attn"]) == {"win_key", "win_value"}
    assert set(eng.state["block_0"]["ssm"]) == {"conv", "ssm"}
    # nothing else is kept: the memory is an activation of the launch
    assert set(eng.state) == {"block_0", "block_1", "block_2", "block_3",
                              "block_4"}


@pytest.mark.parametrize("max_len", [64, 256])
def test_window_bytes_do_not_depend_on_the_longest_sequence(params, max_len):
    eng = ServeEngine(config(max_len=max_len), params, temperature=0.0,
                      slots=3, num_blocks=3 * max_len // BLOCK + 1,
                      block_size=BLOCK, prefill_chunk=CHUNK)
    health = eng.health()
    assert eng.fns.cfg.window_ring == RING == E.window_ring(W, BLOCK, CHUNK)
    assert health["window_bytes"] == 2 * 2 * (3 * RING + BLOCK) * 4 * 8 * 4
    assert health["pool_bytes"] == 2 * (3 * max_len + BLOCK) * 4 * 8 * 4
    # the published sizes: 639 positions needed, 640 kept, 5 blocks
    assert E.window_ring(512, 128, 128) == 640
    assert E.window_ring(512, 16, 128) == 640  # whole chunks too
    assert E.window_ring(8, 4, 6) == 24


def outputs_by_layer(cfg, tree, tokens, pool=None, state=None):
    """Every attention module's output for the decode step after
    ``tokens[:-1]`` were prefilled, by block name; also the pool and state
    the prefill left."""
    fns = E.build_step_fns(cfg, temperature=0.0, **GEOMETRY)
    if pool is None:
        _, state, pool = paged_logits(cfg, tree, tokens[:-1], CHUNK)
    tables = jnp.zeros((3, fns.n_blk), jnp.int32).at[1].set(
        jnp.arange(1, 1 + fns.n_blk))
    n = len(tokens) - 1
    _, mut = _DECODE_WATCHED(fns.model)(
        {"params": tree, "cache": pool, "state": state},
        jnp.asarray([[0], [tokens[-1]], [0]], jnp.int32),
        jnp.asarray([0, n, 0], jnp.int32), tables,
        jnp.asarray([0, 1, 0], jnp.int32))
    def row_1(result):  # the full layer's is ``(out, handed)``
        out = result[0] if isinstance(result, tuple) else result
        return np.asarray(out[1])

    outs = {path[0]: row_1(v["__call__"][0])
            for path, v in _by_module(mut["intermediates"])}
    return outs, pool, state


@functools.cache
def _DECODE_WATCHED(model):
    return jax.jit(lambda variables, toks, index, tables, valid: model.apply(
        variables, toks, index, block_tables=tables, valid=valid,
        mutable=["cache", "state", "intermediates"],
        capture_intermediates=lambda m, _: isinstance(
            m, (T.HybridAttention, T.MultiHeadAttention))))


def _by_module(tree, path=()):
    for k, v in tree.items():
        if "__call__" in v:
            yield path + (k,), v
        else:
            yield from _by_module(v, path + (k,))


def test_a_change_to_the_pools_leaves_moves_every_layer_that_reads_them(
        params):
    (tokens,) = prompts([19], seed=7)
    base, pool, state = outputs_by_layer(config(), params, tokens)
    assert set(base) == {"block_1", "block_3", "block_5", "block_7"}
    # one key of position 2 (block 1 of slot 1's table, slot 2 of the block)
    moved = jax.tree.map(lambda x: x, pool)
    moved["block_5"]["attn"]["cached_key"] = pool["block_5"]["attn"][
        "cached_key"].at[1, 0, 3, 2].add(3.0)
    after, _, _ = outputs_by_layer(config(), params, tokens, moved, state)
    for name in ("block_5", "block_7"):  # the full layer and the cross one
        assert np.abs(after[name] - base[name]).max() > 1e-4, name
    for name in ("block_1", "block_3"):  # the window layers read their rings
        np.testing.assert_array_equal(after[name], base[name])
    # a cross layer has no key or value of its own anywhere
    assert "block_7" not in pool and "block_7" not in state


# ---- the window ------------------------------------------------------------


@functools.cache
def window_layer_alone(differential: bool):
    """One window layer alone: ``(through_the_ring, training_view)``, each
    ``x (1, 30, d) -> (30, d)``. The first sends 30 positions through the
    ring of slot 1 of 2, five chunks of 4 and then a position at a time,
    from a ring that holds whatever the slot's last request left."""
    layers = (("window_attention", "dense"),)
    flat = T.HybridAttention(
        config(layers=layers, num_layers=1, differential=differential),
        kind="window_attention", layer=3)
    module = T.HybridAttention(
        config(layers=layers, num_layers=1, differential=differential,
               paged_num_blocks=9, paged_block_size=BLOCK, window_ring=RING,
               decode=True), kind="window_attention", layer=3)
    x = jnp.zeros((1, 30, Z["d"]))
    tree = jax.tree.map(lambda v: 3.0 * v, nn.meta.unbox(flat.init(
        jax.random.PRNGKey(1), x)["params"]))
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 1, Z["d"])),
        jnp.zeros((2,), jnp.int32), block_tables=jnp.zeros((2, 1), jnp.int32),
        valid=jnp.ones((2,), jnp.int32)))["state"]
    assert shapes["win_key"].shape[0] == 2 * (RING // BLOCK) + 1
    step = jax.jit(lambda state, piece, start: module.apply(
        {"params": tree, "state": state}, piece, start,
        block_tables=jnp.zeros((1, 1), jnp.int32),
        state_rows=jnp.ones((1,), jnp.int32),
        valid=jnp.full((1,), piece.shape[1], jnp.int32), mutable=["state"]))

    def through_the_ring(x):
        state = jax.tree.map(lambda s: jnp.full(s.shape, 9.0, s.dtype),
                             shapes)
        out, start = [], 0
        for size in [CHUNK] * 5 + [1] * 10:
            y, mut = step(state, x[:, start:start + size],
                          jnp.full((1,), start, jnp.int32))
            state = mut["state"]
            out.append(y)
            start += size
        return np.asarray(jnp.concatenate(out, axis=1)[0])

    training_view = jax.jit(lambda x: flat.apply({"params": tree}, x)[0])
    return through_the_ring, training_view


@pytest.mark.parametrize("moved, differential", [
    (2, True), (7, True), (10, True), (21, True), (10, False)])
def test_a_query_sees_the_keys_of_its_window_and_no_other(moved,
                                                          differential):
    """The input at position ``moved`` changes the layer's outputs at
    ``moved .. moved + 7`` and no other, across chunk boundaries (7 | 8) and
    across the ring's wrap (11 | 12, 23 | 24); and the outputs are the
    training view's, which has no ring. Plain heads too (not in pairs)."""
    through_the_ring, training_view = window_layer_alone(differential)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 30, Z["d"]))
    base = through_the_ring(x)
    np.testing.assert_allclose(base, np.asarray(training_view(x)),
                               rtol=1e-4, atol=1e-5)
    after = through_the_ring(x.at[0, moved].add(1.0))
    changed = np.abs(after - base).max(axis=-1) > 1e-6
    assert list(np.flatnonzero(changed)) == list(
        range(moved, min(30, moved + W)))


@pytest.mark.parametrize("chunk", [1, CHUNK])
def test_the_paged_kernel_takes_a_window_and_a_scale(chunk):
    """The Pallas kernel (interpret mode) with the two options this model
    brings, against the dense read of the same views: rows at lengths
    under the window, over it, and idle."""
    heads, kv_heads, hd, bs, n_blk = 8, 2, 16, 8, 3
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k[0], (3, chunk, heads, hd), jnp.float32)
    pool_k = jax.random.normal(k[1], (10, kv_heads, hd, bs), jnp.float32)
    pool_v = jax.random.normal(k[2], (10, kv_heads, hd, bs), jnp.float32)
    tables = jnp.asarray([[3, 1, 2], [4, 5, 6], [9, 9, 9]], jnp.int32)
    lengths = jnp.asarray([chunk + 2, 21, chunk], jnp.int32)
    got = DA.paged_decode_attention(
        q, pool_k, pool_v, tables, lengths, block_size=bs, blk_k=bs,
        scale=0.5, window=6)
    from distributed_tensorflow_guide_tpu.serve.paged_cache import (
        gather_view,
    )

    assert tables.shape[1] == n_blk
    want = T._dense_cache_read(q, gather_view(pool_k, tables),
                               gather_view(pool_v, tables), lengths - chunk,
                               "bhdk", jnp.float32, scale=0.5, window=6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    # without them it is the kernel it was
    plain = DA.paged_decode_attention(q, pool_k, pool_v, tables, lengths,
                                      block_size=bs, blk_k=bs)
    want = T._dense_cache_read(q, gather_view(pool_k, tables),
                               gather_view(pool_v, tables), lengths - chunk,
                               "bhdk", jnp.float32)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---- the memory and the lambdas ---------------------------------------------


def test_the_memory_units_gate_by_the_last_scans_output(params):
    """Every gated memory unit is handed the scan output of layer 4 (the
    last Mamba-1 layer) of the same forward pass; with zeros in its place
    the logits move; and it is kept nowhere."""
    (tokens,) = prompts([11], seed=8)
    model = Transformer(config())
    seen = {"scans": [], "memories": []}

    def watch(next_fn, args, kwargs, context):
        out = next_fn(*args, **kwargs)
        if context.method_name == "__call__":
            if isinstance(context.module, T.Mamba1):
                seen["scans"].append(out[1])
            elif isinstance(context.module, T.GatedMemory):
                seen["memories"].append(args[1])
        return out

    with nn.intercept_methods(watch):
        base = jax.jit(model.apply)({"params": params}, tokens[None])
    assert len(seen["scans"]) == 3 and len(seen["memories"]) == 1
    assert seen["scans"][-1].shape == (1, 11, 128)
    # the very value of the trace, float32, handed down the layers
    assert seen["memories"][0] is seen["scans"][-1]
    assert seen["memories"][0].dtype == jnp.float32

    def zeroed(next_fn, args, kwargs, context):
        if (isinstance(context.module, T.GatedMemory)
                and context.method_name == "__call__"):
            args = (args[0], jnp.zeros_like(args[1]))
        return next_fn(*args, **kwargs)

    with nn.intercept_methods(zeroed):
        without = jax.jit(lambda v, t: model.apply(v, t))(
            {"params": params}, tokens[None])
    assert float(jnp.max(jnp.abs(without - base))) > 0.05
    np.testing.assert_allclose(
        np.asarray(without[0]), reference_logits(tokens, fault="no_memory"),
        **TOL)


@pytest.mark.parametrize("layer", [1, 5, 17])
def test_lambda_starts_from_the_layers_own_value(params, layer):
    """``lambda_init = 0.8 - 0.6 exp(-0.3 layer)``: the module at a layer's
    index against the reference's differential attention at that index,
    and not at another."""
    cfg = config()
    p = params["block_5"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(layer), (1, 13, Z["d"]))
    got, handed = T.MultiHeadAttention(cfg, layer=layer).apply(
        {"params": p}, x, hand_kv=True)
    assert handed[0].shape == (1, 2, 16, 13)  # pairs of key heads, as kept

    def reference(i):
        leaves = {
            "lq1": p["lambda_q1"], "lk1": p["lambda_k1"],
            "lq2": p["lambda_q2"], "lk2": p["lambda_k2"],
            "subln_g": p["subln"], "proj_w": p["proj"]["kernel"],
            "proj_b": p["proj"]["bias"]}
        qkv = phi4flash.projected(x[0], p["qkv"]["kernel"], p["qkv"]["bias"],
                                  "float32")
        return phi4flash.differential_attention(
            qkv[:, :8], qkv[:, 8:12], qkv[:, 12:], leaves, layer=i,
            window=None, sizes=Z, operands="float32", fault=None)

    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(reference(
        layer)), rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(got[0] - reference(layer + 2)))) > 1e-3
    start = 0.8 - 0.6 * np.exp(-0.3 * layer)
    assert 0.2 <= start < 0.8


@pytest.mark.parametrize("kw", [
    dict(), dict(positions="rotary", rope_theta=1e4, qk_norm=True)])
def test_heads_in_pairs_are_the_one_attention_modules(kw):
    """A pattern of plain ``attention`` layers with biases and heads in
    pairs runs ``MultiHeadAttention``, with whatever else that module
    wires (rotary positions, head norms): chunked prefill through the pool
    (a pair of key heads one head twice as wide) against the training
    view."""
    cfg = TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=4, num_kv_heads=2, d_model=32,
        d_ff=48, max_len=Z["positions"], dtype=jnp.float32,
        layers=(("attention", "dense"),) * 2, ffn_gate="silu",
        differential=True, attn_bias=True, **{"positions": "none", **kw})
    model = Transformer(cfg)
    tokens = np.random.default_rng(3).integers(0, 64, 19).astype(np.int32)
    tree = jax.tree.map(lambda v: 2.0 * v, nn.meta.unbox(model.init(
        jax.random.PRNGKey(2), tokens[None])["params"]))
    assert set(tree["block_1"]["attn"]) >= {"lambda_q1", "subln", "qkv"}
    assert tree["block_1"]["attn"]["qkv"]["bias"].shape == (8, 8)
    want = model.apply({"params": tree}, tokens[None])[0]
    got, _, pool = paged_logits(cfg, tree, tokens, CHUNK)
    assert pool["block_0"]["attn"]["cached_key"].shape == (49, 1, 16, BLOCK)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


# ---- the scan --------------------------------------------------------------


def scan_inputs(B=2, S=21, D=12, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(k[0], (B, S, D), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, S, D)) - 1.0)
    a = -jnp.exp(jax.random.uniform(k[2], (D, N), minval=0.0, maxval=2.5))
    b = jax.random.normal(k[3], (B, S, N), jnp.float32)
    c = jax.random.normal(k[4], (B, S, N), jnp.float32)
    state = jax.random.normal(k[5], (B, D, N), jnp.float32)
    return u, dt, a, b, c, state


@pytest.mark.parametrize("positions", [1, 21])
def test_the_scan_is_its_step_applied_in_turn(positions):
    """One position and 21 from a carried state that is not zero against
    the recurrence's one step in a Python loop, and from zeros against the
    reference's own scan."""
    u, dt, a, b, c, state = scan_inputs(S=positions)
    ys, carried = [], state
    for t in range(u.shape[1]):
        y, carried = selective_step(u[:, t], dt[:, t], a, b[:, t], c[:, t],
                                    carried)
        ys.append(y)
    got_y, got_state = selective_scan(u, dt, a, b, c, state)
    np.testing.assert_allclose(np.asarray(got_y),
                               np.asarray(jnp.stack(ys, axis=1)), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_state), np.asarray(carried),
                               rtol=1e-6, atol=1e-6)
    got, _ = selective_scan(u[:1], dt[:1], a, b[:1], c[:1],
                            jnp.zeros_like(state[:1]))
    ref = phi4flash.recurrence(u[0], dt[0], a, b[0], c[0])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_a_step_of_size_zero_moves_no_state_and_a_run_can_be_split():
    u, dt, a, b, c, state = scan_inputs(seed=1)
    padded = dt.at[:, 13:].set(0.0)  # positions 13.. are padding
    _, after = selective_scan(u, padded, a, b, c, state)
    _, want = selective_scan(u[:, :13], dt[:, :13], a, b[:, :13], c[:, :13],
                             state)
    np.testing.assert_allclose(np.asarray(after), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    _, same = selective_step(u[:, 0], jnp.zeros_like(dt[:, 0]), a, b[:, 0],
                             c[:, 0], state)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(state))
    y1, mid = selective_scan(u[:, :8], dt[:, :8], a, b[:, :8], c[:, :8],
                             state)
    y2, end = selective_scan(u[:, 8:], dt[:, 8:], a, b[:, 8:], c[:, 8:], mid)
    whole_y, whole = selective_scan(u, dt, a, b, c, state)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2], 1)), np.asarray(whole_y),
        rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(end), np.asarray(whole),
                               rtol=1e-6, atol=1e-6)


# ---- rows, slots and requests ----------------------------------------------


def test_padding_and_idle_rows_leave_every_state_as_it_was(params):
    (tokens,) = prompts([13], seed=3)
    cfg = config()
    _, padded, _ = paged_logits(cfg, params, tokens, CHUNK)  # 3 x 4 + 1 of 4
    _, single, _ = paged_logits(cfg, params, tokens, 1)  # never padded
    nb = RING // BLOCK
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(padded),
                            jax.tree.leaves(single)):
        name = path[-1].key
        if name in ("conv", "ssm"):
            np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b[1]),
                                       rtol=1e-5, atol=1e-5)
            assert np.any(np.asarray(a[1]))
            assert not np.any(np.asarray(a[0])) and not np.any(
                np.asarray(a[2]))
            continue
        # a ring: slot 1's blocks hold positions 1..12 (position 0's slot
        # was taken by position 12); the chunk's three padding positions
        # landed on slots 1..3, whose keys (positions 1..3) no query at or
        # after position 12 sees: what every later query can see is what
        # the unpadded run holds
        own = np.asarray(a[nb:2 * nb]).transpose(1, 2, 0, 3).reshape(
            a.shape[1], a.shape[2], RING)
        want = np.asarray(b[nb:2 * nb]).transpose(1, 2, 0, 3).reshape(
            a.shape[1], a.shape[2], RING)
        live = [p % RING for p in range(13 - W, 13)]
        np.testing.assert_allclose(own[..., live], want[..., live],
                                   rtol=1e-5, atol=1e-5)
        assert not np.any(np.asarray(a[:nb])) and not np.any(
            np.asarray(a[2 * nb:3 * nb]))
    # a decode launch in which only slot 1 is live: the other rows keep
    # what they hold to the last bit, rings too
    fns = E.build_step_fns(cfg, temperature=0.0, **GEOMETRY)
    marked = jax.tree.map(lambda x: x + 0.5, single)
    pool = E.paged_cache_pool(fns.cfg, 3)
    tables = jnp.zeros((3, fns.n_blk), jnp.int32).at[1].set(
        jnp.arange(1, 1 + fns.n_blk))
    _, _, after, _ = fns.decode(
        params, pool, marked, tables, jnp.asarray([0, 13, 0], jnp.int32),
        jnp.asarray([7, 7, 7], jnp.int32), jnp.zeros((3, 2), jnp.uint32))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(after),
                            jax.tree.leaves(marked)):
        rows = nb if path[-1].key.startswith("win_") else 1
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_array_equal(a[:rows], b[:rows])
        np.testing.assert_array_equal(a[2 * rows:3 * rows],
                                      b[2 * rows:3 * rows])
        assert np.any(a[rows:2 * rows] != b[rows:2 * rows])


def test_a_reused_slot_reads_nothing_of_the_request_before(params):
    """One slot, two requests one after the other: the second is served as
    it is alone in a fresh engine, though the slot's state and rings still
    hold the first's when its first chunk runs."""
    first, second = prompts([27, 15], seed=9)
    both = serve(config(), params, [first, second], slots=1)
    alone = serve(config(), params, [second], slots=1)
    assert both.completions()[1] == alone.completions()[0]
    assert both.completions()[0] != both.completions()[1]
    cfg = config()
    _, dirty, _ = paged_logits(cfg, params, first, CHUNK)
    got, _, _ = paged_logits(cfg, params, second, CHUNK, state=dirty)
    want, _, _ = paged_logits(cfg, params, second, CHUNK)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_preempted_request_resumes_to_the_same_tokens(params):
    reqs = prompts([13, 21, 9], seed=6)
    roomy = serve(config(), params, reqs, max_new=20)
    # 12 blocks of 4 beside the trash block: three residents outgrow them
    tight = serve(config(), params, reqs, max_new=20, num_blocks=13)
    assert tight.health()["preemptions"] > 0 == roomy.health()["preemptions"]
    assert tight.completions() == roomy.completions()


def test_what_moves_blocks_alone_refuses_a_model_with_state(params):
    cfg = config()
    assert cfg.stateful and cfg.state_mixers == ("mamba1",
                                                 "window_attention")
    for kw in ({"prefix_cache": True}, {"host_blocks": 8}):
        with pytest.raises(ValueError,
                           match="mamba1 and window_attention mixers"):
            ServeEngine(cfg, params, **GEOMETRY, **kw)
    with pytest.raises(ValueError, match="persist_cache requires"):
        ServeEngine(cfg, params, **GEOMETRY, persist_cache=True)
    eng = ServeEngine(cfg, params, temperature=0.0, **GEOMETRY)
    (prompt,) = prompts([17], seed=7)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=8,
                       rng=np.zeros((2,), np.uint32)))
    for _ in range(7):
        eng.step(0.0)
    with pytest.raises(ValueError, match="with_kv=False"):
        eng.export_stream(0, with_kv=True)
    other = ServeEngine(cfg, params, temperature=0.0, **GEOMETRY)
    other.adopt_stream(eng.export_stream(0, with_kv=False))
    other.run()
    whole = serve(cfg, params, [prompt], max_new=8)
    assert other.completions()[0] == whole.completions()[0]


def test_a_decode_launchs_span_says_what_keys_it_read(params):
    """``engine.apply`` of a decode launch carries ``live_keys`` (the rows'
    lengths after the launch's write, summed) and ``window_keys`` (each
    capped at the window); a prefill launch's carries neither."""
    rec = obs_events.FlightRecorder(capacity=1 << 14)
    serve(config(), params, prompts([5], seed=10), max_new=6, recorder=rec)
    begun = [e.payload for e in rec.events() if e.kind == "span.begin"]
    program = {p["tick"]: p["program"] for p in begun
               if p["name"] == "engine.dispatch"}
    applied = [p for p in begun if p["name"] == "engine.apply"]
    decodes = [p for p in applied if program[p["tick"]] == "decode_step"]
    assert decodes and all("live_keys" not in p for p in applied
                           if program[p["tick"]] != "decode_step")
    # the prompt's 5 keys and the first token's, then one more a launch
    assert [p["live_keys"] for p in decodes] == [6, 7, 8, 9, 10]
    assert [p["window_keys"] for p in decodes] == [6, 7, W, W, W]


# ---- the configuration -----------------------------------------------------


@pytest.mark.parametrize("kw, message", [
    (dict(ssm_dt_rank=None), "ssm_inner, ssm_state and ssm_dt_rank"),
    (dict(window=None), "take a window"),
    (dict(layers=(("gmu", "dense"),) * 8), "last mamba1 layer before"),
    (dict(layers=(("cross_attention", "dense"),) * 8),
     "last attention layer before"),
    (dict(layers=(("attention", "dense"),) * 8), "take a window"),
    (dict(rope_theta=1e4, positions="rotary"), "rotary positions or qk_norm"),
    (dict(qk_norm=True), "rotary positions or qk_norm"),
    (dict(num_kv_heads=1), "must be even"),
    (dict(window_ring=10), "whole blocks"),
])
def test_a_pattern_that_cannot_run_is_refused_by_name(kw, message):
    with pytest.raises(ValueError, match=message):
        config(**kw)


def test_the_new_sizes_are_a_patterned_models():
    for kw in (dict(ssm_inner=8), dict(window=4), dict(differential=True),
               dict(attn_bias=True), dict(tie_embeddings=True)):
        with pytest.raises(ValueError, match="layers"):
            TransformerConfig(**kw)
    cfg = config()
    assert cfg.position_kind == "none"
    assert cfg.state_mixers == ("mamba1", "window_attention")
    plain = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=2, d_model=16, d_ff=32,
        max_len=32, layers=(("attention", "dense"),))
    assert not plain.stateful
    # a window model is paged for a chunk's length
    with pytest.raises(ValueError, match="prefill_chunk"):
        E.paged_config(cfg, num_blocks=9, block_size=4)
    paged = E.paged_config(cfg, num_blocks=9, block_size=4, prefill_chunk=4)
    assert paged.window_ring == RING and paged.decode
