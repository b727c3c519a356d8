"""A model given as a pattern of layers (short-convolution and grouped-query
attention mixers over dense and routed feed-forwards) through the same
``ServeEngine`` as GPT-2: chunked prefill and decode through the block pool
and the per-slot state leaf against the plain reference's full forward
(``yardstick/reference/lfm2.py``, which imports nothing from the package),
at a size the CPU holds, on seeded weights."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_guide_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
)
from distributed_tensorflow_guide_tpu.ops import decode_attention as DA
from distributed_tensorflow_guide_tpu.ops.routed_ffn import routed_ffn
from distributed_tensorflow_guide_tpu.serve import engine as E
from distributed_tensorflow_guide_tpu.serve.engine import Request, ServeEngine
from yardstick import weights_lfm2
from yardstick.reference import lfm2

SEED = 2 ** 31 + 28
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "conv_L_cache": 3, "vocab_size": 256,
    "norm_eps": 1e-5, "rope_parameters": {"rope_theta": 1e6},
    "layer_types": ["conv", "full_attention", "conv", "conv"],
    "num_dense_layers": 1,
    "assumed": {"drawn": {"initializer_range": 0.1, "router_std": 0.5,
                          "expert_bias_std": 0.05, "conv_std": 0.33}},
    "deployment": {"max_positions": 64},
}
Z = weights_lfm2.sizes_of(CONFIG)
GEOMETRY = dict(slots=3, num_blocks=25, block_size=8, prefill_chunk=8)


def config(dtype=jnp.float32, **kw) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=Z["vocab"], num_layers=Z["L"], num_heads=Z["h"],
        d_model=Z["d"], d_ff=Z["ff"], max_len=Z["positions"], dtype=dtype,
        layers=Z["layers"], norm="rmsnorm", norm_eps=Z["eps"],
        ffn_gate="silu", rope_theta=Z["theta"], num_kv_heads=Z["kv"],
        qk_norm=True, conv_kernel=Z["taps"], routed_experts=Z["E"],
        routed_top_k=Z["k"], routed_d_ff=Z["eff"], **kw)


@pytest.fixture(scope="module")
def params():
    """The seed's tree as float32 (the bfloat16 numbers, widened): what
    both sides multiply, so that float32 runs agree to rounding."""
    return jax.tree.map(lambda x: x.astype(jnp.float32),
                        weights_lfm2.flax_tree(SEED, Z))


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, Z["vocab"], n).astype(np.int32)
            for n in lengths]


def serve(cfg, tree, reqs, max_new=6, **geometry):
    eng = ServeEngine(cfg, tree, temperature=0.0,
                      **{**GEOMETRY, **geometry})
    for i, p in enumerate(reqs):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=max_new,
                           rng=np.zeros((2,), np.uint32)))
    eng.run()
    eng.sched.pool.check_leaks()
    return eng


def test_the_tree_is_the_one_the_model_declares():
    import flax.linen as nn

    shapes = jax.eval_shape(Transformer(config()).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    declared = nn.meta.unbox(shapes["params"])
    made = weights_lfm2.flax_tree(SEED, Z)
    assert jax.tree.structure(declared) == jax.tree.structure(made)
    assert ([a.shape for a in jax.tree.leaves(declared)]
            == [a.shape for a in jax.tree.leaves(made)])
    assert "pos_emb" not in made  # rotary positions: no table
    routed = made["block_1"]["mlp"]
    assert routed["router"].dtype == routed["expert_bias"].dtype == jnp.float32
    assert routed["w_gate"]["kernel"].dtype == jnp.bfloat16


def test_training_view_agrees_with_the_reference(params):
    (tokens,) = prompts([23], seed=1)
    got = Transformer(config()).apply({"params": params}, tokens[None])[0]
    want = lfm2.forward(SEED, tokens, Z)
    # float32 on both sides: what differs is the order of sums (XLA's
    # products against HIGHEST ones, a sorted grouped product against a
    # masked sum over all experts); logits are O(1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def paged_logits(cfg, tree, tokens, chunk, slot=1):
    """Logits at every position of ``tokens`` as the engine computes them:
    the prompt in chunks of ``chunk`` (the last one padded), through the
    block pool and the state leaf of slot ``slot``."""
    fns = E.build_step_fns(cfg, temperature=0.0, **GEOMETRY)
    pool = E.paged_cache_pool(fns.cfg, GEOMETRY["slots"])
    state = E.slot_state(fns.cfg, GEOMETRY["slots"])
    n_blk = fns.n_blk
    tables = jnp.arange(1, 1 + n_blk, dtype=jnp.int32)[None]
    out = []
    for start in range(0, len(tokens), chunk):
        piece = np.zeros((1, chunk), np.int32)
        valid = min(chunk, len(tokens) - start)
        piece[0, :valid] = tokens[start:start + valid]
        logits, mut = fns.model.apply(
            {"params": tree, "cache": pool, "state": state}, piece,
            jnp.full((1,), start, jnp.int32), block_tables=tables,
            state_rows=jnp.full((1,), slot, jnp.int32),
            valid=jnp.full((1,), valid, jnp.int32),
            mutable=["cache", "state"])
        pool, state = mut["cache"], mut["state"]
        out.append(logits[0, :valid])
    return jnp.concatenate(out), state


@pytest.mark.parametrize("chunk", [8, 1])
def test_chunked_prefill_and_decode_agree_with_the_full_forward(params,
                                                                chunk):
    """Chunks of 8 are the prefill program's path (21 tokens: two whole
    chunks and a padded one); chunks of 1 are what decode does, a token at
    a time through the pool and the state."""
    (tokens,) = prompts([21], seed=2)
    got, _ = paged_logits(config(), params, tokens, chunk)
    want = lfm2.forward(SEED, tokens, Z)
    # float32: sums in another order, as above, and the softmax over a
    # gathered view of max_len keys with the dead ones masked
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_a_padded_last_chunk_leaves_the_state_of_its_last_valid_position(
        params):
    (tokens,) = prompts([13], seed=3)
    cfg = config()
    _, padded = paged_logits(cfg, params, tokens, 8)  # 8 + 5 of 8
    _, single = paged_logits(cfg, params, tokens, 1)  # never padded
    for a, b in zip(jax.tree.leaves(padded), jax.tree.leaves(single)):
        np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b[1]),
                                   rtol=1e-5, atol=1e-6)
        assert not np.any(np.asarray(a[0])) and not np.any(np.asarray(a[2]))
    # the same prompt in one chunk of 16 and in two of 8: the same tokens
    one = serve(cfg, params, [tokens], prefill_chunk=16, block_size=16,
                num_blocks=13)
    two = serve(cfg, params, [tokens])
    assert one.completions()[0] == two.completions()[0]


def test_the_engine_serves_what_the_reference_puts_first(params):
    reqs = prompts([5, 13, 8, 21, 9], seed=4)  # more requests than slots
    eng = serve(config(), params, reqs)
    assert eng.steps["prefill"] >= 8 and eng.steps["decode"] >= 6
    for i, prompt in enumerate(reqs):
        served = eng.completions()[i]
        toks = np.concatenate([prompt, np.asarray(served, np.int32)])
        ref = np.asarray(lfm2.forward(SEED, toks, Z))[:-1]
        at = np.arange(len(prompt) - 1, len(toks) - 1)
        gap = ref[at].max(-1) - ref[at, toks[at + 1]]
        # float32: a served token is the reference's own choice unless two
        # logits lie within rounding of each other
        assert gap.max() < 1e-3, (i, gap)


def test_bfloat16_serving_stays_near_the_reference():
    tree = weights_lfm2.flax_tree(SEED, Z)
    reqs = prompts([13, 21, 9], seed=5)
    eng = serve(config(jnp.bfloat16), tree, reqs, max_new=8)
    gaps = []
    for i, prompt in enumerate(reqs):
        toks = np.concatenate(
            [prompt, np.asarray(eng.completions()[i], np.int32)])
        ref = np.asarray(lfm2.forward(SEED, toks, Z))[:-1]
        at = np.arange(len(prompt) - 1, len(toks) - 1)
        gaps.extend(ref[at].max(-1) - ref[at, toks[at + 1]])
    # bfloat16 activations (8 bits of mantissa) through 4 layers move a
    # logit of O(1) by some 1e-2; a wrong token would lie ~1 below the best
    assert np.mean(gaps) < 0.05, gaps


def test_a_preempted_request_resumes_to_the_same_tokens(params):
    reqs = prompts([13, 21, 9], seed=6)
    roomy = serve(config(), params, reqs, max_new=20)
    # 6 blocks of 8 beside the trash block: three residents outgrow them
    tight = serve(config(), params, reqs, max_new=20, num_blocks=7)
    assert tight.health()["preemptions"] > 0 == roomy.health()["preemptions"]
    assert tight.completions() == roomy.completions()


def test_what_moves_blocks_alone_refuses_a_model_with_state(params):
    cfg = config()
    assert cfg.stateful
    for kw in ({"prefix_cache": True}, {"host_blocks": 8}):
        with pytest.raises(ValueError, match="state beside"):
            ServeEngine(cfg, params, **GEOMETRY, **kw)
    eng = ServeEngine(cfg, params, temperature=0.0, **GEOMETRY)
    (prompt,) = prompts([9], seed=7)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=8,
                       rng=np.zeros((2,), np.uint32)))
    for _ in range(4):
        eng.step(0.0)
    with pytest.raises(ValueError, match="with_kv=False"):
        eng.export_stream(0, with_kv=True)
    record = eng.export_stream(0, with_kv=False)
    with pytest.raises(ValueError, match="KV payloads"):
        eng.adopt_stream({**record, "payloads": [[np.zeros(1)]]})
    # the continuation alone is adopted, re-prefills, and ends the same
    other = ServeEngine(cfg, params, temperature=0.0, **GEOMETRY)
    other.adopt_stream(record)
    other.run()
    whole = serve(cfg, params, [prompt], max_new=8)
    assert other.completions()[0] == whole.completions()[0]
    # attention alone carries no such state and keeps its prefix cache
    plain = dataclasses.replace(
        cfg, layers=(("attention", "dense"),) * 2, num_layers=2)
    assert not plain.stateful


def test_the_sizes_of_a_patterned_model_are_refused_without_layers():
    with pytest.raises(ValueError, match="layers"):
        TransformerConfig(num_kv_heads=2)
    with pytest.raises(ValueError, match="no wiring"):
        config(lora_rank=2, lora_adapters=1)
    with pytest.raises(ValueError, match="paged engine only"):
        from distributed_tensorflow_guide_tpu.models.generation import (
            decode_config,
        )
        Transformer(decode_config(config())).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), 0)


# ---- the routed layer ------------------------------------------------------


def routed_leaves(layer=1):
    p = weights_lfm2.layer_leaves(weights_lfm2.seed_key(
        weights_lfm2.seed_arg(SEED)), Z, layer, Z["layers"][layer])
    x = jax.random.normal(jax.random.PRNGKey(3), (40, Z["d"]), jnp.float32)
    return p, x


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    p, x = routed_leaves()
    whole, mask = lfm2.routed_ffn(x, p, k=Z["k"], operands="float32")
    parts, loads = [], []
    for first in range(0, Z["E"], 2):  # 4 programs of 2 experts each
        held = slice(first, first + 2)
        y, load = routed_ffn(
            x, p["router_w"], p["bias"], p["e_gate"][held], p["e_up"][held],
            p["e_down"][held], top_k=Z["k"], first=first)
        parts.append(y)
        loads.append(load)
    # the router is every share's alike and counted once: the census
    assert all(np.array_equal(loads[0], l) for l in loads)
    np.testing.assert_array_equal(
        np.asarray(loads[0]), np.asarray((mask > 0).sum(0)))
    assert int(loads[0].sum()) == 40 * Z["k"]  # no token is dropped
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    # a share's part is the reference's part for the same experts
    ref_part, _ = lfm2.routed_ffn(x, p, k=Z["k"], operands="float32",
                                  first=2, count=2)
    np.testing.assert_allclose(np.asarray(parts[1]), np.asarray(ref_part),
                               rtol=1e-4, atol=1e-5)


def test_absent_experts_and_padding_rows_add_nothing():
    p, x = routed_leaves()
    live = jnp.arange(40) < 25
    y, load = routed_ffn(
        x, p["router_w"], p["bias"], p["e_gate"], p["e_up"], p["e_down"],
        top_k=Z["k"], live=live)
    assert not np.any(np.asarray(y[25:])) and np.any(np.asarray(y[:25]))
    assert int(load.sum()) == 25 * Z["k"]
    # a token none of whose experts are held gets exactly nothing
    _, mask = lfm2.routed_ffn(x, p, k=Z["k"], operands="float32")
    y0, _ = routed_ffn(
        x, p["router_w"], p["bias"], p["e_gate"][:1], p["e_up"][:1],
        p["e_down"][:1], top_k=Z["k"], first=0)
    elsewhere = np.asarray(mask[:, 0] == 0)
    assert elsewhere.any() and not np.any(np.asarray(y0)[elsewhere])


def test_the_step_hands_back_the_census_of_each_routed_layer(params):
    eng = ServeEngine(config(), params, temperature=0.0, **GEOMETRY)
    for i, p in enumerate(prompts([5, 9], seed=8)):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=4,
                           rng=np.zeros((2,), np.uint32)))
    fn, seen = eng.fns.decode, []
    eng.fns = type(eng.fns)(**{**vars(eng.fns), "decode": lambda *a: (
        seen.append(fn(*a)) or seen[-1])})
    eng.run()
    loads = [np.asarray(out[3]) for out in seen]
    assert loads and all(l.shape == (3, Z["E"]) for l in loads)
    # top 2 of every live row in every routed layer, idle rows none: one
    # or two rows decode, so two or four assignments a layer
    sums = [tuple(l.sum(1)) for l in loads]
    assert set(sums) <= {(2, 2, 2), (4, 4, 4)} and (4, 4, 4) in sums
    counters = E._routed_counters(loads[0])
    assert 1 <= counters["experts_touched"] <= 4
    assert counters["load_ratio"] >= 1.0
    assert E._routed_counters(np.zeros((3, 8), np.int32)) == {}


# ---- grouped heads in the paged kernel -------------------------------------


def test_grouped_heads_read_the_pool_head_of_their_group():
    B, H, KV, hd, bs, n_blk = 2, 4, 2, 16, 8, 3
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(key[0], (B, 1, H, hd), jnp.float32)
    kp = jax.random.normal(key[1], (B * n_blk + 1, KV, bs, hd), jnp.float32)
    vp = jax.random.normal(key[2], (B * n_blk + 1, KV, bs, hd), jnp.float32)
    tables = jnp.arange(B * n_blk, dtype=jnp.int32).reshape(B, n_blk)
    lengths = jnp.asarray([11, 20], jnp.int32)
    grouped = DA.paged_decode_attention(q, kp, vp, tables, lengths,
                                        block_size=bs, blk_k=8)
    # one pool head a query head, each group's head copied: the kernel as
    # it was before it knew groups
    copied = DA.paged_decode_attention(
        q, jnp.repeat(kp, H // KV, axis=1), jnp.repeat(vp, H // KV, axis=1),
        tables, lengths, block_size=bs, blk_k=8)
    np.testing.assert_array_equal(np.asarray(grouped), np.asarray(copied))
    with pytest.raises(ValueError, match="no multiple"):
        DA.paged_decode_attention(q, kp[:, :1].repeat(3, 1), vp[:, :1].repeat(
            3, 1), tables, lengths, block_size=bs, blk_k=8)


def test_a_gpt2_configuration_builds_the_tree_and_programs_it_did():
    """No size of a patterned model given: the parameter tree has GPT-2's
    names, the step pair takes six and seven operands and donates the
    pool alone, and no state leaf exists."""
    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                            d_model=16, d_ff=32, max_len=32,
                            dtype=jnp.float32)
    shapes = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))["params"]
    assert set(shapes) == {"tok_emb", "pos_emb", "block_0", "block_1",
                           "ln_f", "lm_head"}
    assert set(shapes["block_0"]) == {"ln1", "attn", "ln2", "mlp"}
    assert shapes["block_0"]["attn"]["qkv"]["kernel"].value.shape == (
        16, 3, 2, 8)
    fns = E.build_step_fns(cfg, slots=2, num_blocks=5, block_size=8,
                           prefill_chunk=8)
    assert not fns.patterned and fns.declared_donate_argnums == (1,)
    pool = E.paged_cache_shapes(fns.cfg, 2)
    assert {k: v.shape for k, v in pool["block_0"]["attn"].items()} == {
        "cached_key": (5, 2, 8, 8), "cached_value": (5, 2, 8, 8)}
    assert E.slot_state(fns.cfg, 2) == {}
