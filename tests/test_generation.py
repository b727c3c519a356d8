"""KV-cache generation parity (models/generation.py).

The serving path must be the SAME function the training path computes:
prefill logits equal the full training forward's logits, and greedy
decode equals re-scoring the growing prefix with the training model each
step (the O(S^2) oracle the cache exists to avoid)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_guide_tpu.models.generation import (
    decode_config,
    init_cache,
    make_generate_fn,
)
from distributed_tensorflow_guide_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
)

CFG = TransformerConfig(
    vocab_size=97, num_layers=2, num_heads=2, d_model=32, d_ff=64,
    max_len=32, causal=True, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    model = Transformer(CFG)
    toks = jnp.zeros((1, CFG.max_len), jnp.int32)
    return model.init(jax.random.PRNGKey(0), toks)["params"]


def test_prefill_logits_match_training_forward(params):
    model = Transformer(CFG)
    dmodel = Transformer(decode_config(CFG))
    prompt = np.random.RandomState(0).randint(0, CFG.vocab_size,
                                              (3, 7)).astype(np.int32)
    want = model.apply({"params": params}, prompt)  # (3, 7, V)
    cache = init_cache(CFG, params, 3)
    got, _ = dmodel.apply({"params": params, "cache": cache}, prompt, 0,
                          mutable=["cache"])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_greedy_decode_matches_prefix_rescoring(params):
    model = Transformer(CFG)
    N = 6
    gen = make_generate_fn(CFG, max_new_tokens=N, temperature=0.0)
    prompt = np.random.RandomState(1).randint(0, CFG.vocab_size,
                                              (2, 5)).astype(np.int32)
    out = np.asarray(gen(params, prompt, jax.random.PRNGKey(0)))
    assert out.shape == (2, 5 + N)
    np.testing.assert_array_equal(out[:, :5], prompt)

    # oracle: full training forward on the growing prefix, argmax each step
    seq = prompt
    for _ in range(N):
        logits = model.apply({"params": params}, seq)
        nxt = np.asarray(jnp.argmax(logits[:, -1], -1), np.int32)
        seq = np.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out, seq)


def test_sampled_decode_reproducible_and_in_range(params):
    gen = make_generate_fn(CFG, max_new_tokens=4, temperature=0.8, top_k=10)
    prompt = np.zeros((2, 3), np.int32)
    a = np.asarray(gen(params, prompt, jax.random.PRNGKey(7)))
    b = np.asarray(gen(params, prompt, jax.random.PRNGKey(7)))
    c = np.asarray(gen(params, prompt, jax.random.PRNGKey(8)))
    np.testing.assert_array_equal(a, b)  # same rng -> same tokens
    assert (a >= 0).all() and (a < CFG.vocab_size).all()
    assert not np.array_equal(a, c)  # different rng varies (overwhelmingly)


def test_generate_rejects_overlong(params):
    gen = make_generate_fn(CFG, max_new_tokens=30)
    prompt = np.zeros((1, 5), np.int32)
    with pytest.raises(ValueError, match="max_len"):
        gen(params, prompt, jax.random.PRNGKey(0))


def test_decode_requires_index(params):
    dmodel = Transformer(decode_config(CFG))
    cache = init_cache(CFG, params, 1)
    with pytest.raises(ValueError, match="index"):
        dmodel.apply({"params": params, "cache": cache},
                     jnp.zeros((1, 1), jnp.int32), mutable=["cache"])


def test_single_new_token(params):
    gen = make_generate_fn(CFG, max_new_tokens=1, temperature=0.0)
    prompt = np.zeros((2, 4), np.int32)
    out = np.asarray(gen(params, prompt, jax.random.PRNGKey(0)))
    assert out.shape == (2, 5)


def test_decode_cache_donation_safety(params):
    """The donated-cache decode path (donate_cache=True, the default) under
    the buffer-reuse oracle pattern of tests/test_prefetch.py: every call
    allocates a FRESH cache and donates it into the compiled program, so a
    later call reusing the first call's buffers cannot corrupt results —
    repeated identical calls must be bit-identical, and must match the
    non-donating build.

    On the CPU test backend donation is gated OFF inside make_generate_fn
    (jax warns and ignores it there), so here the value-parity half runs
    on two identical programs; the WIRING is what this test can pin —
    ``donates_cache`` must reflect the knob x backend — and the aliasing
    itself is exercised on real hardware (battery ``gpt2_decode``)."""
    prompt = np.random.RandomState(5).randint(0, CFG.vocab_size,
                                              (2, 4)).astype(np.int32)
    gen = make_generate_fn(CFG, max_new_tokens=6, temperature=0.0,
                           donate_cache=True)
    assert gen.donates_cache == (jax.default_backend() != "cpu")
    a = np.asarray(gen(params, prompt, jax.random.PRNGKey(0)))
    b = np.asarray(gen(params, prompt, jax.random.PRNGKey(0)))
    np.testing.assert_array_equal(a, b)
    no_donate = make_generate_fn(CFG, max_new_tokens=6, temperature=0.0,
                                 donate_cache=False)
    assert no_donate.donates_cache is False
    c = np.asarray(no_donate(params, prompt, jax.random.PRNGKey(0)))
    np.testing.assert_array_equal(a, c)


def test_decode_unroll_parity(params):
    """The scan-unroll knob is an execution-shape change only: greedy AND
    sampled decode produce identical tokens at any unroll (including one
    that does not divide the step count)."""
    prompt = np.random.RandomState(6).randint(0, CFG.vocab_size,
                                              (2, 3)).astype(np.int32)
    # greedy at unroll 4; sampled (rng threading) at unroll 3, which does
    # NOT divide the 5-step decode loop — the remainder-handling case
    for kw, unroll in ((dict(temperature=0.0), 4),
                       (dict(temperature=0.8, top_k=10), 3)):
        base = make_generate_fn(CFG, max_new_tokens=6, **kw)
        want = np.asarray(base(params, prompt, jax.random.PRNGKey(1)))
        genu = make_generate_fn(CFG, max_new_tokens=6, unroll=unroll, **kw)
        got = np.asarray(genu(params, prompt, jax.random.PRNGKey(1)))
        np.testing.assert_array_equal(got, want)


def test_decode_hbm_bytes_model(params):
    """The decode-roofline byte model (bench_generate's denominator) in
    closed form: non-embedding params once + GATHERED embedding rows (B
    token rows + 1 position row, not the whole tables) + full KV cache
    read + one-slot write — then the two round-11 refinements: the int8
    cache halves the KV term (values at 1 byte + the per-slot f32 scales),
    and ``effective_len`` charges only the live block-rounded slots the
    length-aware kernel actually reads (full-``max_len`` charging is only
    correct for the dense static-shape path)."""
    from distributed_tensorflow_guide_tpu.models.generation import (
        decode_cache_bytes_per_step,
        decode_hbm_bytes_per_step,
    )

    B = 3
    got = decode_hbm_bytes_per_step(CFG, params, B)

    def nbytes(tree):
        return sum(leaf.size * np.dtype(leaf.dtype).itemsize
                   for leaf in jax.tree.leaves(tree))

    tables = nbytes(params["tok_emb"]) + nbytes(params["pos_emb"])
    gathered = (B + 1) * CFG.d_model * 4  # f32 embedding rows
    item = np.dtype(CFG.dtype).itemsize
    kv = CFG.num_layers * 2 * B * CFG.max_len * CFG.num_heads \
        * (CFG.d_model // CFG.num_heads) * item
    assert got == nbytes(params) - tables + gathered + kv + kv // CFG.max_len

    base = got - kv - kv // CFG.max_len  # the non-cache terms
    hd = CFG.d_model // CFG.num_heads
    # int8: 1-byte values + two f32 scales per (slot, head), read over the
    # full length + one-slot write — the VALUE bytes are kv/item (halved
    # vs bf16, quartered vs this f32 test config)
    icfg = dataclasses.replace(CFG, kv_dtype="int8")
    scales = CFG.num_layers * B * CFG.num_heads * 8  # 2 x f32, per slot
    kv8 = kv // item + scales * CFG.max_len
    want8 = base + kv8 + kv8 // CFG.max_len
    assert decode_hbm_bytes_per_step(icfg, params, B) == want8
    # effective_len scales ONLY the read term; the one-slot write stays
    L = 24
    wantL = base + kv * L // CFG.max_len + kv // CFG.max_len
    assert decode_hbm_bytes_per_step(CFG, params, B,
                                     effective_len=L) == wantL
    # the cache-only helper is exactly the cache terms of the full model
    assert decode_cache_bytes_per_step(CFG, B) == kv + kv // CFG.max_len
    assert decode_cache_bytes_per_step(
        icfg, B, effective_len=L) == (kv // item // CFG.max_len + scales
                                      ) * (L + 1)
    # the acceptance-gate claim in closed form: at the serving dtype
    # (bf16), int8 HALVES the cache value bytes; the f32 scale rows are
    # the only addition
    bcfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    b16 = decode_cache_bytes_per_step(bcfg, B)
    b8 = decode_cache_bytes_per_step(
        dataclasses.replace(bcfg, kv_dtype="int8"), B)
    assert b8 == b16 / 2 + scales * (CFG.max_len + 1)


# ---- round-11 decode levers: int8 KV cache, Pallas decode-attend, -----------
# ---- self-speculative decoding ----------------------------------------------


def _greedy_tokens(cfg, params, prompt, n=6):
    gen = make_generate_fn(cfg, max_new_tokens=n, temperature=0.0)
    return np.asarray(gen(params, prompt, jax.random.PRNGKey(0)))


def test_int8_kv_decode_parity(params):
    """The quantized cache is an approximation with a pinned tolerance:
    decode-mode prefill logits stay close to the exact-cache logits, and
    greedy decode emits the same tokens on this config (logit gaps dwarf
    the <= scale/2 per-element quantization error)."""
    icfg = dataclasses.replace(CFG, kv_dtype="int8")
    prompt = np.random.RandomState(11).randint(0, CFG.vocab_size,
                                               (2, 6)).astype(np.int32)
    want, _ = Transformer(decode_config(CFG)).apply(
        {"params": params, "cache": init_cache(CFG, params, 2)}, prompt, 0,
        mutable=["cache"])
    got, _ = Transformer(decode_config(icfg)).apply(
        {"params": params, "cache": init_cache(icfg, params, 2)}, prompt, 0,
        mutable=["cache"])
    np.testing.assert_allclose(got, want, atol=0.05, rtol=0.05)
    np.testing.assert_array_equal(_greedy_tokens(icfg, params, prompt),
                                  _greedy_tokens(CFG, params, prompt))


def test_pallas_decode_generate_matches_dense(params):
    """End-to-end generate with decode_impl='pallas' (interpret mode on
    CPU) emits the same greedy tokens as the dense path — with and without
    the quantized cache."""
    prompt = np.random.RandomState(12).randint(0, CFG.vocab_size,
                                               (2, 4)).astype(np.int32)
    want = _greedy_tokens(CFG, params, prompt)
    pcfg = dataclasses.replace(CFG, decode_impl="pallas")
    np.testing.assert_array_equal(_greedy_tokens(pcfg, params, prompt),
                                  want)
    ipcfg = dataclasses.replace(CFG, decode_impl="pallas", kv_dtype="int8")
    icfg = dataclasses.replace(CFG, decode_impl="dense", kv_dtype="int8")
    np.testing.assert_array_equal(_greedy_tokens(ipcfg, params, prompt),
                                  _greedy_tokens(icfg, params, prompt))


def test_decode_cache_donation_safety_quantized(params):
    """The donation-safety contract extends to the QUANTIZED cache tree
    (int8 values + f32 scales, kernel layout): fresh-cache-per-call keeps
    repeated donated calls bit-identical and equal to the non-donating
    build; ``donates_cache`` reflects knob x backend as before."""
    icfg = dataclasses.replace(CFG, kv_dtype="int8", decode_impl="pallas")
    # the quantized tree really is what generate allocates
    from distributed_tensorflow_guide_tpu.models.generation import (
        cache_shapes,
    )

    leaves = jax.tree.leaves(cache_shapes(icfg, 2))
    dtypes = sorted({str(leaf.dtype) for leaf in leaves})
    assert dtypes == ["float32", "int8"]  # values int8, scales f32
    prompt = np.random.RandomState(13).randint(0, CFG.vocab_size,
                                               (2, 4)).astype(np.int32)
    gen = make_generate_fn(icfg, max_new_tokens=6, temperature=0.0,
                           donate_cache=True)
    assert gen.donates_cache == (jax.default_backend() != "cpu")
    a = np.asarray(gen(params, prompt, jax.random.PRNGKey(0)))
    b = np.asarray(gen(params, prompt, jax.random.PRNGKey(0)))
    np.testing.assert_array_equal(a, b)
    no_donate = make_generate_fn(icfg, max_new_tokens=6, temperature=0.0,
                                 donate_cache=False)
    assert no_donate.donates_cache is False
    c = np.asarray(no_donate(params, prompt, jax.random.PRNGKey(0)))
    np.testing.assert_array_equal(a, c)


def test_greedy_speculative_bitwise_identical_to_vanilla(params):
    """THE speculative pin: greedy speculative output is bitwise the
    vanilla greedy output — every emitted token is the verifier's own
    argmax for its position given an all-accepted prefix, so the schedule
    reorders the same argmaxes it would have computed one at a time."""
    prompt = np.random.RandomState(14).randint(0, CFG.vocab_size,
                                               (2, 5)).astype(np.int32)
    base = make_generate_fn(CFG, max_new_tokens=8, temperature=0.0)
    want = np.asarray(base(params, prompt, jax.random.PRNGKey(0)))
    # two lookaheads: the degenerate G=1 and the default G=4 (the full
    # K x G grid lives in the slow-marked composition test — tier-1
    # wall-clock budget)
    for lookahead in (1, 4):
        gen = make_generate_fn(CFG, max_new_tokens=8, temperature=0.0,
                               spec_draft_layers=1,
                               spec_lookahead=lookahead)
        got = np.asarray(gen(params, prompt, jax.random.PRNGKey(0)))
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"lookahead {lookahead}")
        stats = {k: int(v) for k, v in gen.last_stats.items()}
        assert stats["verify_steps"] >= 1
        assert 0 <= stats["accepted_drafts"] <= 7


def test_sampled_speculative_identical_to_vanilla(params):
    """Sampling keys derive from the absolute position (Gumbel coupling),
    so the speculative schedule reproduces the SAMPLED vanilla stream too
    — same rng, same tokens, at any acceptance rate."""
    prompt = np.random.RandomState(15).randint(0, CFG.vocab_size,
                                               (2, 4)).astype(np.int32)
    base = make_generate_fn(CFG, max_new_tokens=7, temperature=0.8,
                            top_k=10)
    want = np.asarray(base(params, prompt, jax.random.PRNGKey(9)))
    gen = make_generate_fn(CFG, max_new_tokens=7, temperature=0.8,
                           top_k=10, spec_draft_layers=1, spec_lookahead=3)
    got = np.asarray(gen(params, prompt, jax.random.PRNGKey(9)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.slow
def test_speculative_levers_compose_across_depths():
    """Exhaustive (draft depth x lookahead) grid on a 4-layer model, plus
    all three levers at once — multi-second (each cell compiles its own
    while-loop program), so tier-1 carries the fast pins above instead."""
    cfg = dataclasses.replace(CFG, num_layers=4)
    model = Transformer(cfg)
    params4 = model.init(jax.random.PRNGKey(1),
                         jnp.zeros((1, cfg.max_len), jnp.int32))["params"]
    prompt = np.random.RandomState(16).randint(0, cfg.vocab_size,
                                               (2, 5)).astype(np.int32)
    want = _greedy_tokens(cfg, params4, prompt, n=8)

    def spec_tokens(c, k, g):
        gen = make_generate_fn(c, max_new_tokens=8, temperature=0.0,
                               spec_draft_layers=k, spec_lookahead=g)
        return np.asarray(gen(params4, prompt, jax.random.PRNGKey(0)))

    for k in (1, 2, 3):
        for g in (1, 4):
            np.testing.assert_array_equal(spec_tokens(cfg, k, g), want,
                                          err_msg=f"K={k} G={g}")
    allcfg = dataclasses.replace(cfg, kv_dtype="int8",
                                 decode_impl="pallas")
    ref = _greedy_tokens(allcfg, params4, prompt, n=8)
    np.testing.assert_array_equal(spec_tokens(allcfg, 2, 4), ref)


@pytest.mark.slow
def test_sharded_serving_composes_with_decode_levers(params):
    """The docs/serving.md claim, pinned: DP- and TP-sharded generate stay
    token-identical to the unsharded run with the round-11 levers on (the
    quantized cache + scales inherit the sharding; lockstep acceptance is
    replicated by construction). Multi-second — each lever combination
    compiles its own sharded program."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_guide_tpu.core.mesh import (
        MeshSpec,
        build_mesh,
    )

    mesh = build_mesh(MeshSpec(data=-1))
    prompt = np.random.RandomState(17).randint(
        0, CFG.vocab_size, (8, 4)).astype(np.int32)
    for kv, impl, k in (("int8", "pallas", 0), ("int8", "dense", 1)):
        cfg = dataclasses.replace(CFG, kv_dtype=kv, decode_impl=impl)
        gen = make_generate_fn(cfg, max_new_tokens=5, temperature=0.0,
                               spec_draft_layers=k)
        want = np.asarray(gen(params, prompt, jax.random.PRNGKey(0)))
        sharded = jax.device_put(prompt, NamedSharding(mesh, P("data")))
        repl = jax.device_put(params, NamedSharding(mesh, P()))
        got = np.asarray(gen(repl, sharded, jax.random.PRNGKey(0)))
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"kv={kv} impl={impl} K={k}")

    # TP: heads sharded over "model" (Megatron rules), int8+pallas
    import flax.linen as nn
    from flax.linen import spmd

    from distributed_tensorflow_guide_tpu.parallel.tensor import (
        DEFAULT_RULES,
    )

    tmesh = build_mesh(MeshSpec(data=4, model=2))
    cfg = dataclasses.replace(CFG, kv_dtype="int8", decode_impl="pallas")
    gen = make_generate_fn(cfg, max_new_tokens=5, temperature=0.0)
    small = prompt[:2]
    want = np.asarray(gen(params, small, jax.random.PRNGKey(0)))
    dmodel = Transformer(decode_config(cfg))
    abstract = jax.eval_shape(
        lambda: dmodel.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 1), jnp.int32), 0))
    specs = nn.get_partition_spec(abstract)["params"]
    rules = tuple((kk, None if kk == "vocab" else v)
                  for kk, v in DEFAULT_RULES)
    tp_params = jax.device_put(
        params, spmd.logical_to_mesh_sharding(specs, tmesh, rules))
    got = np.asarray(gen(tp_params, small, jax.random.PRNGKey(0)))
    np.testing.assert_array_equal(got, want)


def test_speculative_validation(params):
    with pytest.raises(ValueError, match="strictly between"):
        make_generate_fn(CFG, max_new_tokens=4, temperature=0.0,
                         spec_draft_layers=CFG.num_layers)
    with pytest.raises(ValueError, match="spec_lookahead"):
        make_generate_fn(CFG, max_new_tokens=4, temperature=0.0,
                         spec_draft_layers=1, spec_lookahead=0)
    # the lookahead needs cache headroom past the vanilla budget
    gen = make_generate_fn(CFG, max_new_tokens=26, temperature=0.0,
                           spec_draft_layers=1, spec_lookahead=4)
    with pytest.raises(ValueError, match="max_len"):
        gen(params, np.zeros((1, 4), np.int32), jax.random.PRNGKey(0))


def test_default_decode_trace_hermetic_on_cpu(params):
    """On the CPU backend the DEFAULT decode config (decode_impl='auto',
    kv_dtype=None) traces byte-identically to the explicitly-pinned
    dense/unquantized config — no Pallas call and no quantization can
    leak into CI programs by default."""
    from distributed_tensorflow_guide_tpu.analysis.walker import traced_text

    tok = jnp.zeros((2, 1), jnp.int32)

    def trace(cfg):
        model = Transformer(decode_config(cfg))
        cache = init_cache(cfg, params, 2)
        return traced_text(
            lambda p, t: model.apply({"params": p, "cache": cache}, t, 3,
                                     mutable=["cache"]), params, tok)

    default = trace(CFG)
    pinned = trace(dataclasses.replace(CFG, decode_impl="dense"))
    assert default == pinned
    assert "pallas" not in default and "convert_element_type[new_dtype=int8" \
        not in default


def test_generate_with_dp_sharded_prompts(params):
    """Data-parallel serving: prompts sharded over the data axis produce
    the same tokens as the unsharded run (generate is pure SPMD — the
    KV cache inherits the batch sharding)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=-1))
    gen = make_generate_fn(CFG, max_new_tokens=5, temperature=0.0)
    prompt = np.random.RandomState(2).randint(
        0, CFG.vocab_size, (8, 4)).astype(np.int32)
    want = np.asarray(gen(params, prompt, jax.random.PRNGKey(0)))

    sharded_prompt = jax.device_put(
        prompt, NamedSharding(mesh, P("data")))
    repl_params = jax.device_put(params, NamedSharding(mesh, P()))
    got = np.asarray(gen(repl_params, sharded_prompt, jax.random.PRNGKey(0)))
    np.testing.assert_array_equal(got, want)


def test_generate_with_tp_sharded_params(params):
    """Model-parallel serving: TP-sharded params (Megatron logical rules)
    decode the same tokens — GSPMD shards the cache over heads and inserts
    the collectives; no generation-specific sharding code exists."""
    import flax.linen as nn
    from flax.linen import spmd

    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh
    from distributed_tensorflow_guide_tpu.models.generation import (
        decode_config,
    )
    from distributed_tensorflow_guide_tpu.parallel.tensor import DEFAULT_RULES

    mesh = build_mesh(MeshSpec(data=4, model=2))  # CFG has 2 heads
    gen = make_generate_fn(CFG, max_new_tokens=5, temperature=0.0)
    prompt = np.random.RandomState(3).randint(
        0, CFG.vocab_size, (2, 4)).astype(np.int32)
    want = np.asarray(gen(params, prompt, jax.random.PRNGKey(0)))

    # derive the TP shardings from the decode-mode module's logical names
    dmodel = Transformer(decode_config(CFG))
    abstract = jax.eval_shape(
        lambda: dmodel.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 1), jnp.int32), 0))
    specs = nn.get_partition_spec(abstract)["params"]
    # CFG's vocab (97) is deliberately non-divisible: keep vocab-sharded
    # tables replicated, shard heads/mlp — the interesting TP dims here
    rules = tuple((k, None if k == "vocab" else v) for k, v in DEFAULT_RULES)
    shardings = spmd.logical_to_mesh_sharding(specs, mesh, rules)
    tp_params = jax.device_put(params, shardings)
    got = np.asarray(gen(tp_params, prompt, jax.random.PRNGKey(0)))
    np.testing.assert_array_equal(got, want)
