"""A model of latent attention (DeepSeek-V2's MLA: one latent row a
position in the paged pool, attention in the absorbed form) with sandwich
norms, a leading dense layer and sigmoid top-k experts of which the program
holds a share beside a shared expert, through the same ``ServeEngine`` as
the other patterned models: chunked prefill and decode through the latent
pool against the plain reference's full forward
(``yardstick/reference/pangu_moe.py``, the expanded form, which imports
nothing from the package), at a size the CPU holds, on seeded weights; and
the Pallas latent kernel in interpret mode against the expanded
mathematics."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_guide_tpu.models.transformer import (
    Block,
    LatentSizes,
    Transformer,
    TransformerConfig,
)
from distributed_tensorflow_guide_tpu.ops import decode_attention as DA
from distributed_tensorflow_guide_tpu.serve import engine as E
from distributed_tensorflow_guide_tpu.serve.engine import Request, ServeEngine
from distributed_tensorflow_guide_tpu.serve.paged_cache import write_chunk
from yardstick import weights_pangu
from yardstick.drivers.serve_pangu import model_config
from yardstick.reference import pangu_moe

SEED = 2 ** 31 + 41
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_shared_experts": 1, "n_routed_experts": 8, "num_experts_per_tok": 4,
    "routed_scaling_factor": 2.5, "first_k_dense_replace": 1,
    "vocab_size": 256, "rms_norm_eps": 1e-5, "rope_theta": 1e4,
    "published": {"n_routed_experts": {"published": 16}},
    "assumed": {"drawn": {"initializer_range": 0.1, "router_std": 0.5,
                          "expert_bias_std": 0.05, "post_norm_gain": 0.5}},
    # 1 dense + 2 routed layers; 8 of 16 experts held
    "deployment": {"max_positions": 64, "experts_held": [0, 8],
                   "layers_held": [0, 3, 4]},
}
Z = weights_pangu.sizes_of(CONFIG)
GEOMETRY = dict(slots=3, num_blocks=25, block_size=8, prefill_chunk=8)


def config(dtype=jnp.float32, z=Z, **kw) -> TransformerConfig:
    import dataclasses

    return dataclasses.replace(model_config(z, dtype), **kw)


@pytest.fixture(scope="module")
def params():
    """The seed's tree as float32 (the bfloat16 numbers, widened): what
    both sides multiply, so that float32 runs agree to rounding."""
    return jax.tree.map(lambda x: x.astype(jnp.float32),
                        weights_pangu.flax_tree(SEED, Z))


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


def served_gaps(eng, reqs, z=Z):
    """Per request, how far each served token's logit lies below the
    reference's best at its position."""
    out = []
    for i, prompt in enumerate(reqs):
        toks = np.concatenate(
            [prompt, np.asarray(eng.completions()[i], np.int32)])
        ref = np.asarray(pangu_moe.forward(SEED, toks, z))[:-1]
        at = np.arange(len(prompt) - 1, len(toks) - 1)
        out.append(ref[at].max(-1) - ref[at, toks[at + 1]])
    return out


def test_the_tree_is_the_one_the_model_declares():
    import flax.linen as nn

    shapes = jax.eval_shape(Transformer(config()).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    declared = nn.meta.unbox(shapes["params"])
    made = weights_pangu.flax_tree(SEED, Z)
    assert jax.tree.structure(declared) == jax.tree.structure(made)
    assert ([a.shape for a in jax.tree.leaves(declared)]
            == [a.shape for a in jax.tree.leaves(made)])
    assert "pos_emb" not in made  # rotary positions
    # every layer is both halves, each between two norms
    assert set(made["block_0"]) == {"ln1", "ln1_post", "attn", "ln2",
                                    "ln2_post", "mlp"}
    assert set(made["block_1"]) == {"ln1", "ln1_post", "attn", "ln2",
                                    "ln2_post", "mlp", "shared"}
    assert set(made["block_0"]["mlp"]) == {"gate", "up", "down"}
    assert set(made["block_1"]["mlp"]) == {"router", "expert_bias", "w_gate",
                                           "w_up", "w_down"}
    attn = made["block_2"]["attn"]
    assert set(attn) == {"q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj",
                         "kv_a_norm", "kv_b_proj", "o_proj"}
    assert attn["kv_a_proj"]["kernel"].shape == (64, 32 + 8)
    assert attn["kv_b_proj"]["kernel"].shape == (32, 4, 16 + 16)
    assert attn["q_b_proj"]["kernel"].dtype == jnp.bfloat16
    assert made["block_2"]["ln1_post"]["scale"].dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(made["block_2"]["ln2_post"]["scale"]), 0.5)
    assert made["block_1"]["mlp"]["router"].dtype == jnp.float32


def test_training_view_agrees_with_the_reference(params):
    (tokens,) = prompts([23], seed=1)
    got = Transformer(config()).apply({"params": params}, tokens[None])[0]
    want = pangu_moe.forward(SEED, tokens, Z)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def paged_logits(cfg, tree, tokens, chunk):
    """Logits at every position of ``tokens`` as the engine computes them:
    the prompt in chunks of ``chunk`` (the last one padded), through the
    latent pool. Returns them and the pool."""
    fns = E.build_step_fns(cfg, temperature=0.0, **GEOMETRY)
    pool = E.paged_cache_pool(fns.cfg, GEOMETRY["slots"])
    state = E.slot_state(fns.cfg, GEOMETRY["slots"])
    tables = jnp.arange(1, 1 + fns.n_blk, dtype=jnp.int32)[None]
    out = []
    for start in range(0, len(tokens), chunk):
        piece = np.zeros((1, chunk), np.int32)
        valid = min(chunk, len(tokens) - start)
        piece[0, :valid] = tokens[start:start + valid]
        logits, mut = fns.model.apply(
            {"params": tree, "cache": pool, "state": state}, piece,
            jnp.full((1,), start, jnp.int32), block_tables=tables,
            state_rows=jnp.full((1,), 1, jnp.int32),
            valid=jnp.full((1,), valid, jnp.int32),
            mutable=["cache", "state"])
        pool = mut["cache"]
        out.append(logits[0, :valid])
    return jnp.concatenate(out), pool


@pytest.mark.parametrize("chunk,impl", [(8, "dense"), (1, "dense"),
                                        (8, "pallas")])
def test_chunked_prefill_and_decode_agree_with_the_full_forward(params, chunk,
                                                                impl):
    """Chunks of 8 are the prefill program's path (21 tokens: two whole
    chunks and one mostly padding), chunks of 1 decode's, a token at a time
    through the pool; the Pallas kernel (interpret mode) and the write
    kernel where the chip would take them."""
    (tokens,) = prompts([21], seed=2)
    got, pool = paged_logits(config(decode_impl=impl), params, tokens, chunk)
    want = pangu_moe.forward(SEED, tokens, Z)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    # one leaf a layer: a row of latent and rotary key a position, nothing
    # a head
    assert set(pool) == {"block_0", "block_1", "block_2"}
    assert {k: v.shape for k, v in pool["block_1"]["attn"].items()} == {
        "latent": (25, 1, 32 + 8, 8)}


def test_the_engine_serves_what_the_reference_puts_first(params):
    reqs = prompts([5, 13, 8, 21, 9], seed=4)  # more requests than slots
    eng = serve(config(), params, reqs)
    assert eng.steps["prefill"] >= 5 and eng.steps["decode"] >= 6
    for i, gap in enumerate(served_gaps(eng, reqs)):
        # float32: a served token is the reference's own choice unless two
        # logits lie within rounding of each other
        assert gap.max() < 1e-3, (i, gap)
    health = eng.health()
    # the latent pool: 3 layers x 25 blocks x a row of 40 x 8 positions,
    # float32 here; no state beside it
    assert health["pool_bytes"] == 3 * 25 * 40 * 8 * 4
    assert health["state_bytes"] == 0
    routed = health["routed"]
    assert 0 < routed["held_assignments"] < routed["assignments"]


def test_prefill_rows_of_several_prompts_then_decode(params, monkeypatch):
    """A prefill launch carries up to 4 prompts' next chunks (the TPU's
    widths, forced here): prompts of several chunks in rows side by side,
    then decode, against the reference."""
    monkeypatch.setattr(E, "CPU_PREFILL_WIDTHS", E.PREFILL_WIDTHS)
    reqs = prompts([19, 23, 11], seed=8)
    eng = serve(config(), params, reqs, max_new=5)
    assert eng.prefill_chunks > eng.steps["prefill"]  # rows rode together
    for i, gap in enumerate(served_gaps(eng, reqs)):
        assert gap.max() < 1e-3, (i, gap)


def test_bfloat16_serving_stays_near_the_reference():
    tree = weights_pangu.flax_tree(SEED, Z)
    reqs = prompts([13, 21, 9], seed=5)
    eng = serve(config(jnp.bfloat16), tree, reqs, max_new=8)
    gaps = np.concatenate(served_gaps(eng, reqs))
    assert np.mean(gaps) < 0.05, gaps
    assert eng.pool["block_0"]["attn"]["latent"].dtype == jnp.bfloat16


def test_a_preempted_request_resumes_to_the_same_tokens(params):
    reqs = prompts([13, 21, 9], seed=6)
    roomy = serve(config(), params, reqs, max_new=20)
    tight = serve(config(), params, reqs, max_new=20, num_blocks=7)
    assert tight.health()["preemptions"] > 0 == roomy.health()["preemptions"]
    assert tight.completions() == roomy.completions()


# ---- the configuration ------------------------------------------------------


def test_latent_sizes_and_the_sandwich_are_said_once():
    cfg = config()
    assert cfg.latent == LatentSizes(32, 32, 16, 8, 16) and cfg.latent.row == 40
    assert cfg.sandwich_norm and cfg.position_kind == "rotary"
    with pytest.raises(ValueError, match="latent sizes"):
        config(latent=None)
    with pytest.raises(ValueError, match="latent sizes"):
        config(layers=(("attention", "dense"),) * 3, shared_d_ff=None)
    with pytest.raises(ValueError, match="rotary"):
        config(positions="none", rope_theta=None)
    with pytest.raises(ValueError, match="rotary"):
        config(latent=LatentSizes(32, 32, 16, 7, 16))
    with pytest.raises(ValueError, match="layers"):
        TransformerConfig(sandwich_norm=True)
    with pytest.raises(ValueError, match="layers"):
        TransformerConfig(latent=LatentSizes(4, 8, 4, 2, 4))
    # the one-shot cache refuses a patterned model, this kind with it
    with pytest.raises(ValueError,
                       match="paged engine only.*latent_attention layers"):
        Transformer(config(decode=True)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), 0)


# ---- the kernel -------------------------------------------------------------


def expanded(q, rows, lengths, chunk, value, scale):
    """The latent attention the kernel computes, written out: for row b
    and chunk position c, the query at ``lengths[b] - chunk + c`` against
    every row up to it."""
    B, C, H, _ = q.shape
    out = np.zeros((B, C, H, value), np.float32)
    for b in range(B):
        for c in range(C):
            at = int(lengths[b]) - chunk + c
            keys = rows[b, :at + 1]  # (n, row)
            s = np.einsum("hr,nr->hn", q[b, c], keys) * scale
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[b, c] = p @ keys[:, :value]
    return out


@pytest.mark.parametrize("chunk,heads,tiling", [
    (1, 16, None), (1, 16, (16, 2)), (4, 16, (4, 1)), (4, 8, (2, 4))])
def test_the_latent_kernel_is_the_expanded_mathematics(chunk, heads, tiling):
    """Ragged live lengths (one inside the first block, one on a block's
    edge, one across several), block tables scattered over the pool, a row
    whose table ends in the trash block; every tiling of heads and blocks
    a step, against the softmax written out."""
    row, value, bs, n_blk = 40, 32, 8, 8
    rng = np.random.default_rng(0)
    lengths = np.asarray([3, 16, 45], np.int32)
    B = len(lengths)
    N = B * n_blk + 1
    order = rng.permutation(N - 1)
    tables = order.reshape(B, n_blk).astype(np.int32)
    tables[0, 1:] = N - 1  # the trash block past the row's one live block
    seqs = rng.standard_normal((B, n_blk * bs, row)).astype(np.float32)
    pool = np.zeros((N, 1, row, bs), np.float32)
    for b in range(B):
        for j in range(n_blk):
            if tables[b, j] != N - 1:
                pool[tables[b, j], 0] = seqs[b, j * bs:(j + 1) * bs].T
    q = rng.standard_normal((B, chunk, heads, row)).astype(np.float32)
    lengths = np.maximum(lengths, chunk)
    got = DA.paged_latent_attention(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(tables),
        jnp.asarray(lengths), block_size=bs, value=value, scale=0.3,
        tiling=tiling)
    want = expanded(q, seqs, lengths, chunk, value, 0.3)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_the_latent_tiling_carries_every_head_at_decode():
    kw = dict(row=576, value=512, block_size=128, dtype=jnp.bfloat16)
    # a decode launch: all 128 heads a step, so a tile is read once a slot
    assert DA.latent_tiling(128, chunk=1, n_blk=64, **kw) == (128, 8)
    # a prefill chunk of 128: two heads' rows a step, fewer blocks
    hb, nb = DA.latent_tiling(128, chunk=128, n_blk=64, **kw)
    assert hb == 2 and 1 <= nb <= 8 and 64 % nb == 0
    assert DA.latent_tiling(128, chunk=1, n_blk=6, **kw)[1] == 2


def test_paged_write_of_latent_rows_lands_each_position_once():
    """A chunk's rows written through the tables, the plain loop and the
    Pallas write (interpret mode) alike: each position in its block's slot,
    what was there before kept elsewhere."""
    rng = np.random.default_rng(3)
    N, row, bs = 9, 40, 8
    pool = jnp.asarray(rng.standard_normal((N, 1, row, bs)), jnp.float32)
    tables = jnp.asarray([[4, 2, 7, 1], [3, 5, 6, 0]], jnp.int32)
    index = jnp.asarray([5, 8], jnp.int32)  # one straddles two blocks
    rows = jnp.asarray(rng.standard_normal((2, 1, row, 6)), jnp.float32)
    plain = write_chunk(pool, rows, tables, index, block_size=bs)
    kernel = write_chunk(pool, rows, tables, index, block_size=bs,
                         kernel=True)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(kernel))
    want = np.asarray(pool).copy()
    for b in range(2):
        for c in range(6):
            p = int(index[b]) + c
            want[int(tables[b, p // bs]), 0, :, p % bs] = rows[b, 0, :, c]
    np.testing.assert_array_equal(np.asarray(plain), want)


# ---- the routed layer: a chip's share, the shared expert and attention once --


def test_the_two_halves_add_up_to_the_uncut_layer(params):
    """A routed layer as two chips hold it: experts 0-3 and 4-7 of the
    held 8 (the uncut layer here), the router, the shared expert and the
    latent attention whole in both. Each half's feed-forward output is its
    experts' part plus the shared expert; the two halves, with the shared
    expert and the attention counted once, are the reference's layer with
    every expert."""
    p = params["block_1"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 20, Z["d"]),
                          jnp.float32)

    def run(first, count):
        cfg = config(routed_first=first, routed_count=count)
        banks = {name: {"kernel": p["mlp"][name]["kernel"][
            first:first + count]} for name in ("w_gate", "w_up", "w_down")}
        tree = {**p, "mlp": {**p["mlp"], **banks}}
        y, mut = Block(cfg, kinds=("latent_attention", "routed")).apply(
            {"params": tree}, x, mutable=["routed_stats"])
        return y, mut["routed_stats"]["mlp"]["load"][0]

    whole, load = run(0, 8)
    low, load_low = run(0, 4)
    high, load_high = run(4, 4)
    assert np.array_equal(load, load_low) and np.array_equal(load, load_high)
    assert int(load.sum()) == 40 * Z["k"]
    leaves = weights_pangu.layer_leaves(
        weights_pangu.seed_key(weights_pangu.seed_arg(SEED)), Z, 1,
        ("latent_attention", "routed"))
    eps = Z["eps"]
    rms = pangu_moe.rms_norm
    # the attention half, once: the residual stream the feed-forward reads
    mid = jax.vmap(lambda s: s + rms(pangu_moe.latent_attention(
        rms(s, leaves["ln1_g"], eps), leaves, sizes=Z,
        operands="float32"), leaves["ln1_post_g"], eps))(x)
    b = rms(mid, leaves["ln2_g"], eps).reshape(40, -1)
    shared = pangu_moe.gated(b, leaves["s_gate"], leaves["s_up"],
                             leaves["s_down"], "float32")
    parts = []
    for first in (0, 4):
        held = {**leaves, **{n: leaves[n][first:first + 4]
                             for n in ("e_gate", "e_up", "e_down")}}
        part, mask = pangu_moe.routed_ffn(b, held, k=Z["k"],
                                          scale=Z["scale"], first=first,
                                          operands="float32")
        parts.append(part)
    np.testing.assert_array_equal(np.asarray(load),
                                  np.asarray((mask > 0).sum(0)))
    # the program's two halves of the routed feed-forward, before the
    # post-norm (where a deployment's exchange would add them), are the
    # reference's halves and add up to its experts' whole
    from distributed_tensorflow_guide_tpu.models.transformer import RoutedMLP

    for first, part in ((0, parts[0]), (4, parts[1])):
        mlp = {"router": p["mlp"]["router"],
               "expert_bias": p["mlp"]["expert_bias"],
               **{n: {"kernel": p["mlp"][n]["kernel"][first:first + 4]}
                  for n in ("w_gate", "w_up", "w_down")}}
        got = RoutedMLP(config(routed_first=first, routed_count=4)).apply(
            {"params": mlp}, b[None])[0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(part),
                                   rtol=2e-4, atol=2e-4)
    ffn = parts[0] + parts[1] + shared  # the shared expert once
    want = mid.reshape(40, -1) + rms(ffn, leaves["ln2_post_g"], eps)
    np.testing.assert_allclose(np.asarray(whole.reshape(40, -1)),
                               np.asarray(want), rtol=2e-4, atol=2e-4)
    # each program's half: its experts' part and the shared expert, after
    # the attention both computed alike
    for got, part in ((low, parts[0]), (high, parts[1])):
        np.testing.assert_allclose(
            np.asarray(got.reshape(40, -1)),
            np.asarray(mid.reshape(40, -1) + rms(
                part + shared, leaves["ln2_post_g"], eps)),
            rtol=2e-4, atol=2e-4)


# ---- the spans a trace names --------------------------------------------------


def test_a_cpu_profile_of_serving_names_both_scopes(params, tmp_path):
    """A profile of the engine serving on the CPU carries the programs'
    scope paths: ``dtg.mla`` on every latent layer and ``dtg.latent_attn``
    only inside it, around the read of the pool (the write is outside)."""
    import re
    from pathlib import Path

    reqs = prompts([11, 6], seed=9)
    with jax.profiler.trace(str(tmp_path)):
        serve(config(), params, reqs, max_new=3)
    (path,) = Path(tmp_path).rglob("*.xplane.pb")
    raw = path.read_bytes()
    paths = {p.decode() for p in re.findall(rb"[\w./\-]*dtg\.[\w.]+", raw)}
    for b in range(3):
        mine = [p for p in paths if f"block_{b}/" in p]
        assert any("dtg.mla" in p.split("/") for p in mine), b
        kernel = [p for p in mine if "dtg.latent_attn" in p.split("/")]
        assert kernel, b
        for p in kernel:
            parts = p.split("/")
            assert "dtg.mla" in parts[:parts.index("dtg.latent_attn")], p
