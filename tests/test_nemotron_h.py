"""A model whose layers are a mixer or a feed-forward alone (Mamba-2
state-space mixers, grouped-query attention without positions, relu^2
experts of which the program holds a share, a shared expert) through the
same ``ServeEngine`` as GPT-2 and LFM2: chunked prefill and decode through
the block pool and the per-slot state leaves against the plain reference's
full forward (``yardstick/reference/nemotron_h.py``, which imports nothing
from the package), at a size the CPU holds, on seeded weights."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_guide_tpu.models.transformer import (
    Block,
    Transformer,
    TransformerConfig,
)
from distributed_tensorflow_guide_tpu.ops.ssm_scan import (
    ssm_chunked,
    ssm_step,
)
from distributed_tensorflow_guide_tpu.serve import engine as E
from distributed_tensorflow_guide_tpu.serve.engine import Request, ServeEngine
from yardstick import weights_nemotron
from yardstick.reference import nemotron_h

SEED = 2 ** 31 + 33
CONFIG = {
    "hidden_size": 48, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 40, "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 40, "n_routed_experts": 8,
    "num_experts_per_tok": 3, "routed_scaling_factor": 2.5,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "vocab_size": 256, "layer_norm_epsilon": 1e-5,
    # every kind of layer, the plain feed-forward too (no layer of the
    # cell's cut is one)
    "hybrid_override_pattern": "MEM*E-M",
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "published": {"n_routed_experts": {"published": 8}},
    "assumed": {"drawn": {"initializer_range": 0.1, "router_std": 0.5,
                          "expert_bias_std": 0.05, "conv_std": 0.29,
                          "a_max": 16.0}},
    "deployment": {"max_positions": 64, "experts_held": [0, 8]},
}
Z = weights_nemotron.sizes_of(CONFIG)
GEOMETRY = dict(slots=3, num_blocks=25, block_size=8, prefill_chunk=8)


def config(dtype=jnp.float32, z=Z, **kw) -> TransformerConfig:
    return TransformerConfig(**{**dict(
        vocab_size=z["vocab"], num_layers=z["L"], num_heads=z["h"],
        d_model=z["d"], d_ff=z["ff"], max_len=z["positions"], dtype=dtype,
        layers=z["layers"], norm="rmsnorm", norm_eps=z["eps"],
        ffn_gate="relu2", positions="none", num_kv_heads=z["kv"],
        override_head_dim=z["hd"], conv_kernel=z["taps"],
        ssm_heads=z["H"], ssm_head_dim=z["P"], ssm_groups=z["G"],
        ssm_state=z["N"], ssm_chunk=z["chunk"], routed_experts=z["E"],
        routed_top_k=z["k"], routed_d_ff=z["eff"], routed_first=z["first"],
        routed_count=z["held"], routed_scale=z["scale"],
        routed_norm_eps=1e-20, shared_d_ff=z["sff"]), **kw})


@pytest.fixture(scope="module")
def params():
    """The seed's tree as float32 (the bfloat16 numbers, widened): what
    both sides multiply, so that float32 runs agree to rounding."""
    return jax.tree.map(lambda x: x.astype(jnp.float32),
                        weights_nemotron.flax_tree(SEED, Z))


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
        ref = np.asarray(nemotron_h.forward(SEED, toks, z))[:-1]
        at = np.arange(len(prompt) - 1, len(toks) - 1)
        out.append(ref[at].max(-1) - ref[at, toks[at + 1]])
    return out


def test_the_tree_is_the_one_the_model_declares():
    import flax.linen as nn

    shapes = jax.eval_shape(Transformer(config()).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    declared = nn.meta.unbox(shapes["params"])
    made = weights_nemotron.flax_tree(SEED, Z)
    assert jax.tree.structure(declared) == jax.tree.structure(made)
    assert ([a.shape for a in jax.tree.leaves(declared)]
            == [a.shape for a in jax.tree.leaves(made)])
    assert "pos_emb" not in made  # no positions of any kind
    # a layer is one half and has one norm: ln1 over a mixer, ln2 over a
    # feed-forward
    assert set(made["block_0"]) == {"ln1", "ssm"}
    assert set(made["block_1"]) == {"ln2", "mlp", "shared"}
    assert set(made["block_3"]) == {"ln1", "attn"}
    assert set(made["block_5"]) == {"ln2", "mlp"}
    assert set(made["block_1"]["mlp"]) == {"router", "expert_bias", "w_up",
                                           "w_down"}  # no gate bank
    assert set(made["block_3"]["attn"]) == {"qkv", "proj"}  # no head norms
    ssm = made["block_0"]["ssm"]
    assert ssm["A_log"].dtype == ssm["dt_bias"].dtype == jnp.float32
    assert ssm["in_proj"]["kernel"].dtype == jnp.bfloat16
    assert ssm["in_proj"]["kernel"].shape == (48, 32 + 96 + 4)


def test_training_view_agrees_with_the_reference(params):
    (tokens,) = prompts([23], seed=1)  # three chunks of 8, the last short
    got = Transformer(config()).apply({"params": params}, tokens[None])[0]
    want = nemotron_h.forward(SEED, tokens, Z)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def paged_logits(cfg, tree, tokens, chunk, slot=1, state=None):
    """Logits at every position of ``tokens`` as the engine computes them:
    the prompt in chunks of ``chunk`` (the last one padded), through the
    block pool and the state leaves of slot ``slot``."""
    fns = E.build_step_fns(cfg, temperature=0.0, **GEOMETRY)
    pool = E.paged_cache_pool(fns.cfg, GEOMETRY["slots"])
    if state is None:
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
    chunks and one that is mostly padding, each from the state the one
    before left); chunks of 1 are the recurrence's single step, a token at
    a time through the pool and the state."""
    (tokens,) = prompts([21], seed=2)
    got, _ = paged_logits(config(), params, tokens, chunk)
    want = nemotron_h.forward(SEED, tokens, Z)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_padding_and_idle_rows_leave_both_state_leaves_as_they_were(params):
    (tokens,) = prompts([13], seed=3)
    cfg = config()
    _, padded = paged_logits(cfg, params, tokens, 8)  # 8 + 5 of 8
    _, single = paged_logits(cfg, params, tokens, 1)  # never padded
    leaves = jax.tree_util.tree_leaves_with_path(padded)
    kinds = {jax.tree_util.keystr(p).rpartition("'")[0].rpartition("'")[2]
             for p, _ in leaves}
    assert kinds == {"conv", "ssm"}
    for (_, a), b in zip(leaves, jax.tree.leaves(single)):
        np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b[1]),
                                   rtol=1e-5, atol=1e-6)
        assert np.any(np.asarray(a[1]))
        assert not np.any(np.asarray(a[0])) and not np.any(np.asarray(a[2]))
    ssm = padded["block_0"]["ssm"]["ssm"]
    assert ssm.dtype == jnp.float32 and ssm.shape == (3, 4, 8, 16)
    # a decode launch in which only slot 1 is live: the other rows keep
    # what they hold to the last bit
    fns = E.build_step_fns(cfg, temperature=0.0, **GEOMETRY)
    marked = jax.tree.map(lambda x: x.at[0].set(0.5).at[2].set(-0.25),
                          padded)
    pool = E.paged_cache_pool(fns.cfg, 3)
    tables = jnp.zeros((3, fns.n_blk), jnp.int32).at[1].set(
        jnp.arange(1, 1 + fns.n_blk))
    _, _, after, _ = fns.decode(
        params, pool, marked, tables, jnp.asarray([0, 13, 0], jnp.int32),
        jnp.asarray([7, 7, 7], jnp.int32), jnp.zeros((3, 2), jnp.uint32))
    for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(marked)):
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
        np.testing.assert_array_equal(np.asarray(a[2]), np.asarray(b[2]))
        assert np.any(np.asarray(a[1]) != np.asarray(b[1]))


def test_the_engine_serves_what_the_reference_puts_first(params):
    reqs = prompts([5, 13, 8, 21, 9], seed=4)  # more requests than slots
    eng = serve(config(), params, reqs)
    assert eng.steps["prefill"] >= 8 and eng.steps["decode"] >= 6
    for i, gap in enumerate(served_gaps(eng, reqs)):
        # float32: a served token is the reference's own choice unless two
        # logits lie within rounding of each other
        assert gap.max() < 1e-3, (i, gap)
    health = eng.health()
    assert health["state_bytes"] == 3 * 3 * (
        4 * 8 * 16 * 4 + 3 * (32 + 64) * 4)  # 3 mamba2 layers, 3 slots
    assert health["pool_bytes"] == 2 * 25 * 2 * 16 * 8 * 4
    routed = health["routed"]
    assert routed["assignments"] == routed["held_assignments"] > 0


def test_a_reused_slot_reads_no_state_of_the_request_before(params):
    """One slot, two requests one after the other: the second is served as
    it is alone in a fresh engine, though the slot's leaves still hold the
    first's state when its first chunk runs."""
    first, second = prompts([19, 11], seed=9)
    both = serve(config(), params, [first, second], slots=1)
    alone = serve(config(), params, [second], slots=1)
    assert both.completions()[1] == alone.completions()[0]
    assert both.completions()[0] != both.completions()[1]
    # and straight at the model: slot 1's leaves filled with another
    # sequence's state, then a prompt from position 0
    cfg = config()
    _, dirty = paged_logits(cfg, params, first, 8)
    got, _ = paged_logits(cfg, params, second, 8, state=dirty)
    want, _ = paged_logits(cfg, params, second, 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bfloat16_serving_stays_near_the_reference():
    tree = weights_nemotron.flax_tree(SEED, Z)
    reqs = prompts([13, 21, 9], seed=5)
    eng = serve(config(jnp.bfloat16), tree, reqs, max_new=8)
    gaps = np.concatenate(served_gaps(eng, reqs))
    assert np.mean(gaps) < 0.05, gaps
    assert eng.state["block_0"]["ssm"]["ssm"].dtype == jnp.float32
    assert eng.state["block_0"]["ssm"]["conv"].dtype == jnp.bfloat16


def test_a_preempted_request_resumes_to_the_same_tokens(params):
    reqs = prompts([13, 21, 9], seed=6)
    roomy = serve(config(), params, reqs, max_new=20)
    # 6 blocks of 8 beside the trash block: three residents outgrow them
    tight = serve(config(), params, reqs, max_new=20, num_blocks=7)
    assert tight.health()["preemptions"] > 0 == roomy.health()["preemptions"]
    assert tight.completions() == roomy.completions()


def test_what_moves_blocks_alone_refuses_a_model_with_state(params):
    cfg = config()
    assert cfg.stateful and cfg.state_mixers == ("mamba2",)
    for kw in ({"prefix_cache": True}, {"host_blocks": 8}):
        with pytest.raises(ValueError, match="mamba2 mixers"):
            ServeEngine(cfg, params, **GEOMETRY, **kw)
    eng = ServeEngine(cfg, params, temperature=0.0, **GEOMETRY)
    (prompt,) = prompts([9], seed=7)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=8,
                       rng=np.zeros((2,), np.uint32)))
    for _ in range(4):
        eng.step(0.0)
    with pytest.raises(ValueError, match="with_kv=False"):
        eng.export_stream(0, with_kv=True)
    other = ServeEngine(cfg, params, temperature=0.0, **GEOMETRY)
    other.adopt_stream(eng.export_stream(0, with_kv=False))
    other.run()
    whole = serve(cfg, params, [prompt], max_new=8)
    assert other.completions()[0] == whole.completions()[0]


# ---- the configuration ------------------------------------------------------


def test_a_layer_is_a_half_alone_and_positions_are_said_once():
    cfg = config()
    assert cfg.position_kind == "none" and cfg.head_dim == 16
    assert cfg.layers[0] == ("mamba2", None) and cfg.layers[1] == (
        None, "routed")
    with pytest.raises(ValueError, match="not both"):
        config(layers=((None, None),) * 7)
    with pytest.raises(ValueError, match="rope_theta"):
        config(positions="rotary")
    with pytest.raises(ValueError, match="rope_theta"):
        config(rope_theta=1e4)  # positions="none" beside a rotation
    with pytest.raises(ValueError, match="positions"):
        config(positions="alibi")
    with pytest.raises(ValueError, match="ssm_heads"):
        config(ssm_state=None)
    with pytest.raises(ValueError, match="ssm_groups"):
        config(ssm_groups=3)
    with pytest.raises(ValueError, match="no layer here is routed"):
        config(layers=(("mamba2", None),) * 7)
    # what configurations before the field mean by leaving it out
    base = dict(vocab_size=64, num_layers=1, num_heads=2, d_model=16,
                d_ff=32, max_len=32, layers=(("attention", "dense"),))
    assert TransformerConfig(**base).position_kind == "table"
    assert TransformerConfig(**base, rope_theta=1e4).position_kind == "rotary"
    assert TransformerConfig(vocab_size=64).position_kind == "table"
    with pytest.raises(ValueError, match="layers"):
        TransformerConfig(positions="none")
    with pytest.raises(ValueError, match="layers"):
        TransformerConfig(ssm_heads=4)
    # a table where the model has one, none where it has none
    made = jax.eval_shape(
        Transformer(TransformerConfig(**base, positions="table")).init,
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    assert "pos_emb" in made
    made = jax.eval_shape(
        Transformer(TransformerConfig(**base, positions="none")).init,
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    assert "pos_emb" not in made


# ---- the scan ---------------------------------------------------------------


def scan_inputs(B=2, S=21, H=4, P=8, G=2, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, S, H)) - 1.0)
    a = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.5))
    b = jax.random.normal(k[3], (B, S, G, N), jnp.float32)
    c = jax.random.normal(k[4], (B, S, G, N), jnp.float32)
    state = jax.random.normal(k[5], (B, H, P, N), jnp.float32)
    return x, dt, a, b, c, state


def by_steps(x, dt, a, b, c, state):
    ys = []
    for t in range(x.shape[1]):
        y, state = ssm_step(x[:, t], dt[:, t], a, b[:, t], c[:, t], state)
        ys.append(y)
    return jnp.stack(ys, axis=1), state


@pytest.mark.parametrize("chunk", [8, 32])
def test_the_chunked_scan_is_the_recurrence_a_step_at_a_time(chunk):
    """21 positions from a carried state that is not zero: in chunks of 8
    (two boundaries and a short last chunk) and in one chunk of 32, against
    the recurrence itself; and against the reference's own scan."""
    x, dt, a, b, c, state = scan_inputs()
    want_y, want_state = by_steps(x, dt, a, b, c, state)
    got_y, got_state = ssm_chunked(x, dt, a, b, c, state, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_state), np.asarray(want_state),
                               rtol=2e-5, atol=2e-5)
    zero = jnp.zeros_like(state)
    ref = nemotron_h.recurrence(x[0], dt[0], a, jnp.repeat(b[0], 2, axis=1),
                                jnp.repeat(c[0], 2, axis=1))
    got, _ = ssm_chunked(x[:1], dt[:1], a, b[:1], c[:1], zero[:1],
                         chunk=chunk)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_a_step_of_size_zero_moves_no_state_and_a_run_can_be_split():
    x, dt, a, b, c, state = scan_inputs(seed=1)
    # positions 13.. are padding: the state after is the state after 13
    padded = dt.at[:, 13:].set(0.0)
    _, after = ssm_chunked(x, padded, a, b, c, state, chunk=8)
    _, want = ssm_chunked(x[:, :13], dt[:, :13], a, b[:, :13], c[:, :13],
                          state, chunk=8)
    np.testing.assert_allclose(np.asarray(after), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    _, same = ssm_step(x[:, 0], jnp.zeros_like(dt[:, 0]), a, b[:, 0],
                       c[:, 0], state)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(state))
    # a run in two calls, the second from what the first carried out
    y1, mid = ssm_chunked(x[:, :8], dt[:, :8], a, b[:, :8], c[:, :8], state)
    y2, end = ssm_chunked(x[:, 8:], dt[:, 8:], a, b[:, 8:], c[:, 8:], mid)
    whole_y, whole = ssm_chunked(x, dt, a, b, c, state, chunk=32)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2], 1)), np.asarray(whole_y),
        rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(end), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)
    # a long decay underflows to zero and never to a NaN
    far = ssm_chunked(x, dt * 400.0, a, b, c, state, chunk=32)
    assert all(bool(jnp.all(jnp.isfinite(v))) for v in far)


# ---- the grouped product ----------------------------------------------------


def test_the_pallas_grouped_product_is_the_native_one():
    """Five groups of which one is empty and whose sizes fall short of the
    rows (the rest belongs to experts held elsewhere), through JAX's Pallas
    grouped matmul in interpret mode with this file's tiles, against
    ``lax.ragged_dot``; the tiles derived for the Nemotron cell's banks;
    and which banks take which."""
    from distributed_tensorflow_guide_tpu.ops import routed_ffn as R

    k = jax.random.split(jax.random.PRNGKey(0), 2)
    rows = jax.random.normal(k[0], (200, 256), jnp.float32)
    bank = jax.random.normal(k[1], (5, 256, 384), jnp.float32)
    sizes = jnp.asarray([30, 0, 90, 7, 40], jnp.int32)
    native = R.grouped_product(rows, bank, sizes, impl="native")
    pallas = R.grouped_product(rows, bank, sizes, impl="pallas",
                               interpret=True)
    assert pallas.shape == native.shape == (200, 384)
    np.testing.assert_allclose(np.asarray(pallas[:167]),
                               np.asarray(native[:167]), rtol=1e-4,
                               atol=1e-3)
    # the whole contraction and whole lanes of the output within 8 MiB
    assert R.grouped_tiles(2688, 1920, 2) == (128, 2688, 384)
    assert R.grouped_tiles(1920, 2688, 2) == (128, 1920, 384)
    assert R.grouped_tiles(2688, 1920, 2, budget=1 << 20) == (128, 512, 128)
    # the CPU keeps the native call whatever the bank
    assert R.grouped_impl(2688, 1920) == "native"


def test_on_a_tpu_a_bank_of_odd_lanes_takes_the_pallas_product(monkeypatch):
    from distributed_tensorflow_guide_tpu.ops import routed_ffn as R

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert R.grouped_impl(2688, 1920) == R.grouped_impl(1920, 2688) == "pallas"
    assert R.grouped_impl(2048, 1536) == R.grouped_impl(1536, 2048) == "native"
    assert R.grouped_impl(48, 24) == "native"  # no whole lanes


# ---- the routed layer: a chip's share, and the shared expert once ------------


def test_the_two_halves_and_the_shared_expert_once_add_up_to_the_layer(
        params):
    """The layer as two chips hold it: experts 0-3 and 4-7, the router and
    the shared expert whole in both. Their parts, less the shared expert
    that both computed, are the uncut layer; each part is the reference's
    for the same experts."""
    p = params["block_1"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 20, Z["d"]),
                          jnp.float32)

    def run(first, count):
        cfg = config(routed_first=first, routed_count=count)
        tree = {**p, "mlp": {
            **p["mlp"],
            "w_up": {"kernel": p["mlp"]["w_up"]["kernel"][
                first:first + count]},
            "w_down": {"kernel": p["mlp"]["w_down"]["kernel"][
                first:first + count]}}}
        y, mut = Block(cfg, kinds=(None, "routed")).apply(
            {"params": tree}, x, mutable=["routed_stats"])
        return y - x, mut["routed_stats"]["mlp"]["load"][0]

    whole, load = run(0, 8)
    low, load_low = run(0, 4)
    high, load_high = run(4, 4)
    # the census is every share's alike, over all 8 experts
    assert np.array_equal(load, load_low) and np.array_equal(load, load_high)
    assert int(load.sum()) == 40 * Z["k"]
    h = nemotron_h.rms_norm(x.reshape(40, -1), p["ln2"]["scale"], Z["eps"])
    shared = nemotron_h.relu2(h, p["shared"]["up"]["kernel"],
                              p["shared"]["down"]["kernel"], "float32")
    np.testing.assert_allclose(
        np.asarray((low + high).reshape(40, -1) - shared),
        np.asarray(whole.reshape(40, -1)), rtol=1e-4, atol=1e-5)
    # a half is the reference's half: routed part of its experts + shared
    leaves = {"router_w": p["mlp"]["router"], "bias": p["mlp"]["expert_bias"],
              "e_up": p["mlp"]["w_up"]["kernel"][4:],
              "e_down": p["mlp"]["w_down"]["kernel"][4:]}
    part, mask = nemotron_h.routed_ffn(h, leaves, k=Z["k"], scale=Z["scale"],
                                       first=4, operands="float32")
    np.testing.assert_allclose(np.asarray(high.reshape(40, -1)),
                               np.asarray(part + shared), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(load),
                                  np.asarray((mask > 0).sum(0)))
    # the weights of a token's experts sum to the scaling factor
    np.testing.assert_allclose(np.asarray(mask.sum(1)), Z["scale"],
                               rtol=1e-5)
    # a token none of whose experts are held gets the shared expert alone
    nowhere = np.asarray((mask[:, 4:] > 0).sum(1) == 0)
    if nowhere.any():
        np.testing.assert_allclose(
            np.asarray(high.reshape(40, -1))[nowhere],
            np.asarray(shared)[nowhere], rtol=1e-4, atol=1e-5)


def test_an_engine_holding_half_the_experts_serves_the_references_half():
    """``first=0, count=E/2`` through the engine against the reference
    given the same half: the partial sum goes on to the next layer in both,
    and the census says how many assignments fell to experts held."""
    half = {**CONFIG, "n_routed_experts": 4,
            "deployment": {"max_positions": 64, "experts_held": [0, 4]}}
    z = weights_nemotron.sizes_of(half)
    assert (z["E"], z["first"], z["held"]) == (8, 0, 4)
    tree = jax.tree.map(lambda x: x.astype(jnp.float32),
                        weights_nemotron.flax_tree(SEED, z))
    whole = weights_nemotron.flax_tree(SEED, Z)
    # an expert's numbers are its own, whatever share holds it
    np.testing.assert_array_equal(
        np.asarray(tree["block_1"]["mlp"]["w_up"]["kernel"]),
        np.asarray(whole["block_1"]["mlp"]["w_up"]["kernel"][:4],
                   np.float32))
    reqs = prompts([13, 9, 17], seed=11)
    eng = serve(config(z=z), tree, reqs)
    for gap in served_gaps(eng, reqs, z):
        assert gap.max() < 1e-3, gap
    routed = eng.health()["routed"]
    assert 0 < routed["held_assignments"] < routed["assignments"]
    load = np.zeros((2, 8), np.int32)
    load[0, [0, 1, 5]] = [3, 1, 2]
    load[1, [4, 6]] = [4, 2]
    assert E._routed_counters(load, 0, 4) == {
        "assignments": 12, "held_assignments": 4, "experts_touched": 1.0,
        "load_ratio": 3.0}
    assert E._routed_counters(load[1:], 0, 4) == {
        "assignments": 6, "held_assignments": 0}
