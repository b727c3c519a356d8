"""The TPU compiler's verdict without a TPU.

libtpu can describe a topology it is not attached to, and JAX compiles for
it ahead of time: ``get_topology_desc("v5e:2x2")``, then
``jit(f).trace(...).lower(lowering_platforms=("tpu",)).compile()``. So every
Pallas kernel in ``ops/`` meets Mosaic — block shapes, layouts, the scoped
VMEM limit — in tier-1 on the CPU, and a PR learns that a kernel no longer
compiles before it spends chip time on it. Compiling is not executing: that
the programs run, and give right answers, is ``chip_smoke.py``'s to show.

On the CPU the package resolves five switches from ``jax.default_backend()``
(Pallas interpret mode, the decode kernels, fused cross-entropy, donation,
the autotune table). The ``as_on_tpu`` fixture gives them the values the chip
gives them, so what compiles here is what the chip would be handed.

The whole-program variants (GPT-2 124M train step, the engine's step pair)
take tens of seconds of host compile and are marked ``slow``.
"""

import functools
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from distributed_tensorflow_guide_tpu.ops import autotune
from distributed_tensorflow_guide_tpu.ops import decode_attention as DA
from distributed_tensorflow_guide_tpu.ops import flash_attention as FA

# GPT-2 124M head shapes, as chip_smoke.py runs them
B, H, S, HD = 2, 12, 1024, 64
DP = FA.LANE  # head dim padded to a lane
BLK = (128, 128)
SCALE = 1.0 / HD ** 0.5


@pytest.fixture(scope="module")
def v5e():
    """The four devices of a described (not attached) v5e 2x2."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # noqa: BLE001 - no libtpu, or one that cannot
        pytest.skip(f"libtpu cannot describe a v5e topology here: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


@pytest.fixture()
def as_on_tpu(monkeypatch, isolated_autotune_table):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not FA._interpret()


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def compile_for(sharding, fn, *args):
    """Compile ``fn`` (a function, or a ``jax.jit`` of one with its own
    donation) for the described TPU; ``args`` are abstract."""
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args)
    jitted = fn if hasattr(fn, "trace") else jax.jit(fn)
    return jitted.trace(*args).lower(lowering_platforms=("tpu",)).compile()


def assert_mosaic(compiled) -> None:
    assert "tpu_custom_call" in compiled.as_text()


def prefill_operands(rows: int, n_blk: int, chunk: int) -> tuple:
    """The host operands of a prefill launch of ``rows`` rows, abstract:
    tables, start, chunk, valid, keys (a patterned model's program takes
    its slots after them)."""
    return (sds((rows, n_blk), jnp.int32), sds((rows,), jnp.int32),
            sds((rows, chunk), jnp.int32), sds((rows,), jnp.int32),
            sds((rows, 2), jnp.uint32))


def test_compiler_is_in_the_loop(v5e):
    """The control: a kernel whose one block is 32 MiB of VMEM is refused,
    so a kernel that compiles below was really judged."""
    n = 4096 * 2048

    def copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def too_big(x):
        return pl.pallas_call(
            copy, out_shape=sds((4096, 2048), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM))(x)

    assert n * 4 == 32 << 20
    with pytest.raises(Exception, match="(?i)vmem"):
        compile_for(SingleDeviceSharding(v5e[0]), too_big,
                    sds((4096, 2048), jnp.float32))


def tracked_tiles(kernel: str) -> tuple[int, int]:
    """What the table git tracks gives a v5e for this file's shapes (the
    batch/head-generic entry): the tiles the chip is really handed."""
    name = {"fwd": "flash_fwd", "dq": "flash_dq", "dkv": "flash_dkv",
            "carry": "carry_step"}[kernel]
    table = json.loads(autotune.TRACKED_TABLE.read_text())
    ent = table[autotune._key(name, 0, 0, S, HD, "bfloat16", True,
                              "tpu:tpu-v5-lite")]
    return ent["blk_q"], ent["blk_k"]


@pytest.mark.parametrize("tiles", ["default", "table"])
@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv", "carry"])
def test_flash_kernels_compile(v5e, as_on_tpu, kernel, tiles):
    blk = BLK if tiles == "default" else tracked_tiles(kernel)
    x = sds((B, H, S, DP), jnp.bfloat16)
    row = sds((B, H, S, FA.LANE), jnp.float32)  # lse / delta / m / l
    kw = dict(scale=SCALE, blk_q=blk[0], blk_k=blk[1])
    fn, args = {
        "fwd": (functools.partial(FA._fwd_call, causal=True, **kw),
                (x, x, x)),
        "dq": (functools.partial(FA._bwd_dq_call, causal=True, **kw),
               (x, x, x, x, row, row)),
        "dkv": (functools.partial(FA._bwd_dkv_call, causal=True, **kw),
                (x, x, x, x, row, row)),
        "carry": (functools.partial(FA.flash_carry_step, diag=True, **kw),
                  (x, x, x, row, row, sds((B, H, S, DP), jnp.float32))),
    }[kernel]
    assert_mosaic(compile_for(SingleDeviceSharding(v5e[0]), fn, *args))


def test_flash_public_api_compiles_forward_and_backward(v5e, as_on_tpu):
    q = sds((B, S, H, HD), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(FA.flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    # the counts are the process's: other files' tests in this worker may
    # have fallen back already, so compare with what stood before
    before = FA.fallback_stats()
    assert_mosaic(compile_for(SingleDeviceSharding(v5e[0]),
                              jax.grad(loss, argnums=(0, 1, 2)), q, q, q))
    assert FA.fallback_stats() == before


@pytest.mark.parametrize("cache", ["bf16", "int8"])
def test_decode_kernel_compiles(v5e, as_on_tpu, cache):
    b = 8
    q = sds((b, 1, H, HD), jnp.bfloat16)
    kv = sds((b, H, S, HD), jnp.int8 if cache == "int8" else jnp.bfloat16)
    scale = sds((b, H, 1, S), jnp.float32)

    def fn(q, k, v, *scales):
        ks, vs = scales or (None, None)
        return DA.decode_attention(q, k, v, 700, key_scale=ks,
                                   value_scale=vs)

    args = (q, kv, kv) + ((scale, scale) if cache == "int8" else ())
    assert_mosaic(compile_for(SingleDeviceSharding(v5e[0]), fn, *args))


@pytest.mark.parametrize("cache", ["bf16", "int8"])
@pytest.mark.parametrize("block_size,chunk", [(8, 1), (16, 1), (16, 128),
                                              (32, 1), (128, 1)])
def test_paged_decode_kernel_compiles(v5e, as_on_tpu, cache, block_size,
                                      chunk):
    b = 8 if chunk == 1 else 1  # a decode step, or one prefill chunk
    n_blk = S // block_size
    q = sds((b, chunk, H, HD), jnp.bfloat16)
    pool = sds((8 * n_blk + 1, H, HD, block_size),
               jnp.int8 if cache == "int8" else jnp.bfloat16)
    scale = sds((8 * n_blk + 1, H, 1, block_size), jnp.float32)

    def fn(q, k, v, tables, lengths, *scales):
        ks, vs = scales or (None, None)
        return DA.paged_decode_attention(
            q, k, v, tables, lengths, key_scale_pool=ks,
            value_scale_pool=vs, block_size=block_size)

    args = (q, pool, pool, sds((b, n_blk), jnp.int32), sds((b,), jnp.int32))
    args += (scale, scale) if cache == "int8" else ()
    assert_mosaic(compile_for(SingleDeviceSharding(v5e[0]), fn, *args))


# ---- a patterned model's parts, at LFM2's published widths (PR 28) ----------


@pytest.mark.parametrize("chunk", [1, 128])
def test_paged_decode_kernel_compiles_under_grouped_heads(v5e, as_on_tpu,
                                                          chunk):
    """32 query heads over a pool of 8: a decode step of 64 rows, and one
    prefill chunk, in blocks of 128 as the LFM2 cell runs them."""
    b, heads, kv_heads, block_size, n_blk = (64 if chunk == 1 else 1, 32, 8,
                                             128, 16)
    q = sds((b, chunk, heads, HD), jnp.bfloat16)
    pool = sds((1024, kv_heads, HD, block_size), jnp.bfloat16)

    def fn(q, k, v, tables, lengths):
        return DA.paged_decode_attention(q, k, v, tables, lengths,
                                         block_size=block_size)

    assert_mosaic(compile_for(
        SingleDeviceSharding(v5e[0]), fn, q, pool, pool,
        sds((b, n_blk), jnp.int32), sds((b,), jnp.int32)))


#: a decode launch (every slot a row of one token) and the prefill launches
#: of the engine's widths (a row a prompt's 128-token chunk)
LAUNCHES = pytest.mark.parametrize(
    "chunk,prompts", [(1, None), (128, 1), (128, 2), (128, 4)],
    ids=["decode", "prefill1", "prefill2", "prefill4"])


@pytest.mark.parametrize("cache", ["bf16", "int8"])
@LAUNCHES
@pytest.mark.parametrize("cell", ["gpt2-xl", "lfm2-24b-a2b"])
def test_paged_kernel_grid_at_the_serving_cells_shapes(v5e, as_on_tpu, cell,
                                                       chunk, prompts, cache):
    """Both serving cells' attention layer, a decode launch and a prefill
    launch of each width: ONE Pallas call (the benchmark counts launches
    as kernel events over layers), whose grid is a step a slot and key
    tile at decode, every pool head in it (GPT-2 XL 24 x 8 = 192 steps,
    LFM2 64 x 16 = 1,024), and a derived divisor of the heads a step at a
    128-token chunk, a row of the grid a prompt; and the v5e's compiler
    takes it."""
    import math

    from distributed_tensorflow_guide_tpu.analysis import walker

    heads, kv_heads, rows, n_blk = {
        "gpt2-xl": (25, 25, 24, 8), "lfm2-24b-a2b": (32, 8, 64, 16)}[cell]
    b, block_size = prompts or rows, 128
    assert DA.paged_supported(n_blk * block_size, block_size, block_size,
                              chunk)
    dtype = jnp.int8 if cache == "int8" else jnp.bfloat16
    pool = sds((b * n_blk + 1, kv_heads, HD, block_size), dtype)
    scale = sds((b * n_blk + 1, kv_heads, 1, block_size), jnp.float32)

    def fn(q, k, v, tables, lengths, *scales):
        ks, vs = scales or (None, None)
        return DA.paged_decode_attention(
            q, k, v, tables, lengths, key_scale_pool=ks,
            value_scale_pool=vs, block_size=block_size)

    args = (sds((b, chunk, heads, HD), jnp.bfloat16), pool, pool,
            sds((b, n_blk), jnp.int32), sds((b,), jnp.int32))
    args += (scale, scale) if cache == "int8" else ()
    calls = [e for e in walker.walk(jax.make_jaxpr(fn)(*args))
             if walker.prim_name(e) == "pallas_call"]
    assert len(calls) == 1
    grid = tuple(int(g) for g in calls[0].params["grid_mapping"].grid)
    hb = DA.paged_heads_per_step(
        kv_heads, group=heads // kv_heads, chunk=chunk, hd=HD,
        blk_k=block_size, dtype=dtype, q_dtype=jnp.bfloat16)
    assert grid == (b, kv_heads // hb, n_blk)
    if chunk == 1:
        assert hb == kv_heads and math.prod(grid) == b * n_blk
    else:
        assert 1 <= hb < kv_heads and kv_heads % hb == 0
    compiled = compile_for(SingleDeviceSharding(v5e[0]), fn, *args)
    assert compiled.as_text().count("tpu_custom_call") == 1


def leaf_sized_results(text: str, leaf: tuple[int, ...]) -> list[str]:
    """The instructions of a compiled program whose result has a pool
    leaf's shape, in any layout: ``name opcode`` each (parameters and the
    tuple plumbing of a loop left out: they move nothing)."""
    dims = ",".join(map(str, leaf))
    found = re.findall(
        r"%(\S+) = \w+\[" + dims + r"\]\{[^}]*\} ([\w-]+)\(", text)
    return [f"{name} {op}" for name, op in found
            if op not in ("parameter", "get-tuple-element")]


@LAUNCHES
@pytest.mark.parametrize("cell", ["gpt2-xl", "lfm2-24b-a2b"])
def test_paged_layer_moves_no_leaf(v5e, as_on_tpu, cell, chunk, prompts):
    """One attention layer's cache write and paged kernel at the serving
    cells' sizes, leaves donated, in a decode launch and in a prefill
    launch of each width: nothing of a leaf's size happens but the write
    itself, in the leaf's own buffer (ROADMAP S8: a pool declared (N, h,
    bs, hd) was copied whole three to four times a leaf here)."""
    from distributed_tensorflow_guide_tpu.serve.paged_cache import write_chunk

    blocks, heads, kv_heads, rows, n_blk = {
        "gpt2-xl": (129, 25, 25, 24, 8),
        "lfm2-24b-a2b": (1024, 32, 8, 64, 16)}[cell]
    b, block_size = prompts or rows, 128
    leaf = (blocks, kv_heads, HD, block_size)

    def layer(kp, vp, q, k, v, tables, index):
        kp, vp = (write_chunk(p, jnp.transpose(x, (0, 2, 3, 1)), tables,
                              index, block_size=block_size, kernel=True)
                  for p, x in ((kp, k), (vp, v)))
        return kp, vp, DA.paged_decode_attention(
            q, kp, vp, tables, index + chunk, block_size=block_size)

    kv = sds((b, chunk, kv_heads, HD), jnp.bfloat16)
    compiled = compile_for(
        SingleDeviceSharding(v5e[0]), jax.jit(layer, donate_argnums=(0, 1)),
        sds(leaf, jnp.bfloat16), sds(leaf, jnp.bfloat16),
        sds((b, chunk, heads, HD), jnp.bfloat16), kv, kv,
        sds((b, n_blk), jnp.int32), sds((b,), jnp.int32))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3  # two writes and the kernel
    moved = leaf_sized_results(text, leaf)
    assert len(moved) == 2 and all("custom-call" in m for m in moved), moved
    mem = compiled.memory_analysis()
    leaf_bytes = 2 * blocks * kv_heads * HD * block_size
    assert mem.alias_size_in_bytes == 2 * leaf_bytes
    assert mem.temp_size_in_bytes < leaf_bytes // 8


@pytest.mark.parametrize("rows,first,held", [(64, 0, 64), (128, 0, 64),
                                             (128, 8, 8), (256, 0, 64),
                                             (512, 0, 64)])
def test_routed_ffn_compiles_to_native_grouped_products(v5e, rows, first,
                                                        held):
    """A decode launch's rows and those of a prefill launch of one, two and
    four chunks over all 64 experts, and a chip's share of 8: three grouped
    products, each one call of the compiler's own whose operations follow
    the rows, not the experts."""
    from distributed_tensorflow_guide_tpu.ops.routed_ffn import routed_ffn

    d, ff, experts, top_k = 2048, 1536, 64, 4

    def fn(x, router, bias, w_gate, w_up, w_down):
        return routed_ffn(x, router, bias, w_gate, w_up, w_down,
                          top_k=top_k, first=first)

    compiled = compile_for(
        SingleDeviceSharding(v5e[0]), fn, sds((rows, d), jnp.bfloat16),
        sds((d, experts), jnp.float32), sds((experts,), jnp.float32),
        sds((held, d, ff), jnp.bfloat16), sds((held, d, ff), jnp.bfloat16),
        sds((held, ff, d), jnp.bfloat16))
    assert compiled.as_text().count("%ragged-dot-none") >= 3
    flops = compiled.cost_analysis()["flops"]
    products = rows * top_k * 3 * 2 * d * ff
    assert products <= flops < 1.5 * products  # not `held` times them


# ---- a model with state-space mixers, at the Nemotron cell's widths (PR 33) --


@pytest.mark.parametrize("chunk", [1, 128])
def test_paged_kernel_compiles_under_a_group_of_16_at_head_size_128(
        v5e, as_on_tpu, chunk):
    """32 query heads of 128 over a pool of 2: a group's 16 query heads on
    the sublanes of their pool head's step. A decode step of 128 rows takes
    both pool heads a step; a 128-token chunk takes a divisor of them."""
    heads, kv_heads, hd, block_size, n_blk = 32, 2, 128, 128, 16
    b = 128 if chunk == 1 else 1
    pool = sds((2048, kv_heads, hd, block_size), jnp.bfloat16)

    def fn(q, k, v, tables, lengths):
        return DA.paged_decode_attention(q, k, v, tables, lengths,
                                         block_size=block_size)

    hb = DA.paged_heads_per_step(
        kv_heads, group=heads // kv_heads, chunk=chunk, hd=hd,
        blk_k=block_size, dtype=jnp.bfloat16, q_dtype=jnp.bfloat16)
    assert kv_heads % hb == 0 and (hb == kv_heads or chunk > 1)
    compiled = compile_for(
        SingleDeviceSharding(v5e[0]), fn,
        sds((b, chunk, heads, hd), jnp.bfloat16), pool, pool,
        sds((b, n_blk), jnp.int32), sds((b,), jnp.int32))
    assert compiled.as_text().count("tpu_custom_call") == 1


def entry_results(text: str, leaf: tuple[int, ...]) -> list[str]:
    """The instructions of a compiled program's entry computation whose
    result has ``leaf``'s shape, ``name opcode`` each (parameters and tuple
    plumbing left out: they move nothing)."""
    dims = ",".join(map(str, leaf))
    entry = text[text.index("ENTRY"):]
    found = re.findall(
        r"%(\S+) = \w+\[" + dims + r"\]\{[^}]*\} ([\w-]+)\(", entry)
    return [f"{name} {op}" for name, op in found
            if op not in ("parameter", "get-tuple-element")]


def test_state_space_step_pair_compiles_and_moves_no_state_leaf(v5e,
                                                                as_on_tpu):
    """The step programs of a Mamba-2, attention and routed layer at the
    Nemotron cell's widths and geometry (128 slots, 2,048 blocks of 128, a
    128-token chunk; the prefill program at each of the engine's widths),
    pool and state donated: every donated byte is aliased, a launch's
    temporaries are a small part of ONE 268 MB state leaf (so no leaf, and
    no 638 MB bank of experts, is copied), the decode program makes each
    state leaf once, in its own buffer, and a prefill program writes its
    slots' rows as slice updates, one a row."""
    from distributed_tensorflow_guide_tpu.models.transformer import (
        TransformerConfig,
    )
    from distributed_tensorflow_guide_tpu.serve import engine as E
    from yardstick import harness, weights_nemotron

    held = harness.load_json(
        harness.HERE / "configs" / "nemotron-3-nano-30b-a3b.json")
    held["hybrid_override_pattern"] = "M*E"
    z = weights_nemotron.sizes_of(held)
    dep = held["deployment"]
    slots, chunk = dep["slots"], dep["prefill_chunk"]
    cfg = TransformerConfig(
        vocab_size=z["vocab"], num_layers=z["L"], num_heads=z["h"],
        d_model=z["d"], d_ff=z["ff"], max_len=z["positions"], causal=True,
        dtype=jnp.bfloat16, layers=z["layers"], norm="rmsnorm",
        norm_eps=z["eps"], ffn_gate="relu2", positions="none",
        num_kv_heads=z["kv"], override_head_dim=z["hd"],
        conv_kernel=z["taps"], ssm_heads=z["H"], ssm_head_dim=z["P"],
        ssm_groups=z["G"], ssm_state=z["N"], ssm_chunk=z["chunk"],
        routed_experts=z["E"], routed_top_k=z["k"], routed_d_ff=z["eff"],
        routed_d_ff_stored=z["eff_stored"], routed_first=z["first"],
        routed_count=z["held"], routed_scale=z["scale"],
        routed_norm_eps=1e-20, shared_d_ff=z["sff"])
    assert (cfg.num_heads // cfg.kv_heads, cfg.head_dim) == (16, 128)
    from distributed_tensorflow_guide_tpu.ops import routed_ffn

    assert routed_ffn.grouped_impl(z["d"], z["eff_stored"]) == "pallas"
    assert routed_ffn.grouped_impl(2048, 1536) == "native"  # LFM2's banks
    fns = E.build_step_fns(cfg, slots=slots, num_blocks=dep["num_blocks"],
                           block_size=dep["block_size"], prefill_chunk=chunk)
    assert fns.donates_pool and fns.declared_donate_argnums == (1, 2)
    params = jax.eval_shape(lambda: weights_nemotron.flax_tree(1, z))
    pool = E.paged_cache_shapes(fns.cfg, slots)
    state = E._serving_shapes(fns.cfg, slots)["state"]
    ssm_leaf, conv_leaf = (slots, 64, 64, 128), (slots, 3, 6144)
    assert {k: (v.shape, v.dtype) for k, v in
            state["block_0"]["ssm"].items()} == {
                "ssm": (ssm_leaf, jnp.float32),
                "conv": (conv_leaf, jnp.bfloat16)}
    donated = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves((pool, state)))
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    one = SingleDeviceSharding(v5e[0])
    decode = compile_for(
        one, fns.decode, params, pool, state, i32(slots, fns.n_blk),
        i32(slots), i32(slots), sds((slots, 2), jnp.uint32))
    prefills = [compile_for(one, fns.prefill, params, pool, state,
                            *prefill_operands(rows, fns.n_blk, chunk),
                            i32(rows))
                for rows in E.PREFILL_WIDTHS]
    ssm_bytes = 4 * slots * 64 * 64 * 128
    for compiled in (decode, *prefills):
        text = compiled.as_text()
        # the paged kernel, two cache writes, and the two grouped products
        # as the Pallas call with derived tiles (2688 and 1920 are no
        # multiples of 512: the native call would tile them 128 x 128)
        assert text.count("tpu_custom_call") >= 5
        assert "%ragged-dot-none" not in text and "jit(gmm)" in text
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == donated
        assert mem.temp_size_in_bytes < ssm_bytes // 8
    # decode: the leaf comes out of one fusion (state in, state and y out)
    # and nothing else has its shape; prefill: a slice update in place
    assert entry_results(decode.as_text(), ssm_leaf) == []
    for rows, prefill in zip(E.PREFILL_WIDTHS, prefills):
        moved = entry_results(prefill.as_text(), ssm_leaf)
        assert len(moved) == rows and all("fusion" in m for m in moved), moved
        moved = entry_results(prefill.as_text(), conv_leaf)
        assert moved and all("dynamic-update-slice" in m
                             for m in moved), moved
        assert "copy" not in " ".join(
            moved + entry_results(prefill.as_text(), ssm_leaf))
    assert not FA.fallback_stats()


def test_the_token_merge_lowers_and_the_step_pair_takes_what_it_took(
        v5e, as_on_tpu):
    """One launch in flight (PR 34): the slots' pending tokens stay on the
    device, at the serving cells' 24, 64 and 128 slots: a decode launch's
    vector merged by one ``where``, a prefill launch's samples (one, two
    or four) put into their slots' rows. Neither step program changes for
    it: the decode program lowers from the engine's own operands, the
    device vector among them, to the text it lowers to from the host
    vector it used to be handed."""
    from distributed_tensorflow_guide_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
    )
    from distributed_tensorflow_guide_tpu.serve import engine as E

    one = SingleDeviceSharding(v5e[0])
    for slots in (24, 64, 128):
        pending = sds((slots,), jnp.int32)
        programs = [compile_for(one, E._merge_tokens, pending, pending,
                                sds((slots,), jnp.bool_))]
        assert "select" in programs[0].as_text()
        programs += [compile_for(one, E._place_tokens, pending,
                                 sds((rows,), jnp.int32),
                                 sds((rows,), jnp.int32))
                     for rows in E.PREFILL_WIDTHS]
        for merged in programs:
            assert "tpu_custom_call" not in merged.as_text()
            (out,) = jax.tree.leaves(merged.out_info)
            assert (out.shape, out.dtype) == ((slots,), jnp.int32)
            # nothing is donated: a retried launch reads the same vector
            assert merged.memory_analysis().alias_size_in_bytes == 0
    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                            d_model=16, d_ff=32, max_len=256, causal=True,
                            dtype=jnp.bfloat16)
    tree = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    eng = E.ServeEngine(cfg, tree, slots=4, num_blocks=9, block_size=128,
                        prefill_chunk=128)
    assert eng._widths == E.PREFILL_WIDTHS  # as on the chip: all of them
    eng.submit(E.Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                         max_new_tokens=4, rng=np.zeros((2,), np.uint32)))
    eng.sched.admit(0.0)
    operands = eng._decode_operands([0])
    assert isinstance(operands[4], jax.Array)  # where the tokens are
    as_before = operands[:4] + (np.zeros((4,), np.int32),) + operands[5:]

    def lowered(args):
        args = jax.tree.map(lambda a: sds(a.shape, a.dtype), args)
        return eng.fns.decode.trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()

    assert lowered(operands) == lowered(as_before)
    assert "tpu_custom_call" in lowered(operands)


@pytest.mark.parametrize("cell", ["gpt2-xl", "lfm2-24b-a2b"])
def test_prefill_widths_compile_at_the_serving_cells_widths(v5e, as_on_tpu,
                                                            cell):
    """The prefill program of one, two and four rows at GPT-2 XL's and
    LFM2's published widths and their cells' geometry, a layer of each
    kind (the other two serving configurations: the two tests above and
    below), pool and state donated: every donated byte is aliased, the
    paged kernel and the cache writes are there, a launch's temporaries
    stay a small part of one pool leaf (or grow by no more from one row
    to four), and LFM2's convolution state is written a slice a row."""
    from distributed_tensorflow_guide_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
    )
    from distributed_tensorflow_guide_tpu.serve import engine as E
    from yardstick import harness, weights, weights_lfm2

    held = harness.load_json(harness.HERE / "configs" / f"{cell}.json")
    dep = held["deployment"]
    if cell == "gpt2-xl":
        held["n_layer"] = 2
        z = weights.sizes_of(held)
        cfg = TransformerConfig(
            vocab_size=z["vocab"], num_layers=z["L"], num_heads=z["h"],
            d_model=z["d"], d_ff=z["ff"], max_len=z["positions"],
            causal=True, dtype=jnp.dtype(dep["compute_dtype"]))
        params = jax.eval_shape(
            Transformer(cfg).init, jax.random.PRNGKey(0),
            jnp.zeros((1, 8), jnp.int32))["params"]
    else:
        held.update(layer_types=["conv", "full_attention"],
                    num_dense_layers=1)
        z = weights_lfm2.sizes_of(held)
        cfg = TransformerConfig(
            vocab_size=z["vocab"], num_layers=z["L"], num_heads=z["h"],
            d_model=z["d"], d_ff=z["ff"], max_len=z["positions"],
            causal=True, dtype=jnp.dtype(dep["compute_dtype"]),
            layers=z["layers"], norm="rmsnorm", norm_eps=z["eps"],
            ffn_gate="silu", rope_theta=z["theta"], num_kv_heads=z["kv"],
            qk_norm=True, conv_kernel=z["taps"], routed_experts=z["E"],
            routed_top_k=z["k"], routed_d_ff=z["eff"])
        assert [m for m, _ in z["layers"]] == ["short_conv", "attention"]
        params = jax.eval_shape(lambda: weights_lfm2.flax_tree(1, z))
    slots, chunk = dep["slots"], dep["prefill_chunk"]
    fns = E.build_step_fns(cfg, slots=slots, num_blocks=dep["num_blocks"],
                           block_size=dep["block_size"], prefill_chunk=chunk)
    assert fns.donates_pool
    pool = E.paged_cache_shapes(fns.cfg, slots)
    state = (E._serving_shapes(fns.cfg, slots)["state"],
             ) if fns.patterned else ()
    donated = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves((pool, state)))
    pool_leaf = max(a.size * a.dtype.itemsize for a in jax.tree.leaves(pool))
    one = SingleDeviceSharding(v5e[0])
    temps = []
    for rows in E.PREFILL_WIDTHS:
        compiled = compile_for(
            one, fns.prefill, params, pool, *state,
            *prefill_operands(rows, fns.n_blk, chunk),
            *((sds((rows,), jnp.int32),) if fns.patterned else ()))
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= 3  # two writes, the kernel
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == donated
        temps.append(mem.temp_size_in_bytes)
        for leaf in jax.tree.leaves(state):
            moved = entry_results(text, leaf.shape)
            assert len(moved) == rows and all(
                "dynamic-update-slice" in m or m.endswith(" fusion")
                for m in moved), moved
    # GPT-2 XL's float32 tree is converted at each use (ROADMAP S9: 331 MB
    # for these two layers and the head); what the rows add is small
    assert temps[-1] - temps[0] < pool_leaf // 8, temps
    if fns.patterned:
        assert max(temps) < pool_leaf // 8, temps
    assert not FA.fallback_stats()


# ---- whole programs, at chip_smoke.py's sizes -------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("chips", [1, 4])
def test_gpt2_train_step_compiles(v5e, as_on_tpu, chips):
    import optax
    from flax.training import train_state

    from distributed_tensorflow_guide_tpu.core.mesh import (
        MeshSpec,
        build_mesh,
    )
    from distributed_tensorflow_guide_tpu.models.transformer import (
        Transformer,
        gpt2_124m,
        make_lm_loss_fn,
    )
    from distributed_tensorflow_guide_tpu.parallel.data_parallel import (
        DataParallel,
    )

    cfg = gpt2_124m(dtype=jnp.bfloat16)
    model = Transformer(cfg)
    mesh = build_mesh(MeshSpec(data=-1), devices=v5e[:chips])
    dp = DataParallel(mesh)
    state = jax.eval_shape(
        lambda key: train_state.TrainState.create(
            apply_fn=model.apply, tx=optax.adamw(6e-4),
            params=model.init(
                key, jnp.zeros((1, cfg.max_len), jnp.int32))["params"]),
        jax.random.PRNGKey(0))
    step = dp.make_train_step(make_lm_loss_fn(model))
    place = lambda tree, spec: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=NamedSharding(mesh, spec)),
        tree)
    batch = {"tokens": sds((8 * chips, cfg.max_len), jnp.int32)}
    compiled = (step.trace(place(state, P()), place(batch, P("data")))
                .lower(lowering_platforms=("tpu",)).compile())
    assert_mosaic(compiled)
    # fits one chip's 16 GB with room for the serving pool that follows
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 8 << 30
    assert not FA.fallback_stats()


@pytest.mark.slow
def test_serve_step_pair_compiles(v5e, as_on_tpu):
    from distributed_tensorflow_guide_tpu.models.transformer import (
        Transformer,
        gpt2_124m,
    )
    from distributed_tensorflow_guide_tpu.serve.engine import (
        build_step_fns,
        paged_cache_shapes,
    )

    cfg = gpt2_124m(dtype=jnp.bfloat16)
    slots, block_size, chunk = 8, 16, 128
    num_blocks = slots * (cfg.max_len // block_size) + 1
    fns = build_step_fns(cfg, slots=slots, num_blocks=num_blocks,
                         block_size=block_size, prefill_chunk=chunk)
    assert fns.donates_pool
    params = jax.eval_shape(
        Transformer(cfg).init, jax.random.PRNGKey(0),
        jnp.zeros((1, cfg.max_len), jnp.int32))["params"]
    pool = paged_cache_shapes(fns.cfg, slots)
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    one = SingleDeviceSharding(v5e[0])
    decode = compile_for(
        one, fns.decode, params, pool, i32(slots, fns.n_blk), i32(slots),
        i32(slots), sds((slots, 2), jnp.uint32))
    prefill = compile_for(one, fns.prefill, params, pool,
                          *prefill_operands(1, fns.n_blk, chunk))
    assert_mosaic(decode)
    assert_mosaic(prefill)  # a 128-token chunk still takes the kernel
    assert not FA.fallback_stats()


def test_hybrid_decoder_step_pair_compiles_at_the_published_widths(
        v5e, as_on_tpu):
    """Both step programs of one layer of each kind of the
    Phi-4-mini-flash cell (Mamba-1, window attention, Mamba-1 handing its
    memory on, full attention, a gated memory unit, cross-attention) at the
    published widths and the cell's geometry (64 slots, 4,096 blocks of
    128, rings of 640 positions, a 128-token chunk), pool and state
    donated: every donated byte is aliased; the paged kernel runs once in
    each attention layer (heads in pairs: 10 pool heads of 128, four query
    heads each) and the paged write twice where a layer has keys of its
    own, so the cross layer writes nothing; and a launch's temporaries are
    a small part of ONE 105 MB ring leaf, so no leaf, nor the 1 GB
    embedding under the tied head, is copied."""
    from distributed_tensorflow_guide_tpu.models.transformer import (
        TransformerConfig,
    )
    from distributed_tensorflow_guide_tpu.serve import engine as E
    from yardstick import harness, weights_phi4flash

    held = harness.load_json(
        harness.HERE / "configs" / "phi-4-mini-flash-reasoning.json")
    held["num_hidden_layers"] = 6
    held["assumed"]["layout"].update(memory_layer=2, full_layer=3)
    z = weights_phi4flash.sizes_of(held)
    assert [m for m, _ in z["layers"]] == [
        "mamba1", "window_attention", "mamba1", "attention", "gmu",
        "cross_attention"]
    dep = held["deployment"]
    slots, chunk = dep["slots"], dep["prefill_chunk"]
    cfg = TransformerConfig(
        vocab_size=z["vocab"], num_layers=z["L"], num_heads=z["h"],
        d_model=z["d"], d_ff=z["ff"], max_len=z["positions"], causal=True,
        dtype=jnp.bfloat16, layers=z["layers"], norm="layernorm",
        norm_eps=z["eps"], ffn_gate="silu", positions="none",
        num_kv_heads=z["kv"], conv_kernel=z["taps"], ssm_inner=z["inner"],
        ssm_state=z["N"], ssm_dt_rank=z["R"], window=z["window"],
        differential=True, attn_bias=True, tie_embeddings=True)
    fns = E.build_step_fns(cfg, slots=slots, num_blocks=dep["num_blocks"],
                           block_size=dep["block_size"], prefill_chunk=chunk)
    assert fns.cfg.window_ring == 640
    params = jax.eval_shape(lambda: weights_phi4flash.flax_tree(1, z))
    pool = E.paged_cache_shapes(fns.cfg, slots)
    state = E._serving_shapes(fns.cfg, slots)["state"]
    pair_heads = (z["kv"] // 2, 2 * z["hd"], dep["block_size"])
    assert {k: v.shape for k, v in pool["block_3"]["attn"].items()} == {
        "cached_key": (dep["num_blocks"],) + pair_heads,
        "cached_value": (dep["num_blocks"],) + pair_heads}
    ring_leaf = (slots * 5 + 1,) + pair_heads
    assert {k: (v.shape, v.dtype) for k, v in
            state["block_1"]["attn"].items()} == {
                "win_key": (ring_leaf, jnp.bfloat16),
                "win_value": (ring_leaf, jnp.bfloat16)}
    assert {k: (v.shape, v.dtype) for k, v in
            state["block_0"]["ssm"].items()} == {
                "ssm": ((slots, 5120, 16), jnp.float32),
                "conv": ((slots, 3, 5120), jnp.bfloat16)}
    donated = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves((pool, state)))
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    one = SingleDeviceSharding(v5e[0])
    decode = compile_for(
        one, fns.decode, params, pool, state, i32(slots, fns.n_blk),
        i32(slots), i32(slots), sds((slots, 2), jnp.uint32))
    prefills = [compile_for(one, fns.prefill, params, pool, state,
                            *prefill_operands(rows, fns.n_blk, chunk),
                            i32(rows))
                for rows in E.PREFILL_WIDTHS]
    ring_bytes = 2 * int(np.prod(ring_leaf))
    for compiled in (decode, *prefills):
        text = compiled.as_text()
        # window: two writes and a read; full: two writes and a read;
        # cross: a read
        assert text.count("tpu_custom_call") == 7
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == donated
        assert mem.temp_size_in_bytes < ring_bytes
    assert decode.memory_analysis().temp_size_in_bytes < ring_bytes // 4
    assert not FA.fallback_stats()
