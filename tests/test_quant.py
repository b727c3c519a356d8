"""Quantization across the stack (round 19): the ops/quant primitives,
the weight-only decode path, the int8 training preset, and the
int8-compressed gradient collectives.

The load-bearing pins:

* the FUSED dequant never materializes a scaled f32 kernel copy — no
  kernel-shaped f32 multiply exists anywhere in the trace, and the cost
  interpreter charges the matmul's kernel read at the STORED width
  (narrow-origin accounting), so the byte diet is real, not cosmetic;
* int4 pack/unpack is a bitwise round trip over the whole nibble grid;
* the wq8 engine reproduces its own one-shot oracle bitwise AND the f32
  greedy stream exactly at the small geometry (the accuracy pin — int4
  is lossier and pins a logit tolerance instead);
* ``int8_ste_dot`` really contracts int8 x int8 -> int32 and its VJP is
  bit-identical to the unquantized matmul's (straight-through);
* compressed collectives move 1/4 the float bytes plus a 4-byte scale
  and stay inside the shared-scale error bound.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax.training import train_state
from jax import shard_map

import distributed_tensorflow_guide_tpu.collectives as cc
from distributed_tensorflow_guide_tpu.analysis import cost as cost_mod
from distributed_tensorflow_guide_tpu.analysis import lint
from distributed_tensorflow_guide_tpu.analysis import rules as rules_mod
from distributed_tensorflow_guide_tpu.analysis import walker
from distributed_tensorflow_guide_tpu.analysis.contracts import (
    ProgramContract,
)
from distributed_tensorflow_guide_tpu.core import precision
from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec
from distributed_tensorflow_guide_tpu.models.generation import (
    decode_cache_bytes_per_step,
    decode_hbm_bytes_per_step,
    make_generate_fn,
)
from distributed_tensorflow_guide_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
)
from distributed_tensorflow_guide_tpu.ops import quant
from distributed_tensorflow_guide_tpu.parallel.data_parallel import (
    DataParallel,
)
from distributed_tensorflow_guide_tpu.parallel.multislice import (
    MultiSliceLocalSGD,
    two_tier_mesh,
)
from jax.sharding import PartitionSpec as P

CFG = TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                        d_model=16, d_ff=32, max_len=64, causal=True,
                        dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return Transformer(CFG).init(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))["params"]


# ---- the storage-side primitives --------------------------------------------


@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_roundtrip_error_bound(bits):
    """Round-to-nearest on a symmetric per-column grid: every element of
    the dequantized kernel is within scale/2 of the original, and an
    all-zero column maps to scale 1 (never 0/0) and exact zeros."""
    rng = np.random.RandomState(0)
    w = rng.randn(32, 8).astype(np.float32)
    w[:, 3] = 0.0
    q, scale = quant.quantize_channelwise(jnp.asarray(w), bits=bits)
    assert q.dtype == jnp.int8 and scale.shape == (8,)
    assert int(jnp.max(jnp.abs(q))) <= quant.QMAX[bits]
    back = np.asarray(quant.dequantize_channelwise(q, scale))
    assert np.all(np.abs(back - w) <= np.asarray(scale)[None, :] / 2 + 1e-7)
    assert float(scale[3]) == 1.0
    assert np.all(back[:, 3] == 0.0)


def test_pack_unpack_int4_bitwise():
    """The whole [-8, 7] nibble grid survives pack -> unpack bit-for-bit
    (quantize only emits [-7, 7], but the packing layer must be exact on
    the full two's-complement range), and odd leading axes are refused."""
    grid = jnp.asarray(np.arange(-8, 8, dtype=np.int8).reshape(16, 1))
    assert np.array_equal(np.asarray(quant.unpack_int4(quant.pack_int4(grid))),
                          np.asarray(grid))
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randint(-7, 8, (64, 5)).astype(np.int8))
    packed = quant.pack_int4(q)
    assert packed.shape == (32, 5) and packed.dtype == jnp.uint8
    assert np.array_equal(np.asarray(quant.unpack_int4(packed)),
                          np.asarray(q))
    with pytest.raises(ValueError, match="even leading axis"):
        quant.pack_int4(q[:63])


@pytest.mark.parametrize("bits", [8, 4])
def test_wq_matmul_matches_unfused_oracle(bits):
    """(x @ q) * s == x @ (q * s): the scale is constant along the
    contracted axis so the fused form is the same algebra — parity with
    the materializing reference stays at float-rounding level."""
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(4, 64).astype(np.float32))
    w = jnp.asarray(rng.randn(64, 32).astype(np.float32))
    q, scale = quant.quantize_channelwise(w, bits=bits)
    stored = quant.pack_int4(q) if bits == 4 else q
    got = quant.wq_matmul(x, stored, scale, bits=bits)
    ref = x @ quant.dequantize_channelwise(q, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def _kernel_shaped_f32_muls(jaxpr, shape):
    return [e for e in walker.walk(jaxpr)
            for v in e.outvars
            if e.primitive.name == "mul"
            and tuple(v.aval.shape) == shape
            and v.aval.dtype == jnp.float32]


def test_fused_dequant_never_materializes_scaled_kernel():
    """The structural half of the fusion promise: the scale lands on the
    OUTPUT columns, so no f32 multiply anywhere in the trace produces a
    kernel-shaped value (the unfused reference is the positive control —
    it produces exactly that). The byte half: the cost interpreter's
    narrow-origin accounting charges the fused matmul's kernel read at
    int8 width, 3 bytes/elem less than the unfused program pays."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(4, 64).astype(np.float32))
    q, scale = quant.quantize_channelwise(
        jnp.asarray(rng.randn(64, 32).astype(np.float32)))

    fused = jax.make_jaxpr(lambda x, q, s: quant.wq_matmul(x, q, s))(
        x, q, scale)
    unfused = jax.make_jaxpr(
        lambda x, q, s: x @ quant.dequantize_channelwise(q, s))(
        x, q, scale)
    assert not _kernel_shaped_f32_muls(fused.jaxpr, (64, 32))
    assert len(_kernel_shaped_f32_muls(unfused.jaxpr, (64, 32))) == 1

    def _read(jx):
        traced = rules_mod.TracedProgram(
            name="wq", jaxpr=jx,
            arg_leaf_avals=[[jax.ShapeDtypeStruct(a.shape, a.dtype)]
                            for a in (x, q, scale)])
        contract = ProgramContract(name="wq", build=lambda: None)
        return cost_mod.program_cost(traced, contract).hbm_bytes_read

    assert _read(unfused) - _read(fused) == 3 * 64 * 32


# ---- quantize_params + the decode roofline ----------------------------------


def test_quantize_params_structure_and_pure(params):
    """Every projection kernel becomes {qkernel, scale} (the layout
    WeightQuantDense consumes), biases and LayerNorms ride through, and
    the f32 source tree is untouched (pure function)."""
    before = jax.tree.leaves(params)
    qp = quant.quantize_params(params, bits=8)
    for a, b in zip(before, jax.tree.leaves(params)):
        assert a is b
    found = 0

    def walk(node):
        nonlocal found
        if not isinstance(node, dict):
            return
        for name, child in node.items():
            if name in quant.WQ_PROJECTIONS and isinstance(child, dict) \
                    and "qkernel" in child:
                found += 1
                assert "kernel" not in child
                assert child["qkernel"].dtype == jnp.int8
                assert child["scale"].dtype == jnp.float32
            else:
                walk(child)

    walk(qp)
    # qkv/proj/up/down per layer x 2 layers + lm_head
    assert found == 4 * CFG.num_layers + 1


@pytest.mark.parametrize("bits,lo,hi", [(8, 2.5, 4.5), (4, 4.0, 8.5)])
def test_decode_roofline_params_term_shrinks(params, bits, lo, hi):
    """decode_hbm_bytes_per_step is leaf-driven, so handing it the
    quantized tree shrinks the params term toward ~4x (int8) / ~8x
    (packed int4). At this tiny d_out the per-column f32 scales and the
    untouched bias/LayerNorm leaves dilute the ratio well below the pure
    storage factor (the bench at GPT-2 geometry lands ~3.8x/~7.4x),
    hence the wide bands."""
    cfg_q = dataclasses.replace(
        CFG, weight_dtype="int8" if bits == 8 else "int4")
    qp = quant.quantize_params(params, bits=bits)
    cache = decode_cache_bytes_per_step(CFG, 1)
    full = decode_hbm_bytes_per_step(CFG, params, 1) - cache
    slim = decode_hbm_bytes_per_step(cfg_q, qp, 1) - cache
    assert lo <= full / slim <= hi


# ---- serving accuracy pins --------------------------------------------------


def _one_shot(cfg, prm, prompt, max_new, temp=0.0, top_k=None):
    gen = make_generate_fn(cfg, max_new_tokens=max_new, temperature=temp,
                           top_k=top_k)
    out = gen(prm, prompt[None], jax.random.PRNGKey(100))
    return np.asarray(out)[0, len(prompt):].tolist()


def test_wq8_engine_matches_one_shot_and_f32_greedy(params):
    """The weight-only int8 acceptance pin at the small geometry: the
    engine on the quantized config reproduces its own one-shot oracle
    bitwise (same lever code on both sides), and the greedy stream is
    token-identical to the f32 model's — int8 per-column error is far
    below the argmax margins here."""
    from distributed_tensorflow_guide_tpu.serve.engine import (
        Request,
        ServeEngine,
    )

    cfg_q = dataclasses.replace(CFG, weight_dtype="int8")
    qp = quant.quantize_params(params, bits=8)
    prompts = [np.array([3, 5, 7, 9, 11], np.int32),
               np.array([2, 4, 6, 8, 10, 12, 14, 16, 18], np.int32)]
    max_new = [8, 6]
    eng = ServeEngine(cfg_q, qp, temperature=0.0, top_k=None, slots=2,
                      num_blocks=17, block_size=8, prefill_chunk=8)
    for i, (p, mn) in enumerate(zip(prompts, max_new)):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=mn,
                           rng=jax.random.PRNGKey(100 + i)))
    eng.run()
    got = eng.completions()
    for i, (p, mn) in enumerate(zip(prompts, max_new)):
        assert got[i] == _one_shot(cfg_q, qp, p, mn), f"req {i} vs wq8"
        assert got[i] == _one_shot(CFG, params, p, mn), f"req {i} vs f32"
    eng.sched.pool.check_leaks()


def test_wq4_logits_within_tolerance(params):
    """int4 is lossy enough to flip low-margin greedy tokens (no bitwise
    stream guarantee — docs/serving.md says so out loud); the pin is a
    logit-space tolerance against the f32 oracle at this geometry."""
    cfg_q = dataclasses.replace(CFG, weight_dtype="int4")
    qp = quant.quantize_params(params, bits=4)
    x = jnp.asarray(np.array([[3, 5, 7, 9, 11, 2, 4, 6]], np.int32))
    lf = Transformer(CFG).apply({"params": params}, x)
    lq = Transformer(cfg_q).apply({"params": qp}, x)
    assert float(jnp.max(jnp.abs(lf - lq))) < 0.05


# ---- AQT-style int8 training matmuls ----------------------------------------


def test_int8_ste_dot_contracts_int8_and_grads_are_straight_through():
    """The trace really contains an int8 x int8 -> int32 contraction (the
    MXU-native mode the rules gate legalizes), the forward stays within
    the two-operand quantization bound, and the VJP is bit-identical to
    the unquantized matmul's — the straight-through contract."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(4, 16).astype(np.float32))
    w = jnp.asarray(rng.randn(16, 8).astype(np.float32))
    jx = jax.make_jaxpr(quant.int8_ste_dot)(x, w)
    dots = [e for e in walker.walk(jx.jaxpr)
            if e.primitive.name == "dot_general"]
    assert [str(v.aval.dtype) for v in dots[0].invars] == ["int8", "int8"]
    assert str(dots[0].outvars[0].aval.dtype) == "int32"

    ref = x @ w
    rel = float(jnp.max(jnp.abs(quant.int8_ste_dot(x, w) - ref))
                / jnp.max(jnp.abs(ref)))
    assert rel < 0.05

    _, vjp_q = jax.vjp(quant.int8_ste_dot, x, w)
    _, vjp_f = jax.vjp(lambda a, b: a @ b, x, w)
    ct = jnp.asarray(rng.randn(4, 8).astype(np.float32))
    for got, want in zip(vjp_q(ct), vjp_f(ct)):
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_int8_policy_loss_parity_with_f32():
    """PRESETS["int8"] trains the tiny LM step-for-step against "f32" —
    same f32 masters, same everything except the projection contraction
    representation, so the loss curves track within a tight band."""
    small = dataclasses.replace(CFG, max_len=32)

    def train(cfg, steps=5):
        model = Transformer(cfg)
        prm = model.init(jax.random.PRNGKey(0),
                         jnp.zeros((2, 8), jnp.int32))["params"]
        tx = optax.adam(1e-2)
        opt = tx.init(prm)
        xs = np.random.RandomState(0).randint(
            0, cfg.vocab_size, (steps, 4, 8)).astype(np.int32)

        @jax.jit
        def step(prm, opt, x):
            def loss_fn(p):
                lp = jax.nn.log_softmax(
                    model.apply({"params": p}, x[:, :-1]), -1)
                return -jnp.mean(jnp.take_along_axis(
                    lp, x[:, 1:, None], -1))

            loss, g = jax.value_and_grad(loss_fn)(prm)
            up, opt = tx.update(g, opt, prm)
            return optax.apply_updates(prm, up), opt, loss

        out = []
        for x in xs:
            prm, opt, loss = step(prm, opt, x)
            out.append(float(loss))
        return out

    l_f32 = train(precision.PRESETS["f32"].apply_to_transformer(small))
    l_int8 = train(precision.PRESETS["int8"].apply_to_transformer(small))
    for a, b in zip(l_f32, l_int8):
        assert abs(a - b) / a < 5e-3


# ---- int8-compressed gradient collectives -----------------------------------


def test_int8_pmean_parity_bytes_and_passthrough(mesh8):
    """One shared-scale bucket over 8 devices: the mean lands within
    scale/2 of the exact pmean, the wire carries exactly 1 byte/elem of
    float payload plus the single 4-byte scale pmax, and integer leaves
    (and all-integer trees) never touch a collective."""
    rng = np.random.RandomState(5)
    tree = {"w": jnp.asarray(rng.randn(8, 16, 4).astype(np.float32)),
            "b": jnp.asarray(rng.randn(8, 4).astype(np.float32)),
            "count": jnp.arange(8, dtype=jnp.int32)}
    specs = {"w": P("data"), "b": P("data"), "count": P("data")}
    fn = jax.jit(shard_map(lambda t: quant.int8_pmean(t, "data"),
                           mesh=mesh8, in_specs=(specs,), out_specs=specs,
                           check_vma=False))
    with cc.trace_comm() as rec:
        jax.eval_shape(fn, tree)
    # per-device payload: (1,16,4)+(1,4) float elems in int8 + 4B scale
    assert dict(rec.bytes) == {"pmax[data]": 4, "psum[data]": 68}

    got = fn(tree)
    n = 8
    amax = float(max(jnp.max(jnp.abs(tree["w"])), jnp.max(jnp.abs(tree["b"]))))
    bound = amax / (127 // n) / 2 + 1e-7
    for key in ("w", "b"):
        ref = jnp.broadcast_to(jnp.mean(tree[key], axis=0, keepdims=True),
                               tree[key].shape)
        assert float(jnp.max(jnp.abs(got[key] - ref))) <= bound
    assert np.array_equal(np.asarray(got["count"]),
                          np.asarray(tree["count"]))

    ints = jax.jit(shard_map(lambda t: quant.int8_pmean(t, "data"),
                             mesh=mesh8, in_specs=({"count": P("data")},),
                             out_specs={"count": P("data")},
                             check_vma=False))
    with cc.trace_comm() as rec2:
        jax.eval_shape(ints, {"count": tree["count"]})
    assert dict(rec2.bytes) == {}


def _toy_state(dim=8, seed=5):
    rng = np.random.RandomState(seed)
    return train_state.TrainState.create(
        apply_fn=None,
        params={"w": jnp.asarray(rng.randn(dim, 1).astype(np.float32)
                                 * 0.1)},
        tx=optax.sgd(0.05))


def _toy_loss(params, batch):
    err = batch["x"] @ params["w"] - batch["y"]
    return jnp.mean(err ** 2), {}


def test_dp_compress_parity_and_wire_savings(mesh8):
    """compress="int8" on the bucketed backward: training tracks the
    uncompressed run (the gradients-tolerate-it bet, pinned), and the
    traced wire swaps the f32 grad pmean (4 bytes/elem) for an int8 psum
    (1 byte/elem) plus the 4-byte scale pmax side-channel — the metric
    pmean is identical on both sides."""
    dim = 8
    xs = np.random.RandomState(7).randn(64, dim).astype(np.float32)
    batch = {"x": xs, "y": (xs @ np.ones((dim, 1)) * 0.3).astype(np.float32)}
    dp_c = DataParallel(mesh8, overlap=True, bucket_bytes=64,
                        compress="int8")
    dp_p = DataParallel(mesh8, overlap=True, bucket_bytes=64)
    sc, sp = dp_c.replicate(_toy_state()), dp_p.replicate(_toy_state())
    step_c = dp_c.make_train_step(_toy_loss, donate=False)
    step_p = dp_p.make_train_step(_toy_loss, donate=False)
    for _ in range(10):
        sc, mc = step_c(sc, dp_c.shard_batch(batch))
        sp, mp = step_p(sp, dp_p.shard_batch(batch))
    assert float(mc["loss"]) == pytest.approx(float(mp["loss"]), rel=2e-2)
    assert float(jnp.max(jnp.abs(sc.params["w"] - sp.params["w"]))) < 5e-3

    def _traced(dp, state):
        # fresh wrappers: an already-called jitted step would hit the
        # jaxpr cache and skip the python body, recording nothing
        with cc.trace_comm() as rec:
            jax.eval_shape(dp.make_train_step(_toy_loss, donate=False),
                           state, dp.shard_batch(batch))
        return rec.bytes

    plain, comp = _traced(dp_p, sp), _traced(dp_c, sc)
    # one (dim, 1) f32 param -> one bucket; + the 4-byte loss pmean
    assert dict(plain) == {"pmean[data]": 4 * dim + 4}
    assert dict(comp) == {"psum[data]": dim,  # 1 byte/elem on the wire
                          "pmax[data]": 4,    # one bucket -> one scale
                          "pmean[data]": 4}


def test_multislice_compress_parity_and_traced_outer_bytes():
    """The DiLoCo-style outer lever: compressed outer sync tracks the
    uncompressed run, the closed form prices the int8 wire at P/4, and
    the traced DCN payloads reconcile with it exactly (scale pmaxes
    included — plain SGD has no float opt-state, so only the delta
    bucket fires one)."""
    from benchmarks.common import dp_allreduce_bytes, outer_sync_bytes

    mesh22 = two_tier_mesh(MeshSpec(), n_slices=2)
    dim = 8
    xs = np.random.RandomState(9).randn(64, dim).astype(np.float32)
    sb = {"x": xs.reshape(2, 32, dim),
          "y": (xs @ np.ones((dim, 1)) * 0.3).astype(
              np.float32).reshape(2, 32, 1)}
    ms_c = MultiSliceLocalSGD(mesh22, sync_period=2, compress="int8")
    ms_p = MultiSliceLocalSGD(mesh22, sync_period=2)
    s_c = ms_c.replicate(ms_c.init(_toy_state(dim)))
    s_p = ms_p.replicate(ms_p.init(_toy_state(dim)))
    step_c = ms_c.make_train_step(_toy_loss, donate=False)
    step_p = ms_p.make_train_step(_toy_loss, donate=False)
    for _ in range(5):
        s_c, m_c = step_c(s_c, ms_c.shard_batch(sb))
        s_p, m_p = step_p(s_p, ms_p.shard_batch(sb))
    assert float(m_c["loss"]) == pytest.approx(float(m_p["loss"]),
                                               rel=2e-2)
    assert float(jnp.max(jnp.abs(
        s_c.inner.params["w"] - s_p.inner.params["w"]))) < 5e-3

    float_bytes = ms_c.outer_float_bytes(s_c)
    modeled = outer_sync_bytes(float_bytes, 2, compress="int8")
    assert modeled == outer_sync_bytes(float_bytes, 2) / 4
    modeled += 1 * dp_allreduce_bytes(4, 2)  # delta scale pmax only
    with cc.trace_comm() as rec:
        jax.eval_shape(ms_c.make_train_step(_toy_loss, donate=False),
                       s_c, ms_c.shard_batch(sb))
    traced = sum(2.0 * b * (2 - 1) / 2 for key, b in rec.bytes.items()
                 if key.endswith("[dcn]"))
    assert traced == modeled


# ---- the rules gate for integer matmuls -------------------------------------


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _prec(contract):
    report = lint.run_contracts([contract])
    rep = report.programs[0]
    return next(r for r in rep.rules if r.rule == "precision")


def test_int_dot_requires_quantized_matmuls_opt_in():
    def _build():
        return jax.jit(quant.int8_ste_dot), (_sds((4, 16)), _sds((16, 8)))

    prec = _prec(ProgramContract(name="int_dot_no_optin", build=_build))
    assert prec.observed["int_matmuls"] == 1
    assert any("quantized_matmuls" in f.message for f in prec.findings)

    prec = _prec(ProgramContract(name="int_dot_optin", build=_build,
                                 quantized_matmuls=True))
    assert prec.observed["int_matmuls"] == 1
    assert not prec.findings


def test_quantized_dot_must_rescale_and_accumulate_int32():
    from jax import lax

    def _never_rescaled():
        def f(x):
            q = x.astype(jnp.int8)
            return lax.dot_general(
                q, q, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)

        return jax.jit(f), (_sds((16, 8)),)

    prec = _prec(ProgramContract(name="never_rescaled",
                                 build=_never_rescaled,
                                 quantized_matmuls=True))
    assert any("never rescaled" in f.message for f in prec.findings)

    def _int8_accum():
        def f(x):
            q = x.astype(jnp.int8)
            return lax.dot_general(
                q, q, dimension_numbers=(((0,), (0,)), ((), ()))
            ).astype(jnp.float32) * 0.5

        return jax.jit(f), (_sds((16, 8)),)

    prec = _prec(ProgramContract(name="int8_accum", build=_int8_accum,
                                 quantized_matmuls=True))
    assert any("accumulates in" in f.message for f in prec.findings)


# ---- autotune hermeticity for the compressed bucket key ---------------------


def test_compressed_bucket_key_cpu_defaults_only(isolated_autotune_table):
    """The compressed wire tunes under its own dtype key (np.int8) — and
    that key obeys the same CPU defaults-only contract as every other:
    no reads, no writes, no sweeps in tier-1."""
    import json
    import os
    from pathlib import Path

    from distributed_tensorflow_guide_tpu.ops import autotune

    path = Path(os.environ["DTG_AUTOTUNE_TABLE"])
    got = autotune.bucket_bytes_for(param_bytes=1 << 20, world=8,
                                    dtype=np.int8)
    assert got == autotune.DEFAULT_BUCKET_BYTES
    with pytest.raises(RuntimeError, match="defaults-only"):
        autotune.bucket_record(param_bytes=1 << 20, world=8,
                               dtype=np.int8, bucket_bytes=1 << 19)
    assert not path.exists() or json.loads(path.read_text() or "{}") == {}


@pytest.mark.parametrize("bits", [8, 4])
def test_wq_bank_matmul_matches_per_expert_wq_matmul(bits):
    """The expert-bank form (PR 19) is wq_matmul applied expert by
    expert — bitwise, since each expert's rows run the identical fused
    contraction. Also pins the widened-transient discipline: no f32
    tensor of the WHOLE bank's shape appears in the jaxpr (each
    expert's kernel widens alone)."""
    rng = np.random.RandomState(3)
    E, C, D, F = 4, 6, 16, 32
    x = jnp.asarray(rng.randn(E, C, D).astype(np.float32))
    bank = jnp.asarray(rng.randn(E, D, F).astype(np.float32))
    q, scale = jax.vmap(
        lambda k: quant.quantize_channelwise(k, bits=bits))(bank)
    stored = jax.vmap(quant.pack_int4)(q) if bits == 4 else q
    got = quant.wq_bank_matmul(x, stored, scale, bits=bits)
    assert got.shape == (E, C, F)
    for e in range(E):
        ref = quant.wq_matmul(x[e], stored[e], scale[e], bits=bits)
        assert np.array_equal(np.asarray(got[e]), np.asarray(ref)), e
    jaxpr = jax.make_jaxpr(
        lambda a, b, s: quant.wq_bank_matmul(a, b, s, bits=bits))(
        x, stored, scale)
    whole_bank = [v for eqn in walker.walk(jaxpr) for v in eqn.outvars
                  if tuple(v.aval.shape) == (E, D, F)
                  and v.aval.dtype == jnp.float32]
    assert not whole_bank, "dequantized bank materialized at full width"


def test_quantize_params_folds_expert_banks():
    """quantize_params recognizes 3-D (E, d_in, d_out) bank kernels
    under the WQ_BANKS names and emits per-expert qkernel+scale; the
    f32 router projection is exempt (routing is precision-sensitive)."""
    rng = np.random.RandomState(4)
    params = {
        "mlp": {
            "router": {"kernel": rng.randn(16, 4).astype(np.float32)},
            "w_in": {"kernel": rng.randn(4, 16, 32).astype(np.float32)},
            "w_out": {"kernel": rng.randn(4, 32, 16).astype(np.float32)},
        },
    }
    out = quant.quantize_params(params, bits=8)
    assert out["mlp"]["w_in"]["qkernel"].shape == (4, 16, 32)
    assert out["mlp"]["w_in"]["qkernel"].dtype == jnp.int8
    assert out["mlp"]["w_in"]["scale"].shape == (4, 32)
    assert out["mlp"]["w_out"]["scale"].shape == (4, 16)
    assert out["mlp"]["router"]["kernel"].dtype == jnp.float32
    # per-expert channelwise: bank slice e quantizes exactly like the
    # 2-D kernel it is
    q0, s0 = quant.quantize_channelwise(
        jnp.asarray(params["mlp"]["w_in"]["kernel"][0]), bits=8)
    assert np.array_equal(np.asarray(out["mlp"]["w_in"]["qkernel"][0]),
                          np.asarray(q0))
    assert np.array_equal(np.asarray(out["mlp"]["w_in"]["scale"][0]),
                          np.asarray(s0))
