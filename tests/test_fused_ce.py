"""Fused cross-entropy (ops/fused_ce.py) + precision policy (core/precision).

The load-bearing tests are the numerical pins the round-8 issue names:
fused CE must match the naive log_softmax path — loss AND grads — at tp=1
and under vocab parallelism; the fused backward must never materialize a
full (N, V) f32 intermediate (jaxpr-walked, with the naive path as the
positive control for the detector); and the chunk-resolution layer must
stay CPU-hermetic (no autotune table I/O on the cpu backend — PR-2's
hermeticity rule)."""

import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from distributed_tensorflow_guide_tpu.core import precision
from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh
from distributed_tensorflow_guide_tpu.ops import autotune
from distributed_tensorflow_guide_tpu.ops import fused_ce as fce
from distributed_tensorflow_guide_tpu.analysis.walker import (
    max_f32_elems_with_vocab_dim as _max_f32_elems_with_vocab_dim,
)


@pytest.fixture(autouse=True)
def _isolated_table(isolated_autotune_table):
    """Shared isolation (tests/conftest.py): empty in-memory table, tmp
    table file — nothing leaks between tests or to the user cache."""
    yield


def _case(n=24, d=16, v=50, seed=0, dtype=jnp.float32):
    r = np.random.RandomState(seed)
    x = jnp.asarray(r.randn(n, d), jnp.float32).astype(dtype)
    kernel = jnp.asarray(r.randn(d, v) * 0.2, jnp.float32)
    targets = jnp.asarray(r.randint(0, v, (n,)), np.int32)
    return x, kernel, targets


def _naive(x, kernel, targets, reduction="mean"):
    logp = jax.nn.log_softmax(x.astype(jnp.float32) @ kernel)
    ll = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    return -jnp.sum(ll) if reduction == "sum" else -jnp.mean(ll)


# ---- numerical parity -------------------------------------------------------


@pytest.mark.parametrize("chunk", [7, 16, 50, 64])
def test_fused_matches_naive_loss_and_grads(chunk):
    """Loss + BOTH grads match the naive path at every chunking regime:
    ragged tail (7, 16), exactly one chunk (50 = V), chunk > V (clipped)."""
    x, kernel, targets = _case()
    l0, (dx0, dw0) = jax.value_and_grad(
        lambda a, b: _naive(a, b, targets), argnums=(0, 1))(x, kernel)
    l1, (dx1, dw1) = jax.value_and_grad(
        lambda a, b: fce.fused_cross_entropy(a, b, targets, chunk=chunk),
        argnums=(0, 1))(x, kernel)
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    np.testing.assert_allclose(dx0, dx1, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(dw0, dw1, rtol=1e-5, atol=1e-7)


def test_fused_sum_reduction_and_leading_shape():
    """reduction="sum" and (B, S, D)-shaped inputs (the call-site shape)."""
    x, kernel, targets = _case(n=24)
    want = float(_naive(x, kernel, targets, reduction="sum"))
    got = fce.fused_cross_entropy(
        x.reshape(4, 6, -1), kernel, targets.reshape(4, 6),
        chunk=16, reduction="sum")
    np.testing.assert_allclose(want, float(got), rtol=1e-6)


def test_fused_next_token_shift_matches_naive():
    """fused_next_token_loss applies the :-1 / 1: shift the logits-side
    call sites apply — pinned against the explicit spelling."""
    r = np.random.RandomState(1)
    B, S, D, V = 2, 9, 8, 40
    x = jnp.asarray(r.randn(B, S, D), jnp.float32)
    kernel = jnp.asarray(r.randn(D, V) * 0.2, jnp.float32)
    toks = jnp.asarray(r.randint(0, V, (B, S)), np.int32)
    want = _naive(x[:, :-1].reshape(-1, D), kernel,
                  toks[:, 1:].reshape(-1))
    got = fce.fused_next_token_loss(x, kernel, toks, chunk=16)
    np.testing.assert_allclose(float(want), float(got), rtol=1e-6)


def test_fused_bf16_runs_and_keeps_f32_loss():
    """bf16 activations: matmuls in bf16, loss f32, dx back in bf16,
    dW in the kernel's dtype — the precision-policy accumulation
    contract (coarse tolerance: the bf16 matmul IS the diet)."""
    x, kernel, targets = _case(dtype=jnp.bfloat16)
    loss, (dx, dw) = jax.value_and_grad(
        lambda a, b: fce.fused_cross_entropy(a, b, targets, chunk=16),
        argnums=(0, 1))(x, kernel)
    assert loss.dtype == jnp.float32
    assert dx.dtype == jnp.bfloat16 and dw.dtype == kernel.dtype
    l0 = _naive(x.astype(jnp.float32), kernel, targets)
    np.testing.assert_allclose(float(l0), float(loss), rtol=2e-2)


def test_fused_vocab_parallel_matches_naive():
    """The vocab-parallel variant (axis="model"): each device holds a V/8
    kernel shard, the collective triple assembles the loss, the bwd psums
    dx — values AND grads must match the unsharded naive oracle."""
    mesh = build_mesh(MeshSpec(data=1, model=8))
    x, kernel, targets = _case(n=16, d=8, v=64, seed=2)

    def body(x, kernel, targets):
        def loss(x, k):
            return fce.fused_cross_entropy(
                x, k, targets, chunk=4, axis="model")

        l, (dx, dw) = jax.value_and_grad(loss, argnums=(0, 1))(x, kernel)
        return l, dx, dw

    f = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, "model"), P()),
        out_specs=(P(), P(), P(None, "model")),
        check_vma=False,
    ))
    l, dx, dw = f(x, kernel, targets)
    l0, (dx0, dw0) = jax.value_and_grad(
        lambda a, b: _naive(a, b, targets), argnums=(0, 1))(x, kernel)
    np.testing.assert_allclose(float(l0), float(l), rtol=1e-6)
    np.testing.assert_allclose(dx0, dx, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(dw0, dw, rtol=1e-5, atol=1e-7)


def test_fused_rejects_bad_args():
    x, kernel, targets = _case()
    with pytest.raises(ValueError, match="reduction"):
        fce.fused_cross_entropy(x, kernel, targets, chunk=8,
                                reduction="max")
    with pytest.raises(ValueError, match="targets shape"):
        fce.fused_cross_entropy(x, kernel, targets[:-1], chunk=8)
    with pytest.raises(ValueError, match="kernel"):
        fce.fused_cross_entropy(x, kernel.T, targets, chunk=8)


# ---- the no-full-logits pin (analysis.walker, ex tests/pin_utils.py) --------


def test_fused_bwd_never_materializes_full_logits():
    """The acceptance-criteria pin: the fused fwd+bwd jaxpr contains NO
    (N, V) f32 intermediate — the largest vocab-dim tensor is one
    (N, chunk) tile. The naive path is the positive control proving the
    detector sees full logits when they exist."""
    n, d, v, chunk = 48, 8, 64, 16
    x, kernel, targets = _case(n=n, d=d, v=v)

    naive_jaxpr = jax.make_jaxpr(jax.grad(
        lambda a, b: _naive(a, b, targets), argnums=(0, 1)))(x, kernel)
    assert _max_f32_elems_with_vocab_dim(naive_jaxpr, n, v) >= n * v

    fused_jaxpr = jax.make_jaxpr(jax.grad(
        lambda a, b: fce.fused_cross_entropy(a, b, targets, chunk=chunk),
        argnums=(0, 1)))(x, kernel)
    assert _max_f32_elems_with_vocab_dim(fused_jaxpr, n, v) == 0
    # ...and the chunk tiles themselves stay at (n, chunk)
    assert _max_f32_elems_with_vocab_dim(fused_jaxpr, n, chunk) <= n * chunk


def test_pipeline_fused_bwd_never_materializes_full_logits():
    """Same pin END TO END: the whole compiled pipeline train step with
    fused_ce=True (chunk 16 < V) has no (mb·(S−1), V) f32 intermediate.
    The config's vocab (80) collides with no other model dimension, so a
    vocab-dim match in the jaxpr can only be a logits-family tensor; the
    fused_ce=False step is the positive control."""
    import optax

    from distributed_tensorflow_guide_tpu.models.transformer import (
        TransformerConfig,
    )
    from distributed_tensorflow_guide_tpu.parallel.pipeline import (
        PipelinedLM,
    )

    cfg = TransformerConfig(
        vocab_size=80, num_layers=2, num_heads=2, d_model=24, d_ff=48,
        max_len=16, causal=True, dtype=jnp.float32)
    mesh = build_mesh(MeshSpec(data=4, pipe=2))
    r = np.random.RandomState(0)
    tokens = r.randint(0, 80, (16, 16)).astype(np.int32)
    n = 2 * (cfg.max_len - 1)  # one microbatch's next-token positions

    def step_jaxpr(fused):
        # fully abstract: make_jaxpr over ShapeDtypeStructs — the pin is a
        # trace property, no device compute or compile needed
        pp = PipelinedLM(mesh, cfg, num_microbatches=2, fused_ce=fused,
                         ce_chunk=16)
        params = jax.eval_shape(pp.init_host_params, jax.random.PRNGKey(0))
        tx = optax.sgd(0.1)
        opt_state = jax.eval_shape(tx.init, params)
        step = pp.make_train_step(tx, params, donate=False)
        return jax.make_jaxpr(step)(opt_state, params, tokens)

    assert _max_f32_elems_with_vocab_dim(
        step_jaxpr(False), n, cfg.vocab_size) >= n * cfg.vocab_size
    assert _max_f32_elems_with_vocab_dim(
        step_jaxpr(True), n, cfg.vocab_size) == 0


# ---- chunk resolution: autotune table + CPU hermeticity ---------------------


def test_ce_chunk_cpu_is_defaults_only_no_table_io():
    """The tier-1 guard the issue names: on the cpu backend the fused-CE
    chunk layer neither reads nor writes the autotune table and refuses
    to sweep — a stray host table must not change what CI traces."""
    path = Path(os.environ["DTG_AUTOTUNE_TABLE"])
    seeded = {autotune._key(autotune.CE_KERNEL, 0, 0, 50304, 768,
                            "bfloat16", False, "cpu"): {"chunk": 1024}}
    path.write_text(json.dumps(seeded))

    got = autotune.ce_chunk_for(n=1024, d=768, v=50304, dtype=jnp.bfloat16)
    assert got == autotune.DEFAULT_CE_CHUNK  # file ignored on cpu
    with pytest.raises(RuntimeError, match="defaults-only"):
        autotune.ce_record(n=1024, d=768, v=50304, dtype=jnp.bfloat16,
                           chunk=2048)
    with pytest.raises(RuntimeError, match="defaults-only"):
        autotune.ensure_ce_tuned(n=1024, d=768, v=50304,
                                 dtype=jnp.bfloat16,
                                 measure=lambda c: 0.0)
    assert json.loads(path.read_text()) == seeded  # file untouched
    # ...and the fused loss itself resolves through the same defaults-only
    # path (no table read) — it must simply run
    x, kernel, targets = _case(v=50)
    float(fce.fused_cross_entropy(x, kernel, targets))


def test_ce_chunk_table_roundtrip_no_resweep():
    """Same key -> same chunk, sweep runs once, persists across a
    simulated restart; vocab-clipping guards stale entries."""
    calls = []

    def measure(chunk):
        calls.append(chunk)
        return 1.0 / chunk  # favors the widest chunk

    kw = dict(n=64, d=16, v=4096, dtype=jnp.float32, platform="tpu")
    first = autotune.ensure_ce_tuned(measure=measure, **kw)
    assert first == 2048  # widest candidate < v
    n_swept = len(calls)
    assert n_swept == len(autotune.ce_chunk_candidates(4096))

    again = autotune.ensure_ce_tuned(measure=measure, **kw)
    assert again == first and len(calls) == n_swept  # no re-sweep

    autotune.reset()  # "restart": reload from the persisted file
    assert autotune.ensure_ce_tuned(measure=measure, **kw) == first
    assert len(calls) == n_swept
    # the N-generic entry serves nearby batch sizes without a sweep
    assert autotune.ce_chunk_for(n=999, d=16, v=4096, dtype=jnp.float32,
                                 platform="tpu") == first
    # a different vocab misses back to the (clipped) default
    assert autotune.ce_chunk_for(n=64, d=16, v=512, dtype=jnp.float32,
                                 platform="tpu") == 512
    with pytest.raises(ValueError, match="invalid"):
        autotune.ce_record(n=64, d=16, v=512, dtype=jnp.float32,
                           chunk=1024, platform="tpu")


def test_resolve_fused_ce_policy():
    assert fce.resolve_fused_ce(True) is True
    assert fce.resolve_fused_ce(False) is False
    assert fce.resolve_fused_ce("on") is True
    assert fce.resolve_fused_ce("off") is False
    # auto: off on cpu (tier-1 traces stay byte-identical) ...
    assert fce.resolve_fused_ce("auto", vocab_size=50304) is False
    # ... on for TPU + chunkable vocab, off for degenerate vocabs
    assert fce.resolve_fused_ce("auto", vocab_size=50304,
                                platform="tpu") is True
    assert fce.resolve_fused_ce("auto", vocab_size=1024,
                                platform="tpu") is False
    with pytest.raises(ValueError, match="fused_ce"):
        fce.resolve_fused_ce("maybe")


# ---- loss-site wiring (flat LM + MoE) ---------------------------------------


def test_make_lm_loss_fn_fused_matches_naive():
    """The DP/FSDP call-site knob: make_lm_loss_fn(fused_ce=True) matches
    the naive loss and grads on the same params."""
    from distributed_tensorflow_guide_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
        make_lm_loss_fn,
    )

    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=2, d_model=16, d_ff=32,
        max_len=8, causal=True, dtype=jnp.float32)
    model = Transformer(cfg)
    r = np.random.RandomState(0)
    tokens = jnp.asarray(r.randint(0, 64, (2, 8)), np.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    batch = {"tokens": tokens}

    naive = make_lm_loss_fn(model, fused_ce=False)
    fused = make_lm_loss_fn(model, fused_ce=True, ce_chunk=16)
    (l0, m0), g0 = jax.value_and_grad(naive, has_aux=True)(params, batch)
    (l1, m1), g1 = jax.value_and_grad(fused, has_aux=True)(params, batch)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    np.testing.assert_allclose(float(m0["perplexity"]),
                               float(m1["perplexity"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-7)


def test_switch_lm_fused_matches_naive():
    """SwitchLM: one fused train step lands on the same loss and params
    as the naive path from identical init (the (se, n) psum assembly is
    shared, so the global mean cannot fork)."""
    import optax

    from distributed_tensorflow_guide_tpu.models.moe_lm import SwitchLM
    from distributed_tensorflow_guide_tpu.models.transformer import (
        TransformerConfig,
    )

    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=2, d_model=16, d_ff=32,
        max_len=8, causal=True, dtype=jnp.float32)
    mesh = build_mesh(MeshSpec(data=2, expert=4))
    r = np.random.RandomState(0)
    tokens = jnp.asarray(r.randint(0, 64, (8, 8)), np.int32)

    def run(fused):
        lm = SwitchLM(mesh, cfg, num_experts=4, fused_ce=fused,
                      ce_chunk=16)
        params = lm.init_params(jax.random.PRNGKey(0))
        tx = optax.sgd(0.1)
        opt_state = lm.init_opt_state(tx, params)
        step = lm.make_train_step(tx, params, donate=False)
        opt2, params2, m = step(opt_state, params, tokens)
        return float(m["loss"]), jax.tree.map(np.asarray, params2)

    l0, p0 = run(False)
    l1, p1 = run(True)
    np.testing.assert_allclose(l0, l1, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1), strict=True):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


# ---- precision policy (core/precision.py) -----------------------------------


def test_precision_presets_and_apply():
    from distributed_tensorflow_guide_tpu.models.transformer import (
        TransformerConfig,
    )

    pol = precision.resolve("bf16_remat_attn")
    assert pol.compute_dtype == jnp.bfloat16
    assert pol.param_dtype == jnp.float32
    assert pol.accum_dtype == jnp.float32
    assert pol.remat == "attention"
    cfg = pol.apply_to_transformer(TransformerConfig())
    assert cfg.dtype == jnp.bfloat16
    assert cfg.resolved_remat_mode == "attention"
    assert cfg.remat is False  # attention mode is NOT full-block remat

    cfg2 = precision.resolve("bf16_remat").apply_to_transformer(
        TransformerConfig())
    assert cfg2.remat is True and cfg2.resolved_remat_mode == "block"
    assert precision.resolve(None).name == "bf16"
    assert precision.resolve(pol) is pol
    assert precision.resolve("fp8").name == "fp8"  # round 21: now a preset
    with pytest.raises(ValueError, match="unknown precision"):
        precision.resolve("fp6")
    with pytest.raises(ValueError, match="remat"):
        precision.Policy("bad", remat="everything")


def test_remat_mode_attention_is_execution_plan_only():
    """remat_mode="attention" must change NOTHING numerically: same loss,
    same grads as no remat (it re-runs the identical attention ops in the
    backward) — and the param layout is unchanged."""
    import dataclasses

    from distributed_tensorflow_guide_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
        make_lm_loss_fn,
    )

    cfg = TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, d_model=16, d_ff=32,
        max_len=12, causal=True, dtype=jnp.float32)
    r = np.random.RandomState(0)
    tokens = jnp.asarray(r.randint(0, 64, (4, 12)), np.int32)
    params = Transformer(cfg).init(jax.random.PRNGKey(0), tokens)["params"]

    def run(mode):
        model = Transformer(dataclasses.replace(cfg, remat_mode=mode))
        loss_fn = make_lm_loss_fn(model, fused_ce=False)
        (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, {"tokens": tokens})
        return float(l), g

    l0, g0 = run("none")
    l1, g1 = run("attention")
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


def test_pipeline_precision_policy_threads_through():
    """PipelinedLM(precision=...) rewrites the config through the policy —
    activation dtype + remat mode — and the step still runs."""
    import optax

    from distributed_tensorflow_guide_tpu.parallel.pipeline import (
        PipelinedLM,
    )
    from tests.test_pipeline import CFG, _tokens

    mesh = build_mesh(MeshSpec(data=4, pipe=2))
    pp = PipelinedLM(mesh, CFG, num_microbatches=2, precision="f32")
    assert pp.cfg.dtype == jnp.float32
    assert pp.cfg.resolved_remat_mode == "none"

    pp2 = PipelinedLM(mesh, CFG, num_microbatches=2,
                      precision="bf16_remat_attn")
    assert pp2.cfg.dtype == jnp.bfloat16
    assert pp2.cfg.resolved_remat_mode == "attention"
    params = pp2.init_params(jax.random.PRNGKey(0))
    tx = optax.sgd(0.1)
    opt_state = pp2.init_opt_state(tx, params)
    step = pp2.make_train_step(tx, params, donate=False)
    _, _, m = step(opt_state, params, _tokens(16))
    assert np.isfinite(float(m["loss"]))
