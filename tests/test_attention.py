"""Blockwise/ring/Ulysses attention parity vs dense softmax attention."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh
from distributed_tensorflow_guide_tpu.ops.attention import (
    blockwise_attention,
    dense_attention,
)
from distributed_tensorflow_guide_tpu.parallel.sequence import (
    ring_attention,
    ulysses_attention,
)

B, S, H, D = 2, 64, 4, 16


def _qkv(seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, S, H, D).astype(np.float32), dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_size", [16, 64])
def test_blockwise_equals_dense(causal, block_size):
    q, k, v = _qkv()
    out_b = blockwise_attention(q, k, v, causal=causal, block_size=block_size)
    out_d = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_d),
                               rtol=1e-5, atol=1e-5)


def test_blockwise_bf16_close_to_dense_f32():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out_b = blockwise_attention(q, k, v, causal=True, block_size=16)
    out_d = dense_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True,
    )
    np.testing.assert_allclose(
        np.asarray(out_b, np.float32), np.asarray(out_d), rtol=0.05, atol=0.05
    )


def test_fully_masked_rows_return_zero():
    """A query row whose keys are ALL masked must return 0, not mean(V)."""
    from distributed_tensorflow_guide_tpu.ops.attention import (
        block_update,
        finalize,
        init_carry,
    )

    q, k, v = _qkv()
    m, l, o = init_carry(q.shape)
    mask = np.ones((1, 1, S, S), bool)
    mask[..., S // 2 :, :] = False  # second half attends nothing
    m, l, o = block_update(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        m, l, o, scale=0.25, mask=jnp.asarray(mask),
    )
    out = np.asarray(finalize(m, l, o))
    assert np.all(out[:, S // 2 :] == 0.0)
    assert np.any(out[:, : S // 2] != 0.0)


def _ctx_mesh(n):
    return build_mesh(MeshSpec(data=8 // n, context=n, model=1, pipe=1))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("n_ctx", [4, 8])
def test_ring_attention_equals_dense(causal, n_ctx):
    mesh = _ctx_mesh(n_ctx)
    q, k, v = _qkv()

    f = jax.jit(
        shard_map(
            functools.partial(ring_attention, causal=causal),
            mesh=mesh,
            in_specs=(P(None, "context"),) * 3,
            out_specs=P(None, "context"),
            check_vma=False,
        )
    )
    out_r = f(q, k, v)
    out_d = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_d),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_equals_dense(causal):
    mesh = _ctx_mesh(4)  # H=4 heads over 4-way context
    q, k, v = _qkv()
    f = jax.jit(
        shard_map(
            functools.partial(ulysses_attention, causal=causal),
            mesh=mesh,
            in_specs=(P(None, "context"),) * 3,
            out_specs=P(None, "context"),
            check_vma=False,
        )
    )
    out_u = f(q, k, v)
    out_d = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out_u), np.asarray(out_d),
                               rtol=1e-5, atol=1e-5)


def test_ring_attention_grads_match_dense():
    """Backward parity: ring attention is used in training."""
    mesh = _ctx_mesh(4)
    q, k, v = _qkv()

    sm = shard_map(
        functools.partial(ring_attention, causal=True),
        mesh=mesh,
        in_specs=(P(None, "context"),) * 3,
        out_specs=P(None, "context"),
        check_vma=False,
    )
    # scalarize OUTSIDE shard_map on the global output: the shard_map
    # transpose handles cotangent resharding, no manual psum needed
    g_ring = jax.jit(jax.grad(lambda q, k, v: jnp.sum(sm(q, k, v) ** 2)))(
        q, k, v
    )
    g_dense = jax.grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v, causal=True) ** 2)
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_dense),
                               rtol=1e-4, atol=1e-4)


def test_attn_impl_auto_resolution():
    import pytest

    from distributed_tensorflow_guide_tpu.models.transformer import (
        TransformerConfig,
        bert_base,
        gpt2_124m,
    )

    # causal long-context -> flash; everything else -> dense
    assert gpt2_124m().resolved_attn_impl == "flash"       # causal, 1024
    assert bert_base().resolved_attn_impl == "dense"       # bidirectional
    short = TransformerConfig(max_len=512, causal=True)
    assert short.resolved_attn_impl == "dense"
    assert gpt2_124m(attn_impl="dense").resolved_attn_impl == "dense"
    with pytest.raises(ValueError):
        TransformerConfig(attn_impl="bogus")


# ---- Pallas-fused ring attention (the survey's hard native part) ------------
# S_local = 128 per device so the carry kernel engages. The kernel is OPT-IN
# (impl="pallas"): the round-5 on-chip battery measured it at 0.157–0.487x
# of the XLA blockwise path at 1k–4k, so impl="auto" selects xla (pinned in
# tests/test_sp_comm.py); these tests keep the kernel path correct for the
# planned bisect.


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("n_ctx", [2, 4])
def test_ring_flash_equals_dense(causal, n_ctx):
    mesh = _ctx_mesh(n_ctx)
    rng = np.random.RandomState(1)
    s = 128 * n_ctx
    mk = lambda: jnp.asarray(rng.randn(1, s, 2, 16), jnp.float32)
    q, k, v = mk(), mk(), mk()

    f = jax.jit(
        shard_map(
            functools.partial(ring_attention, causal=causal, impl="pallas"),
            mesh=mesh,
            in_specs=(P(None, "context"),) * 3,
            out_specs=P(None, "context"),
            check_vma=False,
        )
    )
    out_r = f(q, k, v)
    out_d = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_d),
                               rtol=2e-5, atol=2e-5)


def test_ring_flash_grads_match_dense():
    n_ctx = 4
    mesh = _ctx_mesh(n_ctx)
    rng = np.random.RandomState(2)
    s = 128 * n_ctx
    mk = lambda: jnp.asarray(rng.randn(1, s, 2, 16), jnp.float32)
    q, k, v = mk(), mk(), mk()

    sm = shard_map(
        functools.partial(ring_attention, causal=True, impl="pallas"),
        mesh=mesh,
        in_specs=(P(None, "context"),) * 3,
        out_specs=P(None, "context"),
        check_vma=False,
    )
    g_ring = jax.jit(jax.grad(lambda q, k, v: jnp.sum(sm(q, k, v) ** 2)))(
        q, k, v
    )
    g_dense = jax.grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v, causal=True) ** 2)
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_dense),
                               rtol=1e-4, atol=1e-4)


def test_ring_flash_matches_ring_xla():
    """The two ring implementations are interchangeable (same public
    contract), including at bf16."""
    n_ctx = 2
    mesh = _ctx_mesh(n_ctx)
    rng = np.random.RandomState(3)
    s = 128 * n_ctx
    mk = lambda: jnp.asarray(rng.randn(2, s, 2, 16), jnp.bfloat16)
    q, k, v = mk(), mk(), mk()

    def run(impl):
        f = jax.jit(
            shard_map(
                functools.partial(ring_attention, causal=True, impl=impl),
                mesh=mesh,
                in_specs=(P(None, "context"),) * 3,
                out_specs=P(None, "context"),
                check_vma=False,
            )
        )
        return np.asarray(f(q, k, v), np.float32)

    np.testing.assert_allclose(run("pallas"), run("xla"), rtol=2e-2,
                               atol=2e-2)


def test_ulysses_flash_core_equals_dense():
    """Ulysses with the flash core (global seq 256 fits the kernel blocks)
    must match dense — forward and gradients."""
    mesh = _ctx_mesh(4)  # H=4 heads over 4-way context
    rng = np.random.RandomState(4)
    mk = lambda: jnp.asarray(rng.randn(2, 256, 4, 16), jnp.float32)
    q, k, v = mk(), mk(), mk()

    def run(impl):
        sm = shard_map(
            functools.partial(ulysses_attention, causal=True, impl=impl),
            mesh=mesh,
            in_specs=(P(None, "context"),) * 3,
            out_specs=P(None, "context"),
            check_vma=False,
        )
        out = jax.jit(sm)(q, k, v)
        g = jax.jit(jax.grad(lambda q: jnp.sum(sm(q, k, v) ** 2)))(q)
        return np.asarray(out), np.asarray(g)

    out_f, g_f = run("flash")
    out_d, g_d = run("dense")
    np.testing.assert_allclose(out_f, out_d, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(g_f, g_d, rtol=1e-4, atol=1e-4)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out_f, np.asarray(ref), rtol=1e-4, atol=1e-4)
