import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

import distributed_tensorflow_guide_tpu.collectives as cc


def test_psum_matches_sum(mesh8):
    x = jnp.arange(8.0)

    f = shard_map(
        lambda v: cc.psum(v, "data"),
        mesh=mesh8,
        in_specs=P("data"),
        out_specs=P("data"),
    )
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, x.sum()))


def test_pmean(mesh8):
    x = jnp.arange(8.0)
    f = shard_map(
        lambda v: cc.pmean(v, "data"),
        mesh=mesh8,
        in_specs=P("data"),
        out_specs=P("data"),
    )
    np.testing.assert_allclose(np.asarray(f(x)), np.full(8, x.mean()))


def test_all_gather_tiled(mesh8):
    x = jnp.arange(16.0).reshape(8, 2)
    f = shard_map(
        lambda v: cc.all_gather(v, "data", tiled=True),
        mesh=mesh8,
        in_specs=P("data", None),
        out_specs=P(None, None),
        check_vma=False,  # all_gather output is replicated; checker can't infer it
    )
    np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x))


def test_reduce_scatter_then_gather_is_allreduce(mesh8):
    x = jnp.arange(64.0).reshape(8, 8)

    def body(v):  # v: (1, 8) per device
        rs = cc.reduce_scatter(v, "data", scatter_axis=1)  # (1, 1): colsum shard
        return cc.all_gather(rs, "data", tiled=True, gather_axis=1)  # (1, 8)

    f = shard_map(body, mesh=mesh8, in_specs=P("data", None), out_specs=P("data", None))
    expected = np.asarray(x).sum(axis=0, keepdims=True).repeat(8, axis=0)
    np.testing.assert_allclose(np.asarray(f(x)), expected)


def test_ring_shift(mesh8):
    x = jnp.arange(8.0)
    f = shard_map(
        functools.partial(cc.ring_shift, axis="data", shift=1),
        mesh=mesh8,
        in_specs=P("data"),
        out_specs=P("data"),
    )
    out = np.asarray(f(x))
    np.testing.assert_allclose(out, np.roll(np.arange(8.0), 1))


def test_all_to_all_roundtrip(mesh8):
    x = jnp.arange(8 * 8.0).reshape(8, 8)

    def body(v):  # v: (1, 8) per device
        w = cc.all_to_all(v, "data", split_axis=1, concat_axis=0)  # (8, 1)
        return cc.all_to_all(w, "data", split_axis=0, concat_axis=1)

    f = shard_map(body, mesh=mesh8, in_specs=P("data", None), out_specs=P("data", None))
    np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x))


def test_trace_comm_counts(mesh8):
    x = jnp.arange(8, dtype=jnp.float32)

    def body(v):
        v = cc.psum(v, "data")
        v = cc.pmean(v, "data")
        return v

    with cc.trace_comm() as rec:
        f = shard_map(body, mesh=mesh8, in_specs=P("data"), out_specs=P("data"))
        jax.jit(f).lower(x)  # force tracing inside the context
    assert rec.calls["psum[data]"] == 1
    assert rec.calls["pmean[data]"] == 1
    assert rec.bytes["psum[data]"] == 4  # one f32 per shard at trace time
    assert rec.total_calls() == 2


def test_axis_size(mesh8):
    f = shard_map(
        lambda v: v * cc.axis_size("data"),
        mesh=mesh8,
        in_specs=P("data"),
        out_specs=P("data"),
    )
    np.testing.assert_allclose(np.asarray(f(jnp.ones(8))), np.full(8, 8.0))
