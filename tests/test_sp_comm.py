"""Communication accounting for the SP layouts — traced-vs-analytic parity.

Pins the identity benchmarks/bench_sp_comm.py relies on: tracing the real
ring / Ulysses shard_map programs under ``collectives.trace_comm`` yields
exactly the call sites and per-device shard bytes the designs predict
(SURVEY.md §5 long-context row; ring = Liu et al. blockwise + KV rotation,
Ulysses = Jacobs et al. all_to_all head-resharding)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import distributed_tensorflow_guide_tpu.collectives as cc
from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh
from distributed_tensorflow_guide_tpu.parallel.sequence import (
    ring_attention,
    ulysses_attention,
)

B, S, H, D = 2, 512, 8, 32


@pytest.fixture()
def ctx_mesh():
    return build_mesh(MeshSpec(data=-1, context=4))


def _lower(mesh, fn):
    # global (B, S, H, D); shard_map hands each device (B, S/4, H, D)
    x = jnp.zeros((B, S, H, D), jnp.float32)
    sm = shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, "context"),) * 3,
        out_specs=P(None, "context"),
        check_vma=False,
    )
    with cc.trace_comm() as rec:
        jax.jit(sm).lower(x, x, x)
    local_bytes = int(np.prod((B, S // 4, H, D))) * 4
    return rec, local_bytes


def test_ring_comm_sites(ctx_mesh):
    rec, t = _lower(
        ctx_mesh, functools.partial(ring_attention, causal=True, impl="xla")
    )
    # one K + one V ppermute site inside the rotation scan, each a full
    # local shard; executed n times per step (the scan body traces once)
    assert rec.calls["ppermute[context]"] == 2
    assert rec.bytes["ppermute[context]"] == 2 * t
    assert rec.calls.get("all_to_all[context]", 0) == 0


def test_ulysses_comm_sites(ctx_mesh):
    rec, t = _lower(
        ctx_mesh,
        functools.partial(ulysses_attention, causal=True, impl="dense"),
    )
    # q/k/v reshard seq->heads plus the output's heads->seq return trip
    assert rec.calls["all_to_all[context]"] == 4
    assert rec.bytes["all_to_all[context]"] == 4 * t
    assert rec.calls.get("ppermute[context]", 0) == 0


def test_ring_pallas_fwd_bwd_comm_sites(ctx_mesh):
    """Backward comm accounting, pinned: the Pallas ring's hand-written
    Q-SIDE backward rotates THREE head_dim-sized tensors per hop (q, the
    output cotangent, the travelling dq partial) plus two lane-thin
    softmax stats (lse's first lane, delta) — 5 backward sites on top of
    the 2 forward-rule ones. Byte check is double duty: at D=32 on the
    128-lane kernel every head_dim site must move the UNPADDED shard
    (t bytes, not 4t) and the two stat rows t/D each — rotating padded
    tensors or the full lane-broadcast lse would blow this sum up (the
    pad and broadcast are applied locally per visit instead)."""
    x = jnp.zeros((B, S, H, D), jnp.float32)
    sm = shard_map(
        functools.partial(ring_attention, causal=True, impl="pallas"),
        mesh=ctx_mesh,
        in_specs=(P(None, "context"),) * 3,
        out_specs=P(None, "context"),
        check_vma=False,
    )

    def loss(q, k, v):
        return jnp.sum(sm(q, k, v).astype(jnp.float32))

    with cc.trace_comm() as rec:
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x)
    t = int(np.prod((B, S // 4, H, D))) * 4
    thin = t // D  # one f32 per (batch, head, position): lse1 or delta
    assert rec.calls["ppermute[context]"] == 7, dict(rec.calls)
    assert rec.bytes["ppermute[context]"] == 5 * t + 2 * thin, (
        rec.bytes["ppermute[context]"], t, thin)


def test_ring_pallas_optin_is_never_silent(ctx_mesh, caplog):
    """Explicitly opting into impl='pallas' must warn once per shape,
    citing the last measured pallas/xla ratio (round-5 battery), through
    the package's single degradation registry (fallback_stats) — the
    opt-in path is allowed to be slow, never silently slow."""
    import logging

    from distributed_tensorflow_guide_tpu.ops.flash_attention import (
        fallback_stats,
    )
    from distributed_tensorflow_guide_tpu.parallel.sequence import (
        RING_PALLAS_LAST_MEASURED,
    )

    d_odd = 48  # unique shape so the once-per-shape warning fires HERE
    x = jnp.zeros((B, S, H, d_odd), jnp.float32)
    sm = shard_map(
        functools.partial(ring_attention, causal=True, impl="pallas"),
        mesh=ctx_mesh,
        in_specs=(P(None, "context"),) * 3,
        out_specs=P(None, "context"),
        check_vma=False,
    )
    key = ("ring_attention_pallas_optin", S // 4, d_odd, 0, 0)
    before = fallback_stats().get(key, 0)
    with caplog.at_level(logging.WARNING, logger="dtg.ops.flash"):
        # the warning fires at TRACE time — eval_shape is enough (no
        # Mosaic lowering; keeps the tier-1 suite cheap)
        jax.eval_shape(sm, x, x, x)
    assert fallback_stats().get(key, 0) == before + 1
    if before == 0:
        msgs = [r.message for r in caplog.records]
        assert any("0.157" in m and "impl='pallas'" in m for m in msgs), msgs
    # the measured-ratio constant the warning cites stays a real dict
    assert set(RING_PALLAS_LAST_MEASURED) == {1024, 2048, 4096}


def test_ring_auto_selects_measured_winner(ctx_mesh):
    """impl='auto' must select the XLA blockwise path — the on-chip winner
    at every measured length (round-5 battery: Pallas at 0.157–0.487x of
    XLA at seq 1k/2k/4k) — even for lane-aligned shapes the kernel could
    run. The two paths share the forward trace signature (2 ppermute
    sites), so the pin is the GRAD trace: the Pallas path's hand-written
    backward issues 5 more wrapper-visible ppermute sites, while the XLA
    path's backward comes from autodiff transposes that bypass the
    wrappers — auto must show the XLA signature."""

    def grad_sites(impl, s):
        x = jnp.zeros((B, s, H, D), jnp.float32)
        sm = shard_map(
            functools.partial(ring_attention, causal=True, impl=impl),
            mesh=ctx_mesh,
            in_specs=(P(None, "context"),) * 3,
            out_specs=P(None, "context"),
            check_vma=False,
        )

        def loss(q, k, v):
            return jnp.sum(sm(q, k, v).astype(jnp.float32))

        with cc.trace_comm() as rec:
            jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x)
        return rec.calls["ppermute[context]"]

    aligned = 4 * 128   # the kernel COULD run here; auto must still say xla
    assert grad_sites("pallas", aligned) == 7
    assert grad_sites("xla", aligned) == 2
    assert grad_sites("auto", aligned) == 2
    # non-aligned shapes: auto runs xla too (and pallas refuses, pinned in
    # test_attention.py) — no silent path switch in either direction
    assert grad_sites("auto", 4 * 96) == 2
