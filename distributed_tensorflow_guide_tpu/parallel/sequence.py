"""Sequence/context parallelism: ring attention + Ulysses — first-class per
the build mandate (SURVEY.md §5 long-context row; absent from the reference).

Two standard layouts over the ``context`` mesh axis:

* **Ring attention** (Liu et al. 2023): Q/K/V are sequence-sharded; each of
  the ``n`` devices computes blockwise attention of its local Q against the
  KV block it currently holds, then rotates KV one hop around the ICI ring
  (``lax.ppermute``) — after ``n`` steps every Q block has seen every KV
  block, with per-device memory O(S/n) and only neighbor communication.
  The online-softmax carry (ops/attention.py) is what makes the partial
  results mergeable. Causality is enforced per (q-block, kv-block) pair:
  blocks strictly above the diagonal are skipped-by-masking.

* **Ulysses** (Jacobs et al. 2023): ``all_to_all`` reshards sequence ↔ heads
  around the attention core, so attention itself runs with full sequence on
  1/n of the heads — one transpose-style collective each way, no per-step
  ring traffic. Better when heads ≥ ring size and S/n is small.

Both compose with data parallelism (batch over ``data``) in one shard_map.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

import distributed_tensorflow_guide_tpu.collectives as cc
from distributed_tensorflow_guide_tpu.ops import attention as A
from distributed_tensorflow_guide_tpu.ops import flash_attention as F

# What ring_attention's impl="auto" resolves to — the ONE place the policy
# lives, so instruments (benchmarks/bench_ring_attention.py) report the
# actual pick instead of restating it. "xla" per the round-5 on-chip
# battery (Pallas at 0.157–0.487x of XLA at seq 1k–4k); flip here when a
# future capture inverts it.
RING_AUTO_IMPL = "xla"

# Last measured pallas/xla throughput ratios (round-5 on-chip battery,
# causal fwd+bwd, bf16, B=4 H=12 D=64) — what the impl="pallas" opt-in
# warning cites, and what the next capture should overwrite. Measured with
# the then-hardcoded 128x128 blocks; the autotune table (ops/autotune.py)
# is the bisect instrument for closing it.
RING_PALLAS_LAST_MEASURED = {1024: 0.157, 2048: 0.255, 4096: 0.487}


def ring_attention(q, k, v, *, axis: str = "context", causal: bool = False,
                   impl: str = "auto"):
    """Sequence-sharded attention over the ``axis`` ring.

    Per-device shapes (B, S_local, H, D); the global sequence is the
    concatenation of shards in axis order. Must run inside shard_map.

    ``impl``: "xla" is the pure-XLA blockwise path — the measured winner
    on-chip at EVERY tested length (round-5 battery: the Pallas carry path
    sustained only 0.157/0.255/0.487x of XLA at seq 1k/2k/4k), so "auto"
    now selects it unconditionally; the round-3 6.4x-the-other-way numbers
    predate the round-4 rewrites of both paths and are retired. Neither
    has been measured on this machine. "pallas" OPTS IN to the fused carry-kernel path
    (ops/flash_attention.py flash_carry_step, hand-written ring backward,
    ``lax.cond`` dead-rotation skip) — the survey's designated hard native
    part, kept first-class for the planned on-chip bisect and for any part
    where a future capture shows it winning; it needs S_local % 128 == 0
    and refuses otherwise rather than silently taking the other path.
    """
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown ring impl {impl!r}")
    if impl == "auto":
        impl = RING_AUTO_IMPL
    s_local, d = q.shape[1], q.shape[-1]
    if impl == "pallas" and not F.supported(s_local, d):
        # The kernel grid covers s_local // 128 blocks; a ragged tail would
        # be silently left as uninitialized carry memory. Refuse loudly.
        raise ValueError(
            f"impl='pallas' needs per-device seq length divisible by 128 "
            f"(got S_local={s_local}); use impl='xla' or pad the sequence"
        )
    if impl == "pallas":
        # The opt-in path must never be SILENTLY slow: one warning per
        # shape (same once-per-shape registry as the flash fallback, so a
        # profiling audit reads a single surface) citing the last measured
        # pallas/xla ratio.
        ratios = ", ".join(f"{s}: {r}x"
                           for s, r in RING_PALLAS_LAST_MEASURED.items())
        F._note_fallback(
            s_local, d, 0, 0, origin="ring_attention_pallas_optin",
            msg=(
                "ring_attention impl='pallas' opted in: the last on-chip "
                "capture (round-5 battery) measured the Pallas carry path "
                f"at a fraction of the XLA path ({{seq: pallas/xla}} = "
                f"{{{ratios}}}). Tune it first (benchmarks/"
                "bench_flash_kernel.py --tune populates the carry_step "
                "autotune entry) or use impl='auto'."
            ))
        return _ring_flash_public(q, k, v, axis=axis, causal=causal)
    return _ring_xla(q, k, v, axis=axis, causal=causal)


def _ring_xla(q, k, v, *, axis: str, causal: bool):
    n = cc.axis_size(axis)
    my = lax.axis_index(axis)
    s_local = q.shape[1]
    scale = 1.0 / (q.shape[-1] ** 0.5)
    fwd = [(i, (i + 1) % n) for i in range(n)]

    qf = q.astype(jnp.float32)
    m, l, o = A.init_carry(q.shape)
    q_pos = my * s_local + jnp.arange(s_local)

    def body(carry, step):
        m, l, o, k_cur, v_cur, src = carry
        if causal:
            kv_pos = src * s_local + jnp.arange(s_local)
            mask = (q_pos[:, None] >= kv_pos[None, :])[None, None]
        else:
            mask = None
        m, l, o = A.block_update(
            qf, k_cur.astype(jnp.float32), v_cur.astype(jnp.float32),
            m, l, o, scale=scale, mask=mask,
        )
        # rotate KV to the next device; the block we receive came from the
        # previous rank, so its global offset decrements by one each step
        k_cur = cc.ppermute(k_cur, axis, fwd)
        v_cur = cc.ppermute(v_cur, axis, fwd)
        src = (src - 1) % n
        return (m, l, o, k_cur, v_cur, src), None

    (m, l, o, _, _, _), _ = lax.scan(
        body, (m, l, o, k, v, my), jnp.arange(n)
    )
    return A.finalize(m, l, o).astype(q.dtype)


# -- Pallas-fused ring (carry kernel + hand-written ring backward) -----------
#
# Causality over aligned equal-length shards collapses to three static
# cases per rotation — the visiting KV shard is entirely before the local Q
# shard (full attention), IS the local shard (ordinary in-block causal), or
# entirely after (dead). lax.cond dispatches between two static kernel
# variants and skips dead rotations outright; the XLA path above computes
# then masks them (~2x FLOP waste at large rings, round-2 verdict weak 4).


def _pad_lane(x, d, dp):
    """Pad head_dim to the kernel lane width — LOCALLY, at the kernel
    boundary. The ring deliberately rotates UNPADDED tensors: at d=64 on
    the 128-lane kernel, rotating padded tensors would double every hop's
    ICI bytes (measured by bench_sp_comm — 2x wire for a VPU-cheap pad),
    so the pad is re-applied per visit instead of travelling."""
    if dp == d:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, dp - d)))


def _ring_steps_fwd(q, k, v, axis, causal, scale):
    """Ring forward in kernel layout (B, H, S_loc, D) -> (out, lse)."""
    n = cc.axis_size(axis)
    # the rotation-source index matters only for causal masking; tracing
    # axis_index into the non-causal program would put a live-but-unused
    # PartitionId in the scan carry for nothing
    my = lax.axis_index(axis) if causal else jnp.int32(0)
    b, h, s, d = q.shape
    dp = -(-d // F.LANE) * F.LANE
    fwd = [(i, (i + 1) % n) for i in range(n)]
    m, l, acc = F.carry_init(b, h, s, dp)
    qp = _pad_lane(q, d, dp)  # local: pad once, never rotates
    # tuned per-visit block sizes from the autotune table (keyed on the
    # LOGICAL head dim; tested default 128x128 on a miss)
    cblk = F.carry_blocks(b, h, s, d, q.dtype, causal)

    def step(diag):
        def run(m, l, acc, k_cur, v_cur):
            return F.flash_carry_step(qp, _pad_lane(k_cur, d, dp),
                                      _pad_lane(v_cur, d, dp), m, l, acc,
                                      scale=scale, diag=diag,
                                      blk_q=cblk[0], blk_k=cblk[1])

        return run

    def skip(m, l, acc, k_cur, v_cur):
        return m, l, acc

    def body(carry, _):
        m, l, acc, k_cur, v_cur, src = carry
        if causal:
            m, l, acc = lax.cond(
                src == my,
                step(True),
                lambda *a: lax.cond(src < my, step(False), skip, *a),
                m, l, acc, k_cur, v_cur,
            )
        else:
            m, l, acc = step(False)(m, l, acc, k_cur, v_cur)
        k_cur = cc.ppermute(k_cur, axis, fwd)
        v_cur = cc.ppermute(v_cur, axis, fwd)
        return (m, l, acc, k_cur, v_cur, (src - 1) % n), None

    (m, l, acc, _, _, _), _ = lax.scan(
        body, (m, l, acc, k, v, my), None, length=n
    )
    out, lse = F.carry_finalize(m, l, acc)
    return out[..., :d].astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash(q, k, v, axis, causal, scale):
    out, _ = _ring_steps_fwd(q, k, v, axis, causal, scale)
    return out


def _ring_flash_fwd_rule(q, k, v, axis, causal, scale):
    out, lse = _ring_steps_fwd(q, k, v, axis, causal, scale)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd_rule(axis, causal, scale, res, g):
    """Second ring pass, Q-SIDE rotation: (k, v) stay home and (dk, dv)
    accumulate locally; the Q side — q, the output cotangent g, the
    travelling dq partial sum, and two lane-thin softmax stats (lse's
    first lane, delta) — rotates instead, arriving home after n hops.

    Why this orientation: the KV-side rotation moves FOUR head_dim-sized
    tensors per hop (k, v, dk-partial, dv-partial); this one moves THREE
    plus two (B, H, S)-thin rows — ~24% less backward wire at f32 D=64
    and ~32% at bf16 (the f32 partial dominates either way; measured by
    bench_sp_comm's traced table, pinned in tests/test_sp_comm.py).
    Causality flips perspective: the LOCAL kv shard at index ``my`` meets
    the visiting q-block from ``src_q``; src_q == my is the masked
    diagonal, src_q > my full (q after kv), src_q < my dead (skipped).
    Reuses the flash backward kernels per rotation; lse re-broadcasts to
    the lane width locally (broadcast is free, rotating it is not)."""
    q, k, v, out, lse = res
    n = cc.axis_size(axis)
    # causal-only, as in _ring_steps_fwd (PartitionId lowering note there)
    my = lax.axis_index(axis) if causal else jnp.int32(0)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    f32 = jnp.float32
    d = q.shape[-1]
    dp = -(-d // F.LANE) * F.LANE
    delta = jnp.sum(g.astype(f32) * out.astype(f32), axis=-1)  # (B,H,S)
    kp = _pad_lane(k, d, dp)       # local + stationary: pad once
    vp = _pad_lane(v, d, dp)
    # per-kernel tuned blocks for the per-visit backward (dq and dkv have
    # their own autotune entries; tested default 128x128)
    blk_dq, blk_dkv = F.bwd_blocks(q.shape[0], q.shape[1], q.shape[2], d,
                                   q.dtype, causal)

    def run(diag):
        def go(q_cur, g_cur, lse1_cur, delta_cur):
            lse_b = jnp.broadcast_to(lse1_cur, (*lse1_cur.shape[:-1], F.LANE))
            dq_s, dk_s, dv_s = F._bwd_call(
                _pad_lane(q_cur, d, dp), kp, vp,
                _pad_lane(g_cur, d, dp), lse_b, delta_cur,
                scale=scale, causal=diag, blk_dq=blk_dq, blk_dkv=blk_dkv,
            )
            return (dq_s[..., :d].astype(f32), dk_s[..., :d].astype(f32),
                    dv_s[..., :d].astype(f32))

        return go

    def skip(q_cur, g_cur, lse1_cur, delta_cur):
        z = jnp.zeros(q.shape, f32)
        return z, z, z

    def body(carry, _):
        q_cur, g_cur, lse1_cur, delta_cur, dq_cur, dk, dv, src_q = carry
        if causal:
            dq_s, dk_s, dv_s = lax.cond(
                src_q == my,
                run(True),
                lambda *a: lax.cond(src_q > my, run(False), skip, *a),
                q_cur, g_cur, lse1_cur, delta_cur,
            )
        else:
            dq_s, dk_s, dv_s = run(False)(q_cur, g_cur, lse1_cur, delta_cur)
        dq_cur = dq_cur + dq_s
        dk = dk + dk_s
        dv = dv + dv_s
        q_cur = cc.ppermute(q_cur, axis, fwd)
        g_cur = cc.ppermute(g_cur, axis, fwd)
        lse1_cur = cc.ppermute(lse1_cur, axis, fwd)
        delta_cur = cc.ppermute(delta_cur, axis, fwd)
        dq_cur = cc.ppermute(dq_cur, axis, fwd)
        return (q_cur, g_cur, lse1_cur, delta_cur, dq_cur, dk, dv,
                (src_q - 1) % n), None

    z = jnp.zeros(q.shape, f32)
    (_, _, _, _, dq, dk, dv, _), _ = lax.scan(
        body, (q, g, lse[..., :1], delta, z, z, z, my), None, length=n
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd_rule)


def _ring_flash_public(q, k, v, *, axis: str, causal: bool):
    """Public layout (B, S_loc, H, D) -> same. Head-dim lane padding
    happens INSIDE the ring steps (``_pad_lane``) so the rotations move
    unpadded tensors — see the wire-bytes rationale there."""
    d = q.shape[-1]
    scale = 1.0 / (d ** 0.5)

    def to_kernel(x):
        return jnp.transpose(x, (0, 2, 1, 3))

    out = _ring_flash(to_kernel(q), to_kernel(k), to_kernel(v), axis,
                      causal, scale)
    return jnp.transpose(out, (0, 2, 1, 3))


def ulysses_attention(q, k, v, *, axis: str = "context",
                      causal: bool = False, impl: str = "auto"):
    """Ulysses: all_to_all seq→heads, full-sequence attention on a head
    shard, all_to_all heads→seq back.

    Per-device in/out: (B, S_local, H, D); requires H % axis_size == 0.

    ``impl``: the attention core after resharding sees the FULL sequence,
    so long contexts need the fused kernel — "auto" uses the Pallas flash
    kernel (ops/flash_attention.py) when the global seq length fits its
    blocks, dense otherwise; "dense"/"flash" pin the choice.
    """
    if impl not in ("auto", "dense", "flash"):
        raise ValueError(f"unknown ulysses impl {impl!r}")
    n = cc.axis_size(axis)
    h = q.shape[2]
    if h % n:
        raise ValueError(
            f"context size {n} must divide num_heads {h} (each device "
            "takes H/n heads after the all_to_all)"
        )

    def to_heads(x):  # (B, S/n, H, D) -> (B, S, H/n, D)
        return cc.all_to_all(x, axis, split_axis=2, concat_axis=1)

    def to_seq(x):  # (B, S, H/n, D) -> (B, S/n, H, D)
        return cc.all_to_all(x, axis, split_axis=1, concat_axis=2)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    s_global, d = qh.shape[1], qh.shape[-1]
    fits = F.supported(s_global, d)
    if impl == "flash" and not fits:
        # pinning the kernel must not silently take the slow path (the same
        # contract as ring impl="pallas")
        raise ValueError(
            f"impl='flash' needs global seq length divisible by 128 (got "
            f"{s_global}); use impl='dense' or pad the sequence"
        )
    use_flash = impl == "flash" or (impl == "auto" and fits)
    if use_flash:
        out = F.flash_attention(qh, kh, vh, causal=causal)
    else:
        out = A.dense_attention(qh, kh, vh, causal=causal)
    return to_seq(out)
