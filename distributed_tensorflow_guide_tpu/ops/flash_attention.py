"""Fused flash attention as a Pallas TPU kernel — the framework's "native
code" tier (SURVEY.md §2b/§5: the reference's native machinery is the TF
C++/CUDA runtime; on TPU the idiomatic native tier is a Mosaic kernel).

Forward and backward are hand-written kernels (FlashAttention, Dao et al.
2022; same online-softmax algebra as ops/attention.py, which is the
pure-XLA reference implementation these kernels are tested against):

* forward: one pass over KV blocks per Q block, carrying the running
  row-max ``m`` and normalizer ``l`` in VMEM scratch; O(S) memory, no
  (S, S) score matrix ever hits HBM. Saves per-row logsumexp for backward.
* backward: recomputes probabilities from the saved logsumexp (no stored
  attention matrix) in two kernels — one accumulating dQ over KV blocks,
  one accumulating dK/dV over Q blocks — the standard flash backward split
  that keeps every accumulation local to one grid cell's scratch.

Layout: public API takes (B, S, H, D) like the rest of the package and
transposes to (B, H, S, D) for the kernel so the (S, D) tiles are MXU-shaped.
Head dim is zero-padded to a lane multiple (128); zero columns are exact
no-ops through q·kᵀ and the p·v contraction, and are sliced off on return.

Block sizes are NOT hardcoded: each kernel (fwd, dq, dkv, and the ring
carry step) resolves its own (blk_q, blk_k) from the autotune table
(ops/autotune.py; the tracked ``autotune_table_v1.json`` holds what
``bench_flash_kernel.py --tune`` measured on a v5e). A shape the table has
not seen gets 128x128, which is tested and slow: at (8, 16, 1024, 64) on a
v5e it is 8,192 grid steps of one MXU tile's work each, 3.4-4.4 ms a call
where the tuned tile, the whole 1024x1024 square, takes 0.57-0.75
(PERF.md, PR 27). ``autotune.resolution_stats()`` says which a call got.
Explicit ``blk_q``/``blk_k`` arguments pin it, which is what the parity
tests and the sweep itself use.

Precision: the products take q, k, v and do in the dtype they arrive in and
accumulate in float32 (``preferred_element_type``); the probabilities and
their gradients are cast to that dtype right before the second products.
Everything between the products (scale, mask, max, exp, sums, ``lse``,
``delta``, the scratch accumulators) is float32. So bfloat16 inputs get
single-pass MXU products, and float32 inputs get float32 arithmetic
throughout.

On CPU (tests, dryrun) the same kernels run via ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_guide_tpu.ops import autotune
from distributed_tensorflow_guide_tpu.ops.autotune import (
    DEFAULT_BLOCKS,
    FlashBlocks,
)

NEG_INF = -1e30
LANE = 128


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _vmem_spec(block_shape=None, index_map=None):
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


def _vmem_scratch(shape, dtype):
    return pltpu.VMEM(shape, dtype)


# --------------------------------------------------------------------------
# shared kernel pieces
# --------------------------------------------------------------------------


def _causal_block_live(i, j, blk_q: int, blk_k: int):
    """False iff KV block j lies strictly above Q block i's diagonal."""
    return (j * blk_k) <= (i * blk_q + blk_q - 1)


def _masked_scores(q_ref, k_ref, i, j, *, scale, causal, blk_q, blk_k):
    """scale·q·kᵀ for one (Q-block i, KV-block j) pair, causal-masked.

    The single definition shared by forward and both backward kernels so the
    recomputed probabilities can never drift from the forward pass.
    """
    s = jax.lax.dot_general(
        q_ref[0, 0], k_ref[0, 0],  # (blk_q, Dp) x (blk_k, Dp)
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # (blk_q, blk_k)
    if causal:
        q_pos = i * blk_q + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 0
        )
        kv_pos = j * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 1
        )
        s = jnp.where(q_pos >= kv_pos, s, NEG_INF)
    return s


def _softmax_update(m_scr, l_scr, acc_scr, s, v, *, masked: bool):
    """One online-softmax accumulation into the (m, l, acc) scratch state.

    The single definition shared by the standalone forward kernel and the
    ring carry kernel — the ring path's correctness depends on the two
    staying bit-identical (same rescaling, same NEG_INF mask threshold).
    """
    m_prev = m_scr[:, :1]
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    if masked:
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale: float, causal: bool, blk_q: int, blk_k: int):
    i, j = pl.program_id(2), pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: KV blocks strictly above the diagonal contribute nothing.
    should_run = True
    if causal:
        should_run = _causal_block_live(i, j, blk_q, blk_k)

    @pl.when(should_run)
    def _():
        s = _masked_scores(q_ref, k_ref, i, j, scale=scale, causal=causal,
                           blk_q=blk_q, blk_k=blk_k)
        _softmax_update(m_scr, l_scr, acc_scr, s, v_ref[0, 0], masked=causal)

    @pl.when(j == n_kv - 1)
    def _():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        lse = m_scr[:, :1] + jnp.log(safe_l)
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _fwd_call(q, k, v, *, scale, causal, blk_q, blk_k):
    b, h, s, dp = q.shape
    n_q, n_kv = s // blk_q, s // blk_k
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_kv),
        in_specs=[
            _vmem_spec((1, 1, blk_q, dp), lambda b, h, i, j: (b, h, i, 0)),
            _vmem_spec((1, 1, blk_k, dp), lambda b, h, i, j: (b, h, j, 0)),
            _vmem_spec((1, 1, blk_k, dp), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=[
            _vmem_spec((1, 1, blk_q, dp), lambda b, h, i, j: (b, h, i, 0)),
            _vmem_spec((1, 1, blk_q, LANE), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, dp), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, LANE), jnp.float32),
        ],
        scratch_shapes=[
            _vmem_scratch((blk_q, LANE), jnp.float32),
            _vmem_scratch((blk_q, LANE), jnp.float32),
            _vmem_scratch((blk_q, dp), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v)
    # lse stays lane-broadcast at (B, H, S, LANE): the (blk_q,)→(blk_q, 1)
    # sublane relayout a compact (B, H, S) residual would force on every
    # backward read is what Mosaic handles worst; jax's own TPU flash kernel
    # makes the same trade (pallas/ops/tpu/flash_attention.py stores l/m at
    # MIN_BLOCK_SIZE=128 lanes). Backward consumes it directly — no
    # slice-then-rebroadcast round trip through HBM.
    return out, lse


# --------------------------------------------------------------------------
# carry-in/carry-out forward (the ring-attention inner loop)
# --------------------------------------------------------------------------
#
# Ring attention (parallel/sequence.py) rotates KV shards around the ICI
# ring and merges each visit into a running online-softmax state. This
# kernel is the fused inner loop the survey designates as the hard native
# part (SURVEY.md §5): identical math to _fwd_kernel, but the (m, l, acc)
# state enters and leaves as ARRAYS so it can be carried across rotations —
# and no normalization happens here; the caller divides once at the end.
#
# Causality across shards collapses to three STATIC cases per rotation
# (shards are equal-length and aligned): the visiting KV shard is entirely
# before the local Q shard (mode full — no mask), it IS the local shard
# (mode diag — ordinary causal masking within the block), or entirely after
# (dead — the caller skips the kernel call altogether; that is where the
# old XLA path burned ~2x FLOPs at large rings).


def _carry_fwd_kernel(q_ref, k_ref, v_ref, m_in, l_in, acc_in,
                      m_out, l_out, acc_out, m_scr, l_scr, acc_scr,
                      *, scale: float, diag: bool, blk_q: int, blk_k: int):
    i, j = pl.program_id(2), pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(j == 0)
    def _():
        m_scr[:] = m_in[0, 0]
        l_scr[:] = l_in[0, 0]
        acc_scr[:] = acc_in[0, 0]

    should_run = True
    if diag:
        should_run = _causal_block_live(i, j, blk_q, blk_k)

    @pl.when(should_run)
    def _():
        s = _masked_scores(q_ref, k_ref, i, j, scale=scale, causal=diag,
                           blk_q=blk_q, blk_k=blk_k)
        _softmax_update(m_scr, l_scr, acc_scr, s, v_ref[0, 0], masked=diag)

    @pl.when(j == n_kv - 1)
    def _():
        m_out[0, 0] = m_scr[:]
        l_out[0, 0] = l_scr[:]
        acc_out[0, 0] = acc_scr[:]


def flash_carry_step(q, k, v, m, l, acc, *, scale: float, diag: bool,
                     blk_q: int, blk_k: int):
    """One ring-rotation visit: merge KV block (k, v) into the carry.

    Kernel layout: q/k/v (B, H, S, Dp); m/l (B, H, S, LANE) f32
    (lane-broadcast, same trade as _fwd_call's lse); acc (B, H, S, Dp) f32
    un-normalized. ``diag`` selects causal masking for the aligned-shard
    rotation; fully-dead rotations must be skipped by the caller.

    Block sizes are REQUIRED: this function only sees the lane-PADDED head
    dim while the autotune table keys on the logical one, so resolution
    belongs to the caller — :func:`carry_blocks` is the one lookup path
    (parallel/sequence.py uses it; an in-function fallback keyed on the
    padded dim would silently miss every d < LANE entry).
    """
    b, h, s, dp = q.shape
    n_q, n_kv = s // blk_q, s // blk_k
    kernel = functools.partial(
        _carry_fwd_kernel, scale=scale, diag=diag, blk_q=blk_q, blk_k=blk_k
    )
    qs = _vmem_spec((1, 1, blk_q, dp), lambda b, h, i, j: (b, h, i, 0))
    ks = _vmem_spec((1, 1, blk_k, dp), lambda b, h, i, j: (b, h, j, 0))
    ls = _vmem_spec((1, 1, blk_q, LANE), lambda b, h, i, j: (b, h, i, 0))
    return pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_kv),
        in_specs=[qs, ks, ks, ls, ls, qs],
        out_specs=[ls, ls, qs],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, LANE), jnp.float32),
            jax.ShapeDtypeStruct((b, h, s, LANE), jnp.float32),
            jax.ShapeDtypeStruct((b, h, s, dp), jnp.float32),
        ],
        scratch_shapes=[
            _vmem_scratch((blk_q, LANE), jnp.float32),
            _vmem_scratch((blk_q, LANE), jnp.float32),
            _vmem_scratch((blk_q, dp), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v, m, l, acc)


def carry_init(b, h, s, dp):
    """Fresh (m, l, acc) for a ring pass, kernel layout."""
    return (
        jnp.full((b, h, s, LANE), NEG_INF, jnp.float32),
        jnp.zeros((b, h, s, LANE), jnp.float32),
        jnp.zeros((b, h, s, dp), jnp.float32),
    )


def carry_finalize(m, l, acc):
    """(out, lse): normalize the accumulated state once, after all visits."""
    l1 = l[..., :1]
    safe = jnp.where(l1 == 0.0, 1.0, l1)
    out = acc / safe
    lse = m + jnp.log(jnp.where(l == 0.0, 1.0, l))
    return out, lse


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale: float, causal: bool, blk_q: int,
                   blk_k: int):
    i, j = pl.program_id(2), pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    should_run = True
    if causal:
        should_run = _causal_block_live(i, j, blk_q, blk_k)

    @pl.when(should_run)
    def _():
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]  # (blk_q, 1)
        delta = delta_ref[0, 0][:, :1]
        s = _masked_scores(q_ref, k_ref, i, j, scale=scale, causal=causal,
                           blk_q=blk_q, blk_k=blk_k)
        p = jnp.exp(s - lse)  # rows with lse=-inf can't occur (see fwd)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (blk_q, blk_k)
        ds = p * (dp - delta) * scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == n_kv - 1)
    def _():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                    causal: bool, blk_q: int, blk_k: int):
    # grid: (b, h, kv_block j, q_block i) — inner loop over Q blocks
    j, i = pl.program_id(2), pl.program_id(3)
    n_q = pl.num_programs(3)

    @pl.when(i == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    should_run = True
    if causal:
        should_run = _causal_block_live(i, j, blk_q, blk_k)

    @pl.when(should_run)
    def _():
        q = q_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = _masked_scores(q_ref, k_ref, i, j, scale=scale, causal=causal,
                           blk_q=blk_q, blk_k=blk_k)
        p = jnp.exp(s - lse)  # (blk_q, blk_k)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # pᵀ·dO → (blk_k, Dp)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale  # (blk_q, blk_k)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # dsᵀ·q → (blk_k, Dp)

    @pl.when(i == n_q - 1)
    def _():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_call(q, k, v, do, lse_b, delta_b, *, scale, causal, blk_q,
                 blk_k):
    """The dQ backward kernel alone — separately callable so the autotuner
    and the kernel-only microbench can sweep/measure it apart from dK/dV
    (its arithmetic intensity differs: 3 MXU passes per block vs 4)."""
    b, h, s, dp = q.shape
    n_q, n_kv = s // blk_q, s // blk_k
    return pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, blk_q=blk_q,
            blk_k=blk_k,
        ),
        grid=(b, h, n_q, n_kv),
        in_specs=[
            _vmem_spec((1, 1, blk_q, dp), lambda b, h, i, j: (b, h, i, 0)),
            _vmem_spec((1, 1, blk_k, dp), lambda b, h, i, j: (b, h, j, 0)),
            _vmem_spec((1, 1, blk_k, dp), lambda b, h, i, j: (b, h, j, 0)),
            _vmem_spec((1, 1, blk_q, dp), lambda b, h, i, j: (b, h, i, 0)),
            _vmem_spec((1, 1, blk_q, LANE), lambda b, h, i, j: (b, h, i, 0)),
            _vmem_spec((1, 1, blk_q, LANE), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=_vmem_spec(
            (1, 1, blk_q, dp), lambda b, h, i, j: (b, h, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, s, dp), q.dtype),
        scratch_shapes=[_vmem_scratch((blk_q, dp), jnp.float32)],
        interpret=_interpret(),
    )(q, k, v, do, lse_b, delta_b)


def _bwd_dkv_call(q, k, v, do, lse_b, delta_b, *, scale, causal, blk_q,
                  blk_k):
    """The dK/dV backward kernel alone (see _bwd_dq_call)."""
    b, h, s, dp = q.shape
    n_q, n_kv = s // blk_q, s // blk_k
    return pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal, blk_q=blk_q,
            blk_k=blk_k,
        ),
        grid=(b, h, n_kv, n_q),
        in_specs=[
            _vmem_spec((1, 1, blk_q, dp), lambda b, h, j, i: (b, h, i, 0)),
            _vmem_spec((1, 1, blk_k, dp), lambda b, h, j, i: (b, h, j, 0)),
            _vmem_spec((1, 1, blk_k, dp), lambda b, h, j, i: (b, h, j, 0)),
            _vmem_spec((1, 1, blk_q, dp), lambda b, h, j, i: (b, h, i, 0)),
            _vmem_spec((1, 1, blk_q, LANE), lambda b, h, j, i: (b, h, i, 0)),
            _vmem_spec((1, 1, blk_q, LANE), lambda b, h, j, i: (b, h, i, 0)),
        ],
        out_specs=[
            _vmem_spec((1, 1, blk_k, dp), lambda b, h, j, i: (b, h, j, 0)),
            _vmem_spec((1, 1, blk_k, dp), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, dp), k.dtype),
            jax.ShapeDtypeStruct((b, h, s, dp), v.dtype),
        ],
        scratch_shapes=[
            _vmem_scratch((blk_k, dp), jnp.float32),
            _vmem_scratch((blk_k, dp), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v, do, lse_b, delta_b)


def _bwd_call(q, k, v, do, lse, delta, *, scale, causal, blk_dq, blk_dkv):
    """Both backward kernels, each at its OWN tuned (blk_q, blk_k). lse
    arrives lane-broadcast (B, H, S, LANE) straight from forward; delta is
    (B, H, S) and broadcast once here."""
    b, h, s, dp = q.shape
    delta_b = jnp.broadcast_to(delta[..., None], (b, h, s, LANE))
    dq = _bwd_dq_call(q, k, v, do, lse, delta_b, scale=scale, causal=causal,
                      blk_q=blk_dq[0], blk_k=blk_dq[1])
    dk, dv = _bwd_dkv_call(q, k, v, do, lse, delta_b, scale=scale,
                           causal=causal, blk_q=blk_dkv[0],
                           blk_k=blk_dkv[1])
    return dq, dk, dv


# --------------------------------------------------------------------------
# GSPMD composition: custom_partitioning wrappers (flash under pjit/TP)
# --------------------------------------------------------------------------
#
# GSPMD cannot see through a Pallas custom call, so under pjit (the
# TensorParallel strategy) the kernel used to be unusable — round-2 verdict
# weak item 3. These wrappers teach the partitioner the kernel's contract:
# batch and heads shard freely (heads map to the "model" axis under TP);
# sequence, head_dim, and the LANE dim of the lse residual must replicate.
# Shardy propagates via the SdyShardingRule; the partition callback lowers
# to the SAME kernels on the per-shard block. Inside shard_map (DP/PP/SP
# strategies) arrays are already per-device and the raw calls are used —
# see _flash's dispatch.


def _in_auto_mesh() -> bool:
    """True when tracing under a non-empty mesh with no Manual axes — i.e.
    GSPMD/pjit context where custom_partitioning applies. Inside shard_map
    (Manual axes) or plain single-device jit the raw kernel call is right.

    Checks both mesh contexts: ``jax.set_mesh`` (abstract mesh) and the
    legacy ``with mesh:`` block. TensorParallel uses the LEGACY context on
    purpose: ``jax.set_mesh`` flips flax's ``global_mesh_defined()`` and
    activates every logical constraint eagerly, which breaks flax's own
    ``DenseGeneral`` + ``with_logical_partitioning`` combination (the kernel
    initializes flattened to rank 2 while the logical names are rank 4)."""
    am = jax.sharding.get_abstract_mesh()
    if am.axis_names:
        from jax.sharding import AxisType

        return not any(t == AxisType.Manual for t in am.axis_types)
    # legacy `with mesh:` context: no public accessor
    from jax._src import mesh as mesh_lib

    return not mesh_lib.thread_resources.env.physical_mesh.empty


def _bh_sharding(mesh, sharding, rank: int = 4):
    """Batch/head dims keep their propagated sharding; the rest replicate."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    spec = list(sharding.spec) + [None] * rank
    return NamedSharding(mesh, P(spec[0], spec[1], *([None] * (rank - 2))))


def _make_cp():
    from jax.experimental.custom_partitioning import (
        SdyShardingRule,
        custom_partitioning,
    )

    fwd_cp = custom_partitioning(
        lambda q, k, v, scale, causal, blk_q, blk_k: _fwd_call(
            q, k, v, scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k
        ),
        static_argnums=(3, 4, 5, 6),
    )

    def fwd_infer(scale, causal, blk_q, blk_k, mesh, arg_shapes, result_shape):
        s = _bh_sharding(mesh, arg_shapes[0].sharding)
        return (s, s)

    def fwd_part(scale, causal, blk_q, blk_k, mesh, arg_shapes, result_shape):
        s = _bh_sharding(mesh, arg_shapes[0].sharding)

        def lower(q, k, v):
            return _fwd_call(q, k, v, scale=scale, causal=causal,
                             blk_q=blk_q, blk_k=blk_k)

        return mesh, lower, (s, s), (s, s, s)

    fwd_cp.def_partition(
        partition=fwd_part,
        infer_sharding_from_operands=fwd_infer,
        sharding_rule=SdyShardingRule(
            (("b", "h", "s", "d"),) * 3,
            (("b", "h", "s", "d"), ("b", "h", "s", "l")),
            need_replication_factors=("s", "d", "l"),
        ),
    )

    bwd_cp = custom_partitioning(
        lambda q, k, v, do, lse, delta, scale, causal, blk_dq, blk_dkv:
        _bwd_call(q, k, v, do, lse, delta, scale=scale, causal=causal,
                  blk_dq=blk_dq, blk_dkv=blk_dkv),
        static_argnums=(6, 7, 8, 9),
    )

    def bwd_infer(scale, causal, blk_dq, blk_dkv, mesh, arg_shapes,
                  result_shape):
        s = _bh_sharding(mesh, arg_shapes[0].sharding)
        return (s, s, s)

    def bwd_part(scale, causal, blk_dq, blk_dkv, mesh, arg_shapes,
                 result_shape):
        s = _bh_sharding(mesh, arg_shapes[0].sharding)
        s3 = _bh_sharding(mesh, arg_shapes[0].sharding, rank=3)

        def lower(q, k, v, do, lse, delta):
            return _bwd_call(q, k, v, do, lse, delta, scale=scale,
                             causal=causal, blk_dq=blk_dq, blk_dkv=blk_dkv)

        return mesh, lower, (s, s, s), (s, s, s, s, s, s3)

    bwd_cp.def_partition(
        partition=bwd_part,
        infer_sharding_from_operands=bwd_infer,
        sharding_rule=SdyShardingRule(
            (("b", "h", "s", "d"),) * 4
            + (("b", "h", "s", "l"), ("b", "h", "s")),
            (("b", "h", "s", "d"),) * 3,
            need_replication_factors=("s", "d", "l"),
        ),
    )
    return fwd_cp, bwd_cp


_FWD_CP, _BWD_CP = _make_cp()


def _fwd_dispatch(q, k, v, *, scale, causal, blk_q, blk_k):
    if _in_auto_mesh():
        return _FWD_CP(q, k, v, scale, causal, blk_q, blk_k)
    return _fwd_call(q, k, v, scale=scale, causal=causal, blk_q=blk_q,
                     blk_k=blk_k)


def _bwd_dispatch(q, k, v, do, lse, delta, *, scale, causal, blk_dq,
                  blk_dkv):
    if _in_auto_mesh():
        return _BWD_CP(q, k, v, do, lse, delta, scale, causal, blk_dq,
                       blk_dkv)
    return _bwd_call(q, k, v, do, lse, delta, scale=scale, causal=causal,
                     blk_dq=blk_dq, blk_dkv=blk_dkv)


# --------------------------------------------------------------------------
# public API with custom VJP
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, scale, causal, blocks: FlashBlocks):
    out, _ = _fwd_dispatch(q, k, v, scale=scale, causal=causal,
                           blk_q=blocks.fwd[0], blk_k=blocks.fwd[1])
    return out


def _flash_fwd_rule(q, k, v, scale, causal, blocks: FlashBlocks):
    out, lse = _fwd_dispatch(q, k, v, scale=scale, causal=causal,
                             blk_q=blocks.fwd[0], blk_k=blocks.fwd[1])
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(scale, causal, blocks: FlashBlocks, res, g):
    q, k, v, out, lse = res
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    dq, dk, dv = _bwd_dispatch(
        q, k, v, g, lse, delta, scale=scale, causal=causal,
        blk_dq=blocks.dq, blk_dkv=blocks.dkv,
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_blocks(b: int, h: int, s: int, d: int, dtype,
                 causal: bool = True) -> FlashBlocks:
    """Per-kernel tuned blocks for one flash call shape — each of the three
    kernels consults its OWN autotune entry (128x128 on a miss).

    With :func:`carry_blocks` and :func:`bwd_blocks`, these helpers are
    the ONLY lookup paths — key construction (logical head dim, dtype,
    causal regime) lives here, never at call sites. Resolution routes
    through ``autotune.ensure_tuned_online``: with online tuning OFF
    (the default) that is exactly the old trace-safe ``blocks_for``
    lookup; with it ON, an unseen key on a sweep-capable backend pays
    one in-situ sweep here (first trace) and persists the winner."""
    kw = dict(b=b, h=h, s=s, d=d, dtype=dtype, causal=causal)
    return FlashBlocks(
        fwd=autotune.ensure_tuned_online("flash_fwd", **kw),
        dq=autotune.ensure_tuned_online("flash_dq", **kw),
        dkv=autotune.ensure_tuned_online("flash_dkv", **kw),
    )


def bwd_blocks(b: int, h: int, s: int, d: int, dtype,
               causal: bool = True) -> tuple[tuple[int, int],
                                             tuple[int, int]]:
    """(blk_dq, blk_dkv) for a standalone backward call — what the ring's
    hand-written per-visit backward (parallel/sequence.py) resolves."""
    kw = dict(b=b, h=h, s=s, d=d, dtype=dtype, causal=causal)
    return (autotune.ensure_tuned_online("flash_dq", **kw),
            autotune.ensure_tuned_online("flash_dkv", **kw))


def carry_blocks(b: int, h: int, s: int, d: int, dtype,
                 causal: bool = True) -> tuple[int, int]:
    """Tuned blocks for the ring carry kernel, keyed on the LOGICAL head
    dim (the ring call sites know it; flash_carry_step itself only sees the
    padded dim)."""
    return autotune.ensure_tuned_online("carry_step", b=b, h=h, s=s, d=d,
                                        dtype=dtype, causal=causal)


def supported(s: int, d: int, blk_q: int | None = None,
              blk_k: int | None = None) -> bool:
    """Shapes the fused kernel handles; callers fall back to the pure-XLA
    blockwise path otherwise. Defaults to the autotune fallback blocks."""
    if blk_q is None:
        blk_q = DEFAULT_BLOCKS[0]
    if blk_k is None:
        blk_k = DEFAULT_BLOCKS[1]
    return s % blk_q == 0 and s % blk_k == 0 and s >= max(blk_q, blk_k)


# Fallbacks were an unobservable perf cliff (round-2 verdict weak 6): a
# caller asking for flash could silently get the slower XLA blockwise path.
# Every fallback now logs once per shape and is counted; tests and profiling
# read fallback_stats().
_FALLBACKS: dict[tuple, int] = {}


def fallback_stats() -> dict[tuple, int]:
    """(origin, s, d, blk_q, blk_k) -> number of kernel->XLA fallback traces.

    One registry for every auto-degradation in the package — flash's
    blockwise fallback AND ring_attention's impl="auto" XLA path — so a
    profiling audit reads a single surface."""
    return dict(_FALLBACKS)


def _note_fallback(s: int, d: int, blk_q: int, blk_k: int, *,
                   origin: str = "flash_attention",
                   msg: str | None = None) -> None:
    import logging

    key = (origin, s, d, blk_q, blk_k)
    first = key not in _FALLBACKS
    _FALLBACKS[key] = _FALLBACKS.get(key, 0) + 1
    if first:
        logging.getLogger("dtg.ops.flash").warning(msg or (
            f"flash_attention: seq_len {s} not a multiple of block "
            f"({blk_q}, {blk_k}); falling back to the pure-XLA blockwise "
            "path (slower). Pad the sequence or adjust blk_q/blk_k."
        ))


def flash_attention(q, k, v, *, causal: bool = False,
                    blk_q: int | None = None, blk_k: int | None = None):
    """Fused attention, public layout (B, S, H, D) → (B, S, H, D).

    Softmax scale is 1/sqrt(D) over the *logical* head dim (padding lanes
    excluded). Differentiable via hand-written backward kernels.

    Block sizes: by default each of the three kernels (fwd, dq, dkv) takes
    its own entry from the autotune table (ops/autotune.py; 128x128 on a
    miss). Passing ``blk_q``/``blk_k`` pins ALL kernels to that
    one pair — the override the parity tests and the sweep use.
    """
    b, s, hn, d = q.shape
    if blk_q is not None or blk_k is not None:
        pin = (blk_q if blk_q is not None else DEFAULT_BLOCKS[0],
               blk_k if blk_k is not None else DEFAULT_BLOCKS[1])
        blocks = FlashBlocks(fwd=pin, dq=pin, dkv=pin)
    else:
        blocks = flash_blocks(b, hn, s, d, q.dtype, causal)
    if not all(supported(s, d, *pair) for pair in blocks):
        from distributed_tensorflow_guide_tpu.ops.attention import (
            blockwise_attention,
        )

        _note_fallback(s, d, *blocks.fwd)
        return blockwise_attention(q, k, v, causal=causal)
    scale = 1.0 / (d ** 0.5)
    dp = -(-d // LANE) * LANE

    def to_kernel(x):
        x = jnp.transpose(x, (0, 2, 1, 3))  # (B, H, S, D)
        if dp != d:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, dp - d)))
        return x

    out = _flash(to_kernel(q), to_kernel(k), to_kernel(v), scale, causal,
                 blocks)
    out = jnp.transpose(out, (0, 2, 1, 3))
    return out[..., :d]
