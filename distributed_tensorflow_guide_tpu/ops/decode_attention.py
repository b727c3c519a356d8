"""Pallas decode-attention: stream the KV cache past a 1-token query chunk.

The round-5 capture pinned serving decode at ~4% of the v5e's HBM roofline
(VERDICT #6 target >= 0.4). Decode attention is the purest bandwidth
workload in the repo — a C-token chunk (C = 1 in the scan loop) against a
``(B, H, max_len, hd)`` cache — and the XLA dense path pays for it twice:
the full fixed-size cache is read EVERY step (static shapes attend against
all ``max_len`` slots, written or not), and the ``(B, H, C, max_len)``
score/probability intermediates round-trip HBM. This kernel closes both
gaps:

* **length-aware grid**: the cache length (``index + C``) rides in as a
  scalar-prefetch operand, dead KV blocks map their BlockSpec index to the
  last live block (consecutive identical indices elide the DMA — the
  standard Pallas revisit trick) and skip their compute via ``pl.when`` —
  so a step at sequence position L reads ~L slots, not ``max_len``;
* **online softmax in VMEM**: one pass over live KV blocks carrying
  (m, l, acc) scratch — no score matrix ever hits HBM (the flash-forward
  algebra, specialized to a query chunk small enough to stay resident);
* **native int8 cache**: when the cache is quantized
  (``TransformerConfig.kv_dtype="int8"``), the kernel moves int8 blocks
  over the wire and dequantizes in-register — the per-slot-per-head f32
  scales fold into the score columns (k) and the probability columns (v),
  never into a materialized dequantized cache.

Layout contract (the caller is ``models/transformer.py _decode_attend``):
q arrives in the public ``(B, C, H, hd)`` layout; the cache collection is
stored KERNEL-layout ``(B, H, S, hd)`` (plus ``(B, H, 1, S)`` f32 scale
rows when quantized) so the kernel consumes it without a per-step
transpose — a transpose would copy the whole cache every step and hand the
bandwidth win straight back.

Block sizes resolve through the autotune table (``ops/autotune.py``,
kernel key ``decode_attend``; swept on chip by ``bench_flash_kernel.py
--tune``, tested fallback on a miss) — same CPU defaults-only hermeticity
as the flash kernels. On CPU the kernel runs via ``interpret=True`` when
explicitly requested; ``impl="auto"`` resolves to the dense path there so
tier-1 traces never contain a Pallas call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_guide_tpu.ops import autotune
from distributed_tensorflow_guide_tpu.ops.autotune import (
    DECODE_CHUNK_SUBLANES,
    DECODE_KERNEL,
    DECODE_MAX_CHUNK,
    DEFAULT_DECODE_BLK_K,
    PAGED_DECODE_KERNEL,
)
from distributed_tensorflow_guide_tpu.ops.flash_attention import (
    NEG_INF,
    _interpret,
    _vmem_scratch,
    _vmem_spec,
)

LANE = 128


# --------------------------------------------------------------------------
# int8 KV quantization (the write-path helper _decode_attend shares)
# --------------------------------------------------------------------------


def quantize_kv(x):
    """Per-vector symmetric int8: ``x`` (..., hd) -> (values int8 (..., hd),
    scales f32 (...,)). One scale per (batch, head, slot) vector — the
    granularity that keeps dequant a rank-1 broadcast in both the QK^T
    column direction and the AV probability direction. An all-zero vector
    maps to scale 1 (not 0) so dequant is always exact-zero, never 0/0."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    values = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return values.astype(jnp.int8), scale


# --------------------------------------------------------------------------
# block resolution (the ONLY lookup path — key construction lives here)
# --------------------------------------------------------------------------


def decode_blk_k_for(*, b: int, h: int, s: int, d: int, dtype,
                     platform: str | None = None) -> int:
    """The KV block edge a decode call site should use: the tuned table
    entry when one exists (key: s = max_len, dtype = CACHE dtype,
    causal=False), else the ``_default_blk_k`` cascade via the online
    front door (``ensure_tuned_online``: trace-safe; default no-op)."""
    hit = autotune.lookup(DECODE_KERNEL, b=b, h=h, s=s, d=d, dtype=dtype,
                          causal=False, platform=platform)
    if hit is not None:
        return hit[1]
    return autotune.ensure_tuned_online(
        DECODE_KERNEL, b=b, h=h, s=s, d=d, dtype=dtype, causal=False,
        platform=platform, fallback=lambda: _default_blk_k(s))


def ensure_decode_tuned(*, b: int, h: int, s: int, d: int, dtype,
                        iters: int = 20,
                        platform: str | None = None) -> int:
    """Sweep-and-record the decode KV edge for one (shape, cache-dtype)
    key — from the table when present (no re-sweep). Refused on CPU, same
    defaults-only contract as every autotune sweep."""
    blocks = autotune.ensure_tuned(
        DECODE_KERNEL, b=b, h=h, s=s, d=d, dtype=dtype, causal=False,
        iters=iters, platform=platform)
    return blocks[1]


def supported(s: int, blk_k: int, chunk: int = 1) -> bool:
    """Shapes the kernel handles: sublane-multiple KV edge dividing the
    cache length, and a q chunk within the unblocked-tile VMEM cap
    (``DECODE_MAX_CHUNK`` — the one grid cell holds the whole padded
    chunk plus its f32 score temporaries). Callers
    fall back to the dense kernel-layout path otherwise; for a long
    prefill chunk that is the DESIGNED route, not a degradation."""
    cp = -(-chunk // DECODE_CHUNK_SUBLANES) * DECODE_CHUNK_SUBLANES
    return (blk_k % 8 == 0 and s % blk_k == 0 and s >= blk_k
            and cp <= DECODE_MAX_CHUNK)


# --------------------------------------------------------------------------
# roofline byte model (bench_flash_kernel's decode rows)
# --------------------------------------------------------------------------


def cache_slot_bytes(head_dim: int, dtype) -> int:
    """Bytes ONE (slot, head) of the cache occupies: the K and V vectors
    at the CACHE dtype, plus the two per-slot f32 scales when quantized.
    The single definition both byte models scale up —
    ``models/generation.py decode_cache_bytes_per_step`` (whole-cache,
    per decode step) and :func:`decode_kernel_hbm_bytes` (one kernel
    call) — so the serving bench and the kernel-only bench can never
    disagree about the same cache."""
    import numpy as np

    io = np.dtype(dtype).itemsize
    scales = 8 if np.dtype(dtype) == np.dtype(np.int8) else 0
    return 2 * head_dim * io + scales


def decode_kernel_hbm_bytes(*, b: int, h: int, s: int, d: int, dtype,
                            chunk: int = 1, q_dtype=jnp.bfloat16,
                            effective_len: int | None = None) -> float:
    """Minimal algorithmic HBM traffic of ONE kernel call: the q chunk and
    the output written once, the LIVE slice of the cache (K and V, plus the
    f32 scale rows when the cache is int8) read once. ``effective_len``
    models the length-aware grid (block-rounded by the caller); the default
    is the full cache — the dense static-shape ceiling."""
    import numpy as np

    length = s if effective_len is None else min(int(effective_len), s)
    q_io = np.dtype(q_dtype).itemsize
    cache = b * h * length * cache_slot_bytes(d, dtype)
    qo = 2 * b * h * chunk * d * q_io
    return float(cache + qo)


# --------------------------------------------------------------------------
# the kernel
# --------------------------------------------------------------------------


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, *refs, scale: float,
                   blk_k: int, chunk: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    j = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # dead blocks (entirely past the written length) contribute nothing —
    # their BlockSpec index maps to the last live block so no DMA moved
    # either; this guard skips the compute.
    length = len_ref[0]

    @pl.when(j * blk_k < length)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)  # (Cp, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (blk_k, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (Cp, blk_k)
        if quantized:
            # k dequant folds into the score COLUMNS (scale is constant
            # along the contracted hd axis, so it factors out exactly)
            s = s * ks_ref[0, 0]  # (1, blk_k) broadcast
        cp = q.shape[0]
        # rows beyond the logical chunk are sublane padding: clamp their
        # position to the last real row (finite softmax, sliced off later)
        rows = jnp.minimum(
            jax.lax.broadcasted_iota(jnp.int32, (cp, blk_k), 0), chunk - 1)
        q_pos = (length - chunk) + rows
        k_pos = j * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, (cp, blk_k), 1)
        # key_pos <= q_pos enforces causality within the chunk AND hides
        # every not-yet-written slot (q_pos < length by construction) —
        # the same single predicate as the dense path
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        l_scr[:] = jnp.broadcast_to(l_prev * alpha
                                    + jnp.sum(p, axis=1, keepdims=True),
                                    l_scr.shape)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        if quantized:
            # v dequant folds into the probability COLUMNS — the
            # normalizer l above deliberately sums the UNscaled p
            p = p * vs_ref[0, 0]  # (1, blk_k) broadcast
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == n_kv - 1)
    def _():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


def decode_attention(q, cached_key, cached_value, index, *,
                     key_scale=None, value_scale=None,
                     blk_k: int | None = None):
    """Length-aware cache attention for one decode/prefill chunk.

    ``q``: (B, C, H, hd) public layout (C = 1 per decode step, C = prompt
    length at prefill). ``cached_key``/``cached_value``: (B, H, S, hd)
    kernel layout — int8 with ``key_scale``/``value_scale`` (B, H, 1, S)
    f32 when the cache is quantized, else the model dtype with no scales.
    ``index``: the (traced) write position of the chunk's first token; the
    chunk's k/v must already be written at [index, index + C) — this
    function only READS the cache. Returns (B, C, H, hd) in q's dtype.

    ``blk_k`` pins the KV block edge (what the parity tests and the sweep
    use); by default it resolves through the autotune table
    (:func:`decode_blk_k_for`).
    """
    B, C, H, hd = q.shape
    S = cached_key.shape[2]
    quantized = key_scale is not None
    if quantized != (value_scale is not None):
        raise ValueError("key_scale and value_scale must be given together")
    if blk_k is None:
        blk_k = decode_blk_k_for(b=B, h=H, s=S, d=hd,
                                 dtype=cached_key.dtype)
    if not supported(S, blk_k, C):
        raise ValueError(
            f"decode_attention: blk_k {blk_k} / chunk {C} unsupported for "
            f"cache length {S} (need a sublane multiple dividing S and a "
            f"chunk <= {DECODE_MAX_CHUNK}) — callers gate on supported() "
            "and fall back to the dense path")
    cp = -(-C // DECODE_CHUNK_SUBLANES) * DECODE_CHUNK_SUBLANES
    qk = jnp.transpose(q, (0, 2, 1, 3))  # (B, H, C, hd)
    if cp != C:
        qk = jnp.pad(qk, ((0, 0), (0, 0), (0, cp - C), (0, 0)))
    length = jnp.reshape(jnp.asarray(index + C, jnp.int32), (1,))
    scale = 1.0 / (hd ** 0.5)
    n_kv = S // blk_k

    def live_j(j, len_ref):
        # dead blocks revisit the last live block: consecutive identical
        # BlockSpec indices make the Pallas pipeline skip the DMA, which is
        # what turns the static grid into a length-aware read
        last_live = (len_ref[0] + blk_k - 1) // blk_k - 1
        return jnp.minimum(j, last_live)

    q_spec = _vmem_spec((1, 1, cp, hd), lambda b, h, j, L: (b, h, 0, 0))
    kv_spec = _vmem_spec((1, 1, blk_k, hd),
                         lambda b, h, j, L: (b, h, live_j(j, L), 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [qk, cached_key, cached_value]
    if quantized:
        sc_spec = _vmem_spec((1, 1, 1, blk_k),
                             lambda b, h, j, L: (b, h, 0, live_j(j, L)))
        in_specs += [sc_spec, sc_spec]
        operands += [key_scale, value_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, n_kv),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            _vmem_scratch((cp, LANE), jnp.float32),
            _vmem_scratch((cp, LANE), jnp.float32),
            _vmem_scratch((cp, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, scale=scale, blk_k=blk_k,
                               chunk=C, quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, cp, hd), q.dtype),
        interpret=_interpret(),
    )(length, *operands)
    return jnp.transpose(out[:, :, :C], (0, 2, 1, 3))


# --------------------------------------------------------------------------
# sweep/microbench runner (bench_flash_kernel decode rows, autotune sweep)
# --------------------------------------------------------------------------


def make_decode_runner(blk_k: int, *, b: int, h: int, s: int, d: int,
                       dtype, chunk: int = 1,
                       seed: int = 0):
    """A zero-arg callable running ONE decode-attention call at ``blk_k``
    on a FULL cache (length = s, the steady-state worst case the tuner
    should optimize) — the unit the sweep and the kernel-only microbench
    time. ``dtype`` is the CACHE dtype; int8 builds the quantized operands
    (values + per-slot scales), anything else a plain cache."""
    quantized = jnp.dtype(dtype) == jnp.dtype(jnp.int8)
    q_dtype = jnp.bfloat16 if quantized else dtype
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (b, chunk, h, d),
                          jnp.float32).astype(q_dtype)
    kf = jax.random.normal(keys[1], (b, h, s, d), jnp.float32)
    vf = jax.random.normal(keys[2], (b, h, s, d), jnp.float32)
    if quantized:
        k8, ks = quantize_kv(kf)
        v8, vs = quantize_kv(vf)
        ops = (q, k8, v8, ks[:, :, None, :], vs[:, :, None, :])

        def call(q, k8, v8, ks, vs):
            return decode_attention(q, k8, v8, s - chunk, key_scale=ks,
                                    value_scale=vs, blk_k=blk_k)
    else:
        ops = (q, kf.astype(dtype), vf.astype(dtype))

        def call(q, k, v):
            return decode_attention(q, k, v, s - chunk, blk_k=blk_k)

    f = jax.jit(call)
    return lambda: f(*ops)


# --------------------------------------------------------------------------
# paged variant: the cache is a block POOL, reads ride a block table
# --------------------------------------------------------------------------
#
# The serve engine (serve/engine.py) keeps one pool of fixed-size blocks
# per layer shared by every resident request (serve/paged_cache.py); a
# request's cache is whatever blocks its (blocks_per_seq,) table row names.
# The kernel below is the same online-softmax stream as _decode_kernel with
# three changes: the length is PER-REQUEST ((B,) — continuous batching puts
# every slot at its own position), and the KV BlockSpec index map resolves
# physical blocks through the table — both ride in as scalar-prefetch
# operands, so dead blocks still collapse onto the last live physical
# block and elide their DMA exactly as in the contiguous kernel. blk_k
# must divide the pool block size: a tile never straddles two physical
# blocks, which is what keeps the index map a pure table lookup. And a grid
# step carries one key tile of ALL of a slot's pool heads (as many as fit
# VMEM: paged_heads_per_step), each with the query heads that share it: a
# step costs a fraction of a microsecond whatever it computes, and one head
# against one tile of a short context computes next to nothing (PERF.md,
# PR 31), while a block's heads are one contiguous slab of the pool.
#
# The pool is stored ``(num_blocks, Hkv, hd, block_size)``: a block's slots
# lie along the LANE axis and the head dim along the sublanes. That is the
# device's choice, not taste: with a head dim under 128 the TPU runtime
# keeps a ``(N, H, block_size, hd)`` array with the block axis minor anyway
# (a minor axis of 64 would leave half of every (8, 128) tile empty), while
# a Pallas call takes its operands row-major — so a pool declared the other
# way round is relaid out, whole, in front of every call (ROADMAP S8: 44% of
# a serving launch). Declared as it lies, the kernel reads it in place: the
# key tile arrives already transposed (``s = q @ kT``) and the value tile is
# contracted over its lanes (``acc += p @ vT^T``).


def paged_decode_blk_k_for(*, b: int, h: int, s: int, d: int, dtype,
                           block_size: int,
                           platform: str | None = None) -> int:
    """KV edge for the paged kernel: the ``decode_paged`` table entry when
    one exists AND tiles the pool block (:func:`_tiles_block`), else the
    largest tested default that does (``_default_paged_blk_k``, via the
    online front door; a stale result that does not is re-clipped to
    it)."""
    hit = autotune.lookup(PAGED_DECODE_KERNEL, b=b, h=h, s=s, d=d,
                          dtype=dtype, causal=False, platform=platform)
    if hit is not None and _tiles_block(block_size, hit[1]):
        return hit[1]
    blk = autotune.ensure_tuned_online(
        PAGED_DECODE_KERNEL, b=b, h=h, s=s, d=d, dtype=dtype, causal=False,
        block_size=block_size, platform=platform,
        fallback=lambda: _default_paged_blk_k(block_size))
    return (blk if _tiles_block(block_size, blk)
            else _default_paged_blk_k(block_size))


def ensure_paged_decode_tuned(*, b: int, h: int, s: int, d: int, dtype,
                              block_size: int, iters: int = 20,
                              platform: str | None = None) -> int:
    """Sweep-and-record the paged KV edge (refused on CPU, same contract
    as every sweep). Candidates that do not divide the pool block size
    are rejected inside ``measure`` so the shared sweep machinery skips
    them as failed candidates."""

    def measure(kern, blocks):
        if not _tiles_block(block_size, blocks[1]):
            raise ValueError(
                f"blk_k {blocks[1]} does not tile block_size "
                f"{block_size}")
        fn = make_paged_decode_runner(blocks[1], b=b, h=h, s=s, d=d,
                                      dtype=dtype, block_size=block_size)
        return autotune.measure_runner(fn, iters=iters)

    blocks = autotune.ensure_tuned(
        PAGED_DECODE_KERNEL, b=b, h=h, s=s, d=d, dtype=dtype, causal=False,
        iters=iters, measure=measure, platform=platform)
    return blocks[1]


def paged_supported(s: int, block_size: int, blk_k: int,
                    chunk: int = 1) -> bool:
    """:func:`supported` plus the pool constraints: the KV edge tiles the
    physical block (:func:`_tiles_block`) and blocks tile the view."""
    return (supported(s, blk_k, chunk) and _tiles_block(block_size, blk_k)
            and s % block_size == 0)


def _tiles_block(block_size: int, blk_k: int) -> bool:
    """A kernel tile never straddles two physical blocks (``blk_k`` divides
    the block size), and its ``blk_k`` slots are a LANE extent of the
    stored pool: a tile smaller than a block is a multiple of 128 lanes,
    else it is the whole block."""
    return (block_size % blk_k == 0
            and (blk_k == block_size or blk_k % LANE == 0))


def _default_paged_blk_k(block_size: int) -> int:
    """The paged cascade: the largest tested default edge that tiles the
    block, else the block itself (sweep-free, like ``_default_blk_k``)."""
    for cand in (DEFAULT_DECODE_BLK_K, 128):
        if cand < block_size and _tiles_block(block_size, cand):
            return cand
    return block_size


def _vmem_tile_bytes(rows: int, lanes: int, dtype) -> int:
    """What a ``(rows, lanes)`` array of ``dtype`` takes in VMEM: lanes in
    whole groups of 128, rows in whole sublane groups (8 of four bytes, 16
    of two, 32 of one)."""
    import numpy as np

    io = np.dtype(dtype).itemsize
    sub = 8 * max(1, 4 // io)
    return (-(-rows // sub) * sub) * (-(-lanes // LANE) * LANE) * io


def paged_heads_per_step(kv_heads: int, *, group: int, chunk: int, hd: int,
                         blk_k: int, dtype, q_dtype,
                         budget: int | None = None) -> int:
    """How many pool heads one grid step of the paged kernel carries: the
    largest divisor of ``kv_heads`` whose working set fits ``budget``
    (``autotune.VMEM_BUDGET_BYTES`` unless given), never under one.

    A pool head's share of a step: its key and value tiles (and the int8
    cache's two scale rows) and its group's q and o rows, each
    double-buffered by the pipeline; the float32 softmax state ``m``,
    ``l`` (lane-wide) and ``acc``; and the body's float32 temporaries
    (scores, probabilities, the select; the value tile converted). At a
    decode step that is ~0.2 MB a head, so every head of either serving
    model rides in one step; a 128-token prefill chunk is ~0.6 MB a head
    (2.2 MB under a group of four) and takes a divisor of the heads."""
    import numpy as np

    f32 = jnp.float32
    rows = -(-group * chunk // DECODE_CHUNK_SUBLANES) * DECODE_CHUNK_SUBLANES
    kv = 2 * _vmem_tile_bytes(hd, blk_k, dtype)
    if np.dtype(dtype) == np.dtype(np.int8):
        kv += 2 * _vmem_tile_bytes(1, blk_k, f32)
    qo = 2 * _vmem_tile_bytes(rows, hd, q_dtype)
    scratch = (2 * _vmem_tile_bytes(rows, LANE, f32)
               + _vmem_tile_bytes(rows, hd, f32))
    body = (3 * _vmem_tile_bytes(rows, blk_k, f32)
            + 2 * _vmem_tile_bytes(hd, blk_k, f32))
    head = 2 * (kv + qo) + scratch + body
    if budget is None:
        budget = autotune.VMEM_BUDGET_BYTES
    fits = [hb for hb in range(1, kv_heads + 1)
            if kv_heads % hb == 0 and hb * head <= budget]
    return max(fits, default=1)


def _paged_decode_kernel(len_ref, bt_ref, q_ref, k_ref, v_ref, *refs,
                         scale: float, blk_k: int, chunk: int, rows: int,
                         quantized: bool, window: int | None = None):
    """One grid step: ``hb`` pool heads of one slot against one key tile.

    ``q_ref``/``o_ref`` (1, hb, R, hd): a pool head's query rows on the
    sublanes, row ``r < rows`` being query head ``r // chunk`` of the
    head's group at chunk position ``r % chunk`` (``rows = group *
    chunk``; rows from there to ``R`` are padding). ``k_ref``/``v_ref``
    (1, hb, hd, blk_k) of the pool as stored, the int8 cache's scale rows
    (1, hb, 1, blk_k); scratch ``m``, ``l`` (hb, R, LANE), ``acc``
    (hb, R, hd), float32. The online softmax of the one-head kernel over a
    leading head axis. ``window``: a query sees the ``window`` keys up to
    and including its own position and none before them (None: all)."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    j = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]  # per-request live length (continuous batching)

    live = j * blk_k < length
    if window is not None:
        # a tile wholly before the chunk's first query's window adds nothing
        live &= (j + 1) * blk_k > length - chunk - window + 1

    @pl.when(live)
    def _():
        q = q_ref[0]  # (hb, R, hd)
        kT = k_ref[0]  # (hb, hd, blk_k): slots on lanes
        # the stored operands go into the MXU as they are (an int8 tile as
        # q's type, which holds every int8 value): the products of two
        # bfloat16 values are exact in the float32 they accumulate in
        dt = q.dtype if quantized else jnp.promote_types(q.dtype, kT.dtype)
        s = jax.lax.dot_general(
            q.astype(dt), kT.astype(dt), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale  # (hb, R, blk_k)
        if quantized:
            s = s * ks_ref[0]  # (hb, 1, blk_k) broadcast
        rp = q.shape[1]
        # padding rows take the last real row's position (finite softmax,
        # sliced off by the caller); under grouped heads a row's chunk
        # position is its index within its query head's ``chunk`` rows
        r = jnp.minimum(
            jax.lax.broadcasted_iota(jnp.int32, (rp, blk_k), 0), rows - 1)
        if rows != chunk:
            r = jax.lax.rem(r, chunk)
        q_pos = (length - chunk) + r
        k_pos = j * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, (rp, blk_k), 1)
        seen = k_pos <= q_pos
        if window is not None:
            seen &= k_pos > q_pos - window
        s = jnp.where(seen[None], s, NEG_INF)
        m_prev = m_scr[:, :, :1]
        l_prev = l_scr[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        l_scr[...] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=2, keepdims=True), l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        if quantized:
            p = p * vs_ref[0]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (hb, R, hd): the value tile contracted over its lanes

    @pl.when(j == n_kv - 1)
    def _():
        l = l_scr[:, :, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)


def paged_decode_attention(q, key_pool, value_pool, block_tables, lengths,
                           *, key_scale_pool=None, value_scale_pool=None,
                           block_size: int, blk_k: int | None = None,
                           scale: float | None = None,
                           window: int | None = None):
    """Length-aware cache attention reading a paged pool through tables.

    ``q``: (B, C, H, hd) public layout. ``key_pool``/``value_pool``:
    (num_blocks, Hkv, hd, block_size) — the ONE pool layout, a block's
    slots on the lane axis as the device stores them (the section comment
    above says why; int8 with (num_blocks, Hkv, 1, block_size) f32 scale
    pools when quantized); ``Hkv`` divides ``H`` and ``H // Hkv`` query
    heads share a pool head.
    ``block_tables``: (B, blocks_per_seq) int32 physical block ids.
    ``lengths``: (B,) int32 per-request live lengths AFTER the chunk's
    write — request b's chunk occupies logical positions
    [lengths[b] - C, lengths[b]). Only reads; the caller writes the
    chunk first (models/transformer.py _paged_decode_attend, through
    serve/paged_cache.py write_chunk). Returns (B, C, H, hd) in q's dtype.

    The grid is ``(B, Hkv // hb, n_kv)``: one step reads one key tile of
    ``hb`` pool heads, all of them where they fit
    (:func:`paged_heads_per_step`), once for every query head that shares
    them.
    """
    B, C, H, hd = q.shape
    n_blk = block_tables.shape[1]
    S = n_blk * block_size
    quantized = key_scale_pool is not None
    if quantized != (value_scale_pool is not None):
        raise ValueError("key/value scale pools must be given together")
    if blk_k is None:
        blk_k = paged_decode_blk_k_for(b=B, h=H, s=S, d=hd,
                                       dtype=key_pool.dtype,
                                       block_size=block_size)
    if not paged_supported(S, block_size, blk_k, C):
        raise ValueError(
            f"paged_decode_attention: blk_k {blk_k} / chunk {C} "
            f"unsupported for view length {S}, block_size {block_size} — "
            "callers gate on paged_supported() and fall back to the "
            "gathered dense path")
    # grouped heads: the pool holds H // group key/value heads, and the
    # ``group`` query heads of pool head g, g * group + i, ride together
    # on the sublanes of its step
    kv_heads = key_pool.shape[1]
    group = H // kv_heads
    if group < 1 or group * kv_heads != H:
        raise ValueError(
            f"{H} query heads are no multiple of the pool's "
            f"{kv_heads} key/value heads")
    rows = group * C
    rp = -(-rows // DECODE_CHUNK_SUBLANES) * DECODE_CHUNK_SUBLANES
    qk = jnp.transpose(q.reshape(B, C, kv_heads, group, hd),
                       (0, 2, 3, 1, 4)).reshape(B, kv_heads, rows, hd)
    if rp != rows:
        qk = jnp.pad(qk, ((0, 0), (0, 0), (0, rp - rows), (0, 0)))
    lengths = jnp.maximum(jnp.asarray(lengths, jnp.int32), 1)
    tables = jnp.asarray(block_tables, jnp.int32)
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    n_kv = S // blk_k
    sub = block_size // blk_k  # kernel tiles per physical block
    hb = paged_heads_per_step(kv_heads, group=group, chunk=C, hd=hd,
                              blk_k=blk_k, dtype=key_pool.dtype,
                              q_dtype=q.dtype)

    def kv_map(b, g, j, len_ref, bt_ref):
        # keys, values and scale rows alike: a tile is ``blk_k`` lanes of
        # ``hb`` heads of one block. Dead tiles map to the last live one,
        # the revisit trick of the contiguous kernel: consecutive
        # identical (block, offset) pairs elide the DMA
        last_live = (len_ref[b] + blk_k - 1) // blk_k - 1
        lj = jnp.minimum(j, last_live)
        return (bt_ref[b, lj // sub], g, 0, lj % sub)

    q_spec = _vmem_spec((1, hb, rp, hd),
                        lambda b, g, j, L, T: (b, g, 0, 0))
    kv_spec = _vmem_spec((1, hb, hd, blk_k), kv_map)
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [qk, key_pool, value_pool]
    if quantized:
        sc_spec = _vmem_spec((1, hb, 1, blk_k), kv_map)
        in_specs += [sc_spec, sc_spec]
        operands += [key_scale_pool, value_scale_pool]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, kv_heads // hb, n_kv),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            _vmem_scratch((hb, rp, LANE), jnp.float32),
            _vmem_scratch((hb, rp, LANE), jnp.float32),
            _vmem_scratch((hb, rp, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               blk_k=blk_k, chunk=C, rows=rows,
                               quantized=quantized, window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kv_heads, rp, hd), q.dtype),
        interpret=_interpret(),
    )(lengths, tables, *operands)
    out = out[:, :, :rows].reshape(B, kv_heads, group, C, hd)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, C, H, hd)


def _paged_write_kernel(phys_ref, first_ref, new_ref, pool_ref, out_ref, *,
                        chunk: int):
    # slot s of this block is position first + s of the row's chunk
    at = first_ref[pl.program_id(0)] + jax.lax.broadcasted_iota(
        jnp.int32, out_ref.shape, 3)
    out_ref[...] = jnp.where((at >= 0) & (at < chunk), new_ref[...],
                             pool_ref[...])


def paged_write(pool, new, phys, first, *, chunk: int):
    """The write that goes with :func:`paged_decode_attention`: put a
    chunk's slots into the blocks they fall in, in the pool's own buffer.

    ``pool``: (num_blocks, H, d, block_size), aliased to the result.
    ``phys``/``first``: (n,) int32, one entry a touched block: its id,
    and where its first slot lies in its row's chunk of ``chunk``
    positions (negative when the chunk starts inside the block). ``new``:
    (n, H, d, block_size), the chunk's values at each block's slots
    (whatever elsewhere), or (n, H, d, 1) for a chunk of one position,
    broadcast over the block. One grid step a block: read it, select,
    write it back; blocks the grid does not visit keep their contents
    because the buffer is the same. ``serve/paged_cache.py write_chunk``
    prepares the operands and holds the semantics.

    Two entries may name the same block only where its contents do not
    matter (the trash block): a step's read may run before the step
    before it has written back.

    The call sits in a scope of its own, so a trace names it
    ``paged_write.<n>`` and not after the attention method around it: the
    benchmark finds the attention kernel by that method's name.
    """
    n = phys.shape[0]
    block = (1,) + pool.shape[1:]
    pool_spec = _vmem_spec(block, lambda i, P, F: (P[i], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[
            _vmem_spec((1,) + new.shape[1:], lambda i, P, F: (i, 0, 0, 0)),
            pool_spec,
        ],
        out_specs=pool_spec,
    )
    with jax.named_scope("paged_write"):
        return pl.pallas_call(
            functools.partial(_paged_write_kernel, chunk=chunk),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            input_output_aliases={3: 0},  # the pool: phys, first, new, pool
            interpret=_interpret(),
        )(jnp.asarray(phys, jnp.int32), jnp.asarray(first, jnp.int32), new,
          pool)


def paged_write_fits(block: tuple[int, ...], dtype) -> bool:
    """Whether :func:`paged_write`'s grid step fits the VMEM a decode
    kernel may use: a ``block`` (H, d, block_size) of the pool read, one
    written and one of new values, each double-buffered. Where it does not,
    the caller keeps ``write_chunk``'s loop."""
    import math

    import numpy as np

    return (6 * math.prod(block) * np.dtype(dtype).itemsize
            <= autotune.VMEM_BUDGET_BYTES)


def make_paged_decode_runner(blk_k: int, *, b: int, h: int, s: int,
                             d: int, dtype, block_size: int,
                             chunk: int = 1, seed: int = 0,
                             kv_heads: int | None = None):
    """Zero-arg runner for ONE paged decode-attention call: a full pool
    (every request at length s — the steady-state worst case), identity
    block tables. The unit the paged sweep and the kernel microbench
    time. ``kv_heads`` is the pool's head count where ``h`` query heads
    share fewer (as many as query heads unless given)."""
    kv_heads = h if kv_heads is None else kv_heads
    quantized = jnp.dtype(dtype) == jnp.dtype(jnp.int8)
    q_dtype = jnp.bfloat16 if quantized else dtype
    n_blk = s // block_size
    num_blocks = b * n_blk + 1  # +1: the trash block convention
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (b, chunk, h, d),
                          jnp.float32).astype(q_dtype)
    kf = jax.random.normal(keys[1], (num_blocks, kv_heads, block_size, d),
                           jnp.float32)
    vf = jax.random.normal(keys[2], (num_blocks, kv_heads, block_size, d),
                           jnp.float32)
    tables = jnp.arange(b * n_blk, dtype=jnp.int32).reshape(b, n_blk)
    lengths = jnp.full((b,), s, jnp.int32)

    def as_pool(x):  # (N, H, bs, hd) as drawn -> the pool's (N, H, hd, bs)
        return jnp.swapaxes(x, 2, 3)

    if quantized:
        k8, ks = quantize_kv(kf)
        v8, vs = quantize_kv(vf)
        ops = (q, as_pool(k8), as_pool(v8), ks[:, :, None, :],
               vs[:, :, None, :])

        def call(q, k8, v8, ks, vs):
            return paged_decode_attention(
                q, k8, v8, tables, lengths, key_scale_pool=ks,
                value_scale_pool=vs, block_size=block_size, blk_k=blk_k)
    else:
        ops = (q, as_pool(kf.astype(dtype)), as_pool(vf.astype(dtype)))

        def call(q, k, v):
            return paged_decode_attention(
                q, k, v, tables, lengths, block_size=block_size,
                blk_k=blk_k)

    f = jax.jit(call)
    return lambda: f(*ops)


# --------------------------------------------------------------------------
# static cost model (analysis/cost.py kernel registry)
# --------------------------------------------------------------------------


def _block_dims(block_mapping) -> tuple[int, ...]:
    """A BlockSpec's extents as plain ints (this jax wraps each blocked
    dimension in a ``Blocked``)."""
    return tuple(int(getattr(d, "block_size", d))
                 for d in block_mapping.block_shape)


def _attn_kernel_cost(eqn, *, slots_axis: int = 2):
    """Cost of one (paged or dense) decode-attention ``pallas_call`` for
    the static auditor — derived from the equation's grid and BlockSpecs,
    with the HBM side delegated to :func:`decode_kernel_hbm_bytes` so the
    auditor and the kernel microbench price the same call identically.
    The grid is (slots, steps over the key/value heads, key tiles) and a
    block's axis 1 says how many heads a step carries: one of the
    contiguous cache's, ``hb`` of the paged pool's, whose q block holds a
    whole group's rows for each, so the keys are priced once a pool head.
    The q/out rows are counted at their sublane-PADDED size (the BlockSpec
    is all the jaxpr knows); the dense static-shape ceiling, like the
    closed form's default. ``slots_axis`` is where the key block keeps
    its ``blk_k`` slots: 2 of the contiguous cache's (1, 1, blk_k, hd), 3
    of the paged pool's (1, hb, hd, blk_k)."""
    gm = eqn.params["grid_mapping"]
    b, h_steps, n_kv = (int(g) for g in gm.grid)
    bms = list(gm.block_mappings)
    _, hb, rows, hd = _block_dims(bms[0])                 # q block
    blk_k = _block_dims(bms[1])[slots_axis]               # k block
    h, s = h_steps * hb, n_kv * blk_k
    k_aval = eqn.invars[gm.num_index_operands + 1].aval
    q_aval = eqn.outvars[0].aval
    total = decode_kernel_hbm_bytes(
        b=b, h=h, s=s, d=hd, dtype=k_aval.dtype, chunk=rows,
        q_dtype=q_aval.dtype)
    import numpy as np

    qo_half = b * h * rows * hd * np.dtype(q_aval.dtype).itemsize
    return {
        # qk^T + softmax-weighted pv: two (rows, blk_k, hd) contractions
        # a head and key tile over the full static grid
        "flops": 4.0 * b * h * s * rows * hd,
        "read": total - qo_half,
        "write": float(qo_half),
    }


def _paged_write_cost(eqn):
    """Cost of one :func:`paged_write` call: every grid step reads a pool
    block and its new values and writes the block back; the rest of the
    aliased pool is not touched (the auditor's fallback would charge the
    whole leaf, read and written)."""
    import math

    import numpy as np

    gm = eqn.params["grid_mapping"]
    n = int(gm.grid[0])
    new, block, _ = (math.prod(_block_dims(bm))
                     for bm in gm.block_mappings)
    io = np.dtype(eqn.outvars[0].aval.dtype).itemsize
    return {"flops": 0.0, "read": float(n * (new + block) * io),
            "write": float(n * block * io)}


def _register_kernel_costs():
    # analysis.cost is jax-free at import; the dependency edge ops ->
    # analysis is acyclic (analysis never imports ops at module scope)
    from distributed_tensorflow_guide_tpu.analysis.cost import (
        register_kernel_cost,
    )

    register_kernel_cost("_decode_kernel", _attn_kernel_cost)
    register_kernel_cost("_paged_decode_kernel",
                         functools.partial(_attn_kernel_cost, slots_axis=3))
    register_kernel_cost("_paged_write_kernel", _paged_write_cost)


_register_kernel_costs()


def _default_blk_k(s: int) -> int:
    """The tested-default cascade: the largest default edge that divides
    ``s`` (the cache length, or the pool block size on the paged path).
    Sweep-free and lookup-free — the online front door's fallback must
    never re-enter the resolution path."""
    for cand in (DEFAULT_DECODE_BLK_K, 128, 64, 32, 16, 8):
        if cand <= s and s % cand == 0:
            return cand
    return s
