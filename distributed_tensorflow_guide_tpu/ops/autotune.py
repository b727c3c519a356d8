"""Kernel block-size autotuning + roofline accounting for the Pallas tier.

The round-5 on-chip battery showed the hand-written kernels are the repo's
biggest perf liability (flash training MFU 0.155 at seq 1024 vs 0.35–0.40
dense; the ring carry kernel at 0.157–0.487x of the XLA path it was built
to beat). Both FlashAttention (Dao et al. 2022) and Ring Attention (Liu et
al. 2023) report these kernels are block-size- and memory-traffic-
sensitive — yet every call site hardcoded ``blk_q = blk_k = 128``. This
module removes the hardcode:

* a **persistent tuning table** keyed on (kernel, shape, dtype, platform):
  ``blocks_for`` is what call sites ask (never sweeps, never writes — the
  ``DEFAULT_BLOCKS`` fallback on a miss, which is tested for correctness
  and is no tuned choice: see ``resolution_stats``); ``ensure_tuned``
  sweeps the candidate grid ON CHIP and records the winner (exact-shape
  entry plus a batch/head-generic one, so one capture serves nearby
  batches). The table git tracks (``autotune_table_v1.json``) holds the
  v5e sweep at (8, 16, 1024, 64, bfloat16, causal);
* a **per-kernel sweep harness**: the four kernels (forward, dq, dkv,
  ring carry-step) are measured SEPARATELY — their arithmetic
  intensities differ (2/3/4 MXU passes per block pair), so one shared
  block choice was never right;
* the **FLOP / HBM roofline models** the kernel-only microbench
  (benchmarks/bench_flash_kernel.py) reports fractions against.

Hermeticity contract (tier-1 CI): under ``JAX_PLATFORMS=cpu`` this module
is a *defaults-only path* — it never reads or writes the table file and
refuses to sweep (interpret-mode timings are meaningless, and a stray
table on the host must not change which kernel programs CI traces).
Pinned by tests/test_autotune.py.
"""

from __future__ import annotations

import json
import logging
import os
import threading
from pathlib import Path
from typing import Callable, NamedTuple

log = logging.getLogger("dtg.ops.autotune")

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv", "carry_step",
           "decode_attend", "decode_paged")

# The tested fallback every call site gets on a table miss — the historical
# hardcode, now the one definition it reduces to.
DEFAULT_BLOCKS: tuple[int, int] = (128, 128)

# --- decode attention (ops/decode_attention.py) ----------------------------
# Same table, same platform keying, same CPU defaults-only contract. The
# decode kernel streams the KV cache past a 1-token query chunk, so its
# only real tuning axis is the KV block edge (blk_k); the Q edge is pinned
# at the sublane-padded chunk (DECODE_CHUNK_SUBLANES). Entries key on
# s = max_len and dtype = the CACHE dtype (int8 entries are distinct from
# bf16 ones — the bandwidth/VMEM balance differs), causal=False (the
# length masking is runtime state, not a block-liveness regime).
DECODE_KERNEL = "decode_attend"
# The paged variant (serve/paged_cache.py pools): same grid, same tuning
# axis, but the KV edge must additionally DIVIDE the pool block size —
# a kernel tile never straddles two physical blocks, so the block-table
# index map stays a pure block-id lookup. Distinct table key: the tuned
# edge for a contiguous (B, H, S, hd) cache need not be the winner when
# every tile rides through an indirection.
PAGED_DECODE_KERNEL = "decode_paged"
DECODE_CHUNK_SUBLANES = 8  # single-token q chunks are padded to one sublane

# Largest q chunk the kernel accepts: the q tile is NOT blocked (one grid
# cell holds the whole padded chunk + its (chunk, blk_k) f32 score
# temporaries), so an unbounded prefill chunk could exceed VMEM at serve
# time even though the chunk=1 sweep passed. Chunks past this route to the
# dense path (prefill is one big MXU matmul — bandwidth is not its
# bottleneck); decode steps (1) and speculative verify chunks (G+1) sit
# far below it. The VMEM candidate filter charges THIS worst case, not
# the 8-row decode tile, so a tuned blk_k is safe for every admitted
# chunk.
DECODE_MAX_CHUNK = 128

# Tested fallback KV edge on a table miss, clipped by divisibility in
# decode_attention.decode_blk_k_for (a 32-slot test cache can't take 256).
DEFAULT_DECODE_BLK_K = 256

# --- chunked fused cross-entropy (ops/fused_ce.py) -------------------------
# Same table, same platform keying, same CPU defaults-only contract — but a
# ONE-dimensional tuning axis: the vocab-chunk width of the fused CE loop.
# The key reuses _key with (b=N tokens, h=0, s=V_local, d=d_model); entries
# store {"chunk": c}.
CE_KERNEL = "fused_ce"

# Tested static fallback: at GPT-2's (N=16k, V=50304) shape an 8k-wide f32
# score tile is (N, 8192) per chunk — comfortably inside the per-core VMEM
# working set for the microbatch sizes the pipeline feeds the head, and
# seven chunks keep the python-unrolled loop's trace cost trivial.
DEFAULT_CE_CHUNK = 8192

# Sweep grid for --tune (bench_fused_ce.py): lane-multiple widths from one
# MXU tile column block up to half the GPT-2 vocab.
CE_CHUNK_CANDIDATES = (1024, 2048, 4096, 8192, 16384, 32768)

# --- bucketed DP all-reduce (parallel/overlap.py) --------------------------
# Same table, same platform keying, same CPU defaults-only contract — the
# tuning axis is the gradient BUCKET byte budget of the overlapped
# data-parallel backward. The key reuses _key with (b=world, h=0,
# s=param MiB, d=0); entries store {"bucket_bytes": x}.
BUCKET_KERNEL = "dp_bucket"

# Tested static fallback: 4 MiB per bucket. Big enough that each bucket's
# ring all-reduce amortizes its latency on ICI, small enough that the
# first reduction launches well before the backward finishes (PyTorch
# DDP's default is 25 MB against NCCL launch overheads; ICI collective
# launch is far cheaper, so the sweet spot sits lower — the sweep decides
# per model/world on chip).
DEFAULT_BUCKET_BYTES = 4 * 1024 * 1024

# Sweep grid for bench_comm_overlap --tune: 1 MiB (fine-grained, maximum
# overlap surface) up to 32 MiB (few launches, near-monolithic).
BUCKET_BYTES_CANDIDATES = tuple((1 << 20) * m for m in (1, 2, 4, 8, 16, 32))

LANE = 128  # TPU lane width; block edges must be sublane (8) multiples

# Block-edge candidates for the sweep, filtered per shape by divisibility
# and the VMEM working-set budget below.
CANDIDATE_EDGES = (64, 128, 256, 512, 1024)

# VMEM. A v5e TensorCore has 128 MiB of it; a Mosaic kernel gets what its
# scoped limit allows, 16 MiB unless the call's compiler parameters carry a
# ``vmem_limit_bytes`` (no call in ops/ does: asking the four flash calls
# for 32 MiB cost the gpt2-medium train step 33 MB more of HBM, PR 27). The
# filter below charges a candidate its modelled per-grid-cell working set
# (kernel_vmem_bytes): the flash kernels against the whole scoped limit,
# their model being held to the compiler's verdicts; the decode kernels
# against half of it, the other half headroom for what their model does not
# see. The model is an estimate either way, so the sweep lets a candidate
# that the compiler refuses cost that candidate alone.
DEFAULT_SCOPED_VMEM_BYTES = 16 * 1024 * 1024
VMEM_BUDGET_BYTES = DEFAULT_SCOPED_VMEM_BYTES // 2


def vmem_budget_bytes(kernel: str) -> int:
    """What the candidate filter lets one grid cell of ``kernel`` use."""
    if kernel in (DECODE_KERNEL, PAGED_DECODE_KERNEL):
        return VMEM_BUDGET_BYTES
    return DEFAULT_SCOPED_VMEM_BYTES


class FlashBlocks(NamedTuple):
    """Per-kernel (blk_q, blk_k) for one flash_attention call — the unit
    the custom_vjp carries as a static argument."""

    fwd: tuple[int, int]
    dq: tuple[int, int]
    dkv: tuple[int, int]


_lock = threading.Lock()
_mem: dict[str, dict] = {}  # in-memory table; file merged in lazily
_loaded_from: str | None = None


def _platform(platform: str | None = None) -> str:
    """The table's platform key. On TPU this includes the device_kind
    (e.g. ``tpu:tpu-v5-lite``) — block winners are a VMEM/MXU-balance
    property of the GENERATION, so a v5e-tuned table must miss (and fall
    back to defaults / re-sweep) on a v4/v6e reading the same table,
    same keying discipline as benchmarks/common.py's peak tables."""
    if platform is not None:
        return platform
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        return backend
    kind = jax.devices()[0].device_kind.lower().replace(" ", "-")
    return f"tpu:{kind}"


#: The table git tracks, next to this module. Absent means the defaults.
TRACKED_TABLE = Path(__file__).resolve().parent / "autotune_table_v1.json"


def table_path() -> Path:
    """Where the table lives: $DTG_AUTOTUNE_TABLE, else the file git
    tracks next to this module — never the home directory: which kernel
    programs a chip compiles is decided by the checkout, so two machines
    on one commit compile the same thing. A sweep writes its winners
    there; committing the file is what makes them the defaults."""
    env = os.environ.get("DTG_AUTOTUNE_TABLE")
    return Path(env) if env else TRACKED_TABLE


def _dtype_name(dtype) -> str:
    import numpy as np

    try:
        return np.dtype(dtype).name
    except TypeError:
        return str(dtype)


def _key(kernel: str, b: int, h: int, s: int, d: int, dtype: str,
         causal: bool, platform: str) -> str:
    # causal is part of the key: the masking regime changes each
    # candidate's live-block count and therefore its winner — blocks
    # tuned under one regime must not silently govern the other
    mode = "causal" if causal else "full"
    return f"{kernel}|b{b}|h{h}|s{s}|d{d}|{dtype}|{mode}|{platform}"


def reset() -> None:
    """Drop the in-memory table AND the online-tune session state (tests;
    the next TPU lookup reloads)."""
    global _loaded_from, _online_override, _online_spent_s
    with _lock:
        _mem.clear()
        _resolved.clear()
        _loaded_from = None
        _online_override = None
        _online_attempted.clear()
        _online_spent_s = 0.0


def _maybe_load(platform: str) -> None:
    """Merge the persisted table into memory — never on CPU (hermeticity
    contract in the module docstring)."""
    global _loaded_from
    if platform == "cpu":
        return
    path = table_path()
    with _lock:
        if _loaded_from == str(path):
            return
        try:
            data = json.loads(path.read_text())
        except (OSError, ValueError):
            data = {}
        for k, v in data.items():
            _mem.setdefault(k, v)  # in-memory entries win
        _loaded_from = str(path)


def _valid(blocks: tuple[int, int], s: int) -> bool:
    bq, bk = blocks
    return (bq > 0 and bk > 0 and bq % 8 == 0 and bk % 8 == 0
            and s % bq == 0 and s % bk == 0)


def _resolve(kernel: str, *, b: int, h: int, s: int, d: int, dtype,
             causal: bool = True, platform: str | None = None
             ) -> tuple[tuple[int, int] | None, str, str]:
    """(tuned blocks or None, where they came from, the exact key). Tries
    the exact shape ("table"), then the batch/head-generic entry the sweep
    also records ("generic"); a miss is (None, "default", key). Entries
    that no longer divide the shape are ignored (stale-table safety)."""
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r} (one of {KERNELS})")
    plat = _platform(platform)
    _maybe_load(plat)
    dt = _dtype_name(dtype)
    exact = _key(kernel, b, h, s, d, dt, causal, plat)
    for source, key in (("table", exact),
                        ("generic", _key(kernel, 0, 0, s, d, dt, causal,
                                         plat))):
        ent = _mem.get(key)
        if ent:
            blocks = (int(ent["blk_q"]), int(ent["blk_k"]))
            if _valid(blocks, s):
                return blocks, source, exact
    return None, "default", exact


def lookup(kernel: str, *, b: int, h: int, s: int, d: int, dtype,
           causal: bool = True,
           platform: str | None = None) -> tuple[int, int] | None:
    """Tuned (blk_q, blk_k) for the key, or None (see :func:`_resolve`)."""
    return _resolve(kernel, b=b, h=h, s=s, d=d, dtype=dtype, causal=causal,
                    platform=platform)[0]


# A call site that silently misses the table runs the 128x128 default, many
# times slower at long sequences on the chip, and nothing else shows it.
# Every resolution is kept, and logged when a key first resolves or resolves
# differently; benchmarks and tests read resolution_stats(), as they read
# flash_attention.fallback_stats() for fallbacks.
_resolved: dict[tuple[str, str], dict] = {}


def resolution_stats() -> dict[tuple[str, str], dict]:
    """(kernel, exact key) -> ``{"blocks": (blk_q, blk_k), "source": s}``
    for every key :func:`blocks_for` has resolved in this process: ``s`` is
    "table" (the exact shape's entry), "generic" (the batch/head-generic
    entry) or "default" (a miss: ``DEFAULT_BLOCKS``)."""
    with _lock:
        return {k: dict(v) for k, v in _resolved.items()}


def blocks_for(kernel: str, *, b: int, h: int, s: int, d: int, dtype,
               causal: bool = True,
               platform: str | None = None) -> tuple[int, int]:
    """The block sizes a call site should use: the tuned entry when one
    exists, else ``DEFAULT_BLOCKS``. Never sweeps, never writes — safe at
    trace time on any platform."""
    hit, source, key = _resolve(kernel, b=b, h=h, s=s, d=d, dtype=dtype,
                                causal=causal, platform=platform)
    blocks = hit if hit is not None else DEFAULT_BLOCKS
    seen = {"blocks": blocks, "source": source}
    with _lock:
        news = _resolved.get((kernel, key)) != seen
        _resolved[(kernel, key)] = seen
    if news:
        # a miss off the CPU (where a miss is the contract) is worth a
        # warning: the sweep has not seen this shape on this chip
        missed = source == "default" and not key.endswith("|cpu")
        log.log(logging.WARNING if missed else logging.INFO,
                "autotune: %s -> %dx%d (%s)", key, *blocks, source)
    return blocks


def record(kernel: str, *, b: int, h: int, s: int, d: int, dtype,
           blocks: tuple[int, int], detail: dict | None = None,
           causal: bool = True,
           platform: str | None = None, generalize: bool = True) -> None:
    """Write one tuning entry (exact key + the batch/head-generic key) and
    persist the table. Refused on CPU — see the hermeticity contract."""
    plat = _platform(platform)
    if plat == "cpu":
        raise RuntimeError(
            "autotune.record refused on the CPU platform: tier-1 CI is a "
            "defaults-only path (no table writes, no sweeps) so its traced "
            "programs never depend on ambient tuning state")
    blocks = (int(blocks[0]), int(blocks[1]))
    if not _valid(blocks, s):
        raise ValueError(f"blocks {blocks} invalid for seq {s} "
                         "(need sublane multiples that divide s)")
    _maybe_load(plat)
    dt = _dtype_name(dtype)
    ent: dict = {"blk_q": blocks[0], "blk_k": blocks[1]}
    if detail:
        ent["detail"] = detail
    with _lock:
        _mem[_key(kernel, b, h, s, d, dt, causal, plat)] = ent
        if generalize:
            _mem[_key(kernel, 0, 0, s, d, dt, causal, plat)] = dict(ent)
        _persist_locked()


def _persist_locked() -> None:
    """Write the in-memory table to disk (caller holds ``_lock``)."""
    path = table_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(path.name + ".tmp")
    tmp.write_text(json.dumps(_mem, indent=1, sort_keys=True))
    os.replace(tmp, path)


# --------------------------------------------------------------------------
# fused cross-entropy chunk table (ops/fused_ce.py call sites)
# --------------------------------------------------------------------------


def ce_chunk_candidates(v: int) -> list[int]:
    """The sweep grid for one vocab width: candidate chunks that actually
    chunk (strictly narrower than the vocab — at chunk >= V the fused loop
    degenerates to the single-matmul pass the default already covers)."""
    return [c for c in CE_CHUNK_CANDIDATES if c < v]


def ce_chunk_lookup(*, n: int, d: int, v: int, dtype,
                    platform: str | None = None) -> int | None:
    """Tuned chunk for the key, or None. Exact-N entry first, then the
    N-generic one the sweep also records; entries wider than the vocab are
    clipped (stale-table safety)."""
    plat = _platform(platform)
    _maybe_load(plat)
    dt = _dtype_name(dtype)
    for key in (_key(CE_KERNEL, n, 0, v, d, dt, False, plat),
                _key(CE_KERNEL, 0, 0, v, d, dt, False, plat)):
        ent = _mem.get(key)
        if ent and int(ent.get("chunk", 0)) > 0:
            return min(int(ent["chunk"]), v)
    return None


def ce_chunk_for(*, n: int, d: int, v: int, dtype,
                 platform: str | None = None) -> int:
    """The chunk a fused-CE call site should use: the tuned entry when one
    exists, else ``DEFAULT_CE_CHUNK`` (clipped to the vocab). Never sweeps,
    never writes — safe at trace time on any platform; on CPU the table is
    never even read (``_maybe_load`` hermeticity contract)."""
    hit = ce_chunk_lookup(n=n, d=d, v=v, dtype=dtype, platform=platform)
    return hit if hit is not None else min(DEFAULT_CE_CHUNK, v)


def ce_record(*, n: int, d: int, v: int, dtype, chunk: int,
              detail: dict | None = None, platform: str | None = None,
              generalize: bool = True) -> None:
    """Write one fused-CE chunk entry (exact-N key + the N-generic key) and
    persist. Refused on CPU — same defaults-only contract as :func:`record`."""
    plat = _platform(platform)
    if plat == "cpu":
        raise RuntimeError(
            "autotune.ce_record refused on the CPU platform: tier-1 CI is a "
            "defaults-only path (no table writes, no sweeps) so its traced "
            "programs never depend on ambient tuning state")
    chunk = int(chunk)
    if chunk < 1 or chunk > v:
        raise ValueError(f"chunk {chunk} invalid for vocab {v}")
    _maybe_load(plat)
    dt = _dtype_name(dtype)
    ent: dict = {"chunk": chunk}
    if detail:
        ent["detail"] = detail
    with _lock:
        _mem[_key(CE_KERNEL, n, 0, v, d, dt, False, plat)] = ent
        if generalize:
            _mem[_key(CE_KERNEL, 0, 0, v, d, dt, False, plat)] = dict(ent)
        _persist_locked()


def ensure_ce_tuned(*, n: int, d: int, v: int, dtype, iters: int = 10,
                    measure: Callable | None = None,
                    platform: str | None = None) -> int:
    """Tuned fused-CE chunk for the key — from the table when present (no
    re-sweep), else sweep-and-record. ``measure(chunk) -> secs_per_call``
    is injectable for tests; the default times the real fused loss
    (value_and_grad — the chunk choice is a BACKWARD-traffic property too).
    Refused on CPU."""
    hit = ce_chunk_lookup(n=n, d=d, v=v, dtype=dtype, platform=platform)
    if hit is not None:
        return hit
    plat = _platform(platform)
    if plat == "cpu":
        raise RuntimeError(
            "autotune CE sweep refused on the CPU platform (defaults-only "
            "path): interpret-mode timings are meaningless and tier-1 CI "
            "must stay hermetic — use ce_chunk_for() for the fallback chunk")
    cands = ce_chunk_candidates(v)
    if not cands:
        return ce_chunk_for(n=n, d=d, v=v, dtype=dtype, platform=plat)
    if measure is None:
        import jax
        import jax.numpy as jnp

        from distributed_tensorflow_guide_tpu.ops import fused_ce as fce

        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(keys[0], (n, d), jnp.float32).astype(dtype)
        kernel = jax.random.normal(keys[1], (d, v), jnp.float32) * 0.02
        targets = jax.random.randint(keys[2], (n,), 0, v, jnp.int32)

        def measure(chunk):  # noqa: F811 - documented injection point
            f = jax.jit(jax.value_and_grad(
                lambda xx, kk: fce.fused_cross_entropy(
                    xx, kk, targets, chunk=chunk),
                argnums=(0, 1)))
            return measure_runner(lambda: f(x, kernel), iters=iters)

    timed: dict[int, float] = {}
    failed: list[dict] = []
    for chunk in cands:
        try:
            timed[chunk] = float(measure(chunk))
        except Exception as e:  # noqa: BLE001 - record and move on
            failed.append({"chunk": chunk, "error": str(e)[:200]})
    if not timed:
        return ce_chunk_for(n=n, d=d, v=v, dtype=dtype, platform=plat)
    best = min(timed, key=timed.get)
    detail = {
        "iters": iters,
        "swept": [{"chunk": c, "secs_per_call": round(t, 7)}
                  for c, t in sorted(timed.items())],
    }
    if failed:
        detail["failed"] = failed
    ce_record(n=n, d=d, v=v, dtype=dtype, chunk=best, detail=detail,
              platform=plat)
    return best


# --------------------------------------------------------------------------
# DP gradient-bucket table (parallel/overlap.py call sites)
# --------------------------------------------------------------------------


def bucket_candidates(param_bytes: int) -> list[int]:
    """The sweep grid for one gradient-tree size: budgets that actually
    bucket (strictly smaller than the tree — at budget >= param_bytes the
    partition degenerates to the single monolithic all-reduce the
    overlap-off path already covers)."""
    return [c for c in BUCKET_BYTES_CANDIDATES if c < param_bytes]


def _param_mib(param_bytes: int) -> int:
    """MiB-granular size key: bucket winners are a property of the
    gradient-tree SCALE, not its exact byte count — nearby models (a layer
    added, a head resized) should share an entry instead of re-sweeping."""
    return max(1, round(param_bytes / (1 << 20)))


def bucket_lookup(*, param_bytes: int, world: int, dtype,
                  platform: str | None = None) -> int | None:
    """Tuned bucket bytes for the key, or None. Exact-world entry first,
    then the world-generic one the sweep also records."""
    plat = _platform(platform)
    _maybe_load(plat)
    dt = _dtype_name(dtype)
    mib = _param_mib(param_bytes)
    for key in (_key(BUCKET_KERNEL, world, 0, mib, 0, dt, False, plat),
                _key(BUCKET_KERNEL, 0, 0, mib, 0, dt, False, plat)):
        ent = _mem.get(key)
        if ent and int(ent.get("bucket_bytes", 0)) > 0:
            return int(ent["bucket_bytes"])
    return None


def bucket_bytes_for(*, param_bytes: int, world: int, dtype,
                     platform: str | None = None) -> int:
    """The bucket budget an overlapped-DP call site should use: the tuned
    entry when one exists, else ``DEFAULT_BUCKET_BYTES``. Never sweeps,
    never writes — safe at trace time on any platform; on CPU the table is
    never even read (``_maybe_load`` hermeticity contract)."""
    hit = bucket_lookup(param_bytes=param_bytes, world=world, dtype=dtype,
                        platform=platform)
    return hit if hit is not None else DEFAULT_BUCKET_BYTES


def bucket_record(*, param_bytes: int, world: int, dtype,
                  bucket_bytes: int, detail: dict | None = None,
                  platform: str | None = None,
                  generalize: bool = True) -> None:
    """Write one bucket entry (exact-world key + the world-generic key)
    and persist. Refused on CPU — same defaults-only contract as
    :func:`record`."""
    plat = _platform(platform)
    if plat == "cpu":
        raise RuntimeError(
            "autotune.bucket_record refused on the CPU platform: tier-1 CI "
            "is a defaults-only path (no table writes, no sweeps) so its "
            "traced programs never depend on ambient tuning state")
    bucket_bytes = int(bucket_bytes)
    if bucket_bytes < 1:
        raise ValueError(f"bucket_bytes {bucket_bytes} invalid (need >= 1)")
    _maybe_load(plat)
    dt = _dtype_name(dtype)
    mib = _param_mib(param_bytes)
    ent: dict = {"bucket_bytes": bucket_bytes}
    if detail:
        ent["detail"] = detail
    with _lock:
        _mem[_key(BUCKET_KERNEL, world, 0, mib, 0, dt, False, plat)] = ent
        if generalize:
            _mem[_key(BUCKET_KERNEL, 0, 0, mib, 0, dt, False, plat)] = (
                dict(ent))
        _persist_locked()


def ensure_bucket_tuned(*, param_bytes: int, world: int, dtype,
                        measure: Callable[[int], float],
                        platform: str | None = None) -> int:
    """Tuned bucket budget for the key — from the table when present (no
    re-sweep), else sweep-and-record. ``measure(bucket_bytes) ->
    secs_per_step`` is REQUIRED (unlike the CE sweep there is no canonical
    standalone workload: the right bucket is a property of the caller's
    model + mesh, so the bench times its own overlapped step per
    candidate — bench_comm_overlap.py --tune). Refused on CPU."""
    hit = bucket_lookup(param_bytes=param_bytes, world=world, dtype=dtype,
                        platform=platform)
    if hit is not None:
        return hit
    plat = _platform(platform)
    if plat == "cpu":
        raise RuntimeError(
            "autotune bucket sweep refused on the CPU platform "
            "(defaults-only path): interpret-mode timings are meaningless "
            "and tier-1 CI must stay hermetic — use bucket_bytes_for() for "
            "the fallback budget")
    cands = bucket_candidates(param_bytes)
    if not cands:
        return bucket_bytes_for(param_bytes=param_bytes, world=world,
                                dtype=dtype, platform=plat)
    timed: dict[int, float] = {}
    failed: list[dict] = []
    for bb in cands:
        try:
            timed[bb] = float(measure(bb))
        except Exception as e:  # noqa: BLE001 - record and move on
            failed.append({"bucket_bytes": bb, "error": str(e)[:200]})
    if not timed:
        return bucket_bytes_for(param_bytes=param_bytes, world=world,
                                dtype=dtype, platform=plat)
    best = min(timed, key=timed.get)
    detail = {
        "param_bytes": int(param_bytes), "world": int(world),
        "swept": [{"bucket_bytes": bb, "secs_per_step": round(t, 7)}
                  for bb, t in sorted(timed.items())],
    }
    if failed:
        detail["failed"] = failed
    bucket_record(param_bytes=param_bytes, world=world, dtype=dtype,
                  bucket_bytes=best, detail=detail, platform=plat)
    return best


# --------------------------------------------------------------------------
# roofline models (shared by the sweep, the microbench, and the tests)
# --------------------------------------------------------------------------


def padded_head_dim(d: int) -> int:
    return -(-d // LANE) * LANE


def live_block_count(s: int, blk_q: int, blk_k: int, causal: bool) -> int:
    """Grid cells that actually compute: causal kernels skip every KV block
    strictly above the Q block's diagonal (pl.when), so dead cells cost
    neither FLOPs nor (meaningful) bandwidth."""
    n_q, n_kv = s // blk_q, s // blk_k
    if not causal:
        return n_q * n_kv
    return sum(1 for i in range(n_q) for j in range(n_kv)
               if j * blk_k <= i * blk_q + blk_q - 1)


# MXU matmuls per live (Q-block, KV-block) pair: fwd/carry do qk^T + p.v;
# dq adds ds.k; dkv does qk^T + p^T.do + do.v^T + ds^T.q. The decode kernel
# is the forward pair again (qk^T + p.v) over a sublane-padded 1-token chunk.
_MXU_PASSES = {"flash_fwd": 2, "carry_step": 2, "flash_dq": 3,
               "flash_dkv": 4, "decode_attend": 2, "decode_paged": 2}


def kernel_flops(kernel: str, *, b: int, h: int, s: int, d: int,
                 blocks: tuple[int, int], causal: bool = True) -> float:
    """Hardware MXU FLOPs of ONE kernel call: 2*M*N*K per matmul over the
    PADDED head dim (what the MXU executes), live causal blocks only.

    The decode kernel's grid has ONE fixed q tile (the sublane-padded
    chunk, ``blocks[0]``) against all s/blk_k KV blocks — charging the
    training kernels' (s/blk_q) x (s/blk_k) grid would inflate its FLOP
    throughput ~s/blk_q-fold."""
    bq, bk = blocks
    dp = padded_head_dim(d)
    if kernel in (DECODE_KERNEL, PAGED_DECODE_KERNEL):
        live = s // bk
    else:
        live = live_block_count(s, bq, bk, causal)
    return 2.0 * _MXU_PASSES[kernel] * bq * bk * dp * live * b * h


def kernel_hbm_bytes(kernel: str, *, b: int, h: int, s: int, d: int,
                     dtype) -> float:
    """Minimal algorithmic HBM traffic of ONE call: every operand read
    once, every output written once (perfect on-chip reuse). The roofline
    fraction against this is a kernel-efficiency measure — block-induced
    re-reads (e.g. K/V fetched once per Q block) show up as a LOW
    fraction, which is exactly the signal the tuner chases."""
    import numpy as np

    io = np.dtype(dtype).itemsize
    dp = padded_head_dim(d)
    t = b * h * s * dp      # one head-dim-sized tensor
    lane = b * h * s * LANE  # one lane-broadcast softmax stat (always f32)
    if kernel == "flash_fwd":       # read q,k,v; write o + lse
        return 4 * t * io + lane * 4
    if kernel == "carry_step":      # read q,k,v + (m,l,acc); write (m,l,acc)
        return 3 * t * io + 2 * (2 * lane + t) * 4
    if kernel == "flash_dq":        # read q,k,v,do + lse,delta; write dq
        return 5 * t * io + 2 * lane * 4
    if kernel == "flash_dkv":       # read q,k,v,do + lse,delta; write dk,dv
        return 6 * t * io + 2 * lane * 4
    raise ValueError(f"unknown kernel {kernel!r}")


def kernel_vmem_bytes(kernel: str, blk_q: int, blk_k: int, dp: int,
                      dtype) -> int:
    """Per-grid-cell VMEM working set: in/out tiles (double-buffered by the
    Pallas pipeline, hence x2) + f32 scratch + the (blk_q, blk_k)
    temporaries the kernel body keeps whole: the float32 scores out of the
    MXU, and what goes back into it in the operands' dtype (p for
    fwd/carry; p and ds for the backward kernels). What lies between them
    is elementwise and Mosaic does not keep it whole. Used to filter sweep
    candidates.

    Held to the compiler (PR 27; the v5e's, ahead of time, at the default
    scoped limit). At s=1024 and padded head dim 128 every tile up to
    1024 x 1024 compiles for all four kernels in both dtypes; this model
    admits all of them in bfloat16 and, in float32, all but the 1024 x 1024
    of dq, dkv and carry (18.5-21 MiB modelled). At s=2048, edges 512 to
    2048, padded head dims 128 and 256, both dtypes, it admits 61 of 144
    and the compiler refuses 4 of those, all at head dim 256 (13-15.5 MiB
    modelled); it filters 8 that compile. The budget it is compared with
    is :func:`vmem_budget_bytes`."""
    import numpy as np

    io = np.dtype(dtype).itemsize
    q_t, k_t, l_t = blk_q * dp, blk_k * dp, blk_q * LANE
    score = blk_q * blk_k
    if kernel == "flash_fwd":
        tiles = (2 * q_t + 2 * k_t) * io + l_t * 4
        scratch = (2 * l_t + q_t) * 4
        body = score * (4 + io)
    elif kernel == "carry_step":
        tiles = (q_t + 2 * k_t) * io + 2 * (2 * l_t + q_t) * 4
        scratch = (2 * l_t + q_t) * 4
        body = score * (4 + io)
    elif kernel == "flash_dq":
        tiles = (3 * q_t + 2 * k_t) * io + 2 * l_t * 4
        scratch = q_t * 4
        body = score * (4 + 2 * io)
    elif kernel == "flash_dkv":
        tiles = (2 * q_t + 4 * k_t) * io + 2 * l_t * 4
        scratch = 2 * k_t * 4
        body = score * (4 + 2 * io)
    elif kernel in ("decode_attend", "decode_paged"):
        # q tile + K/V cache tiles (at the CACHE dtype — int8 is what makes
        # the big edges affordable) + the two (1, blk_k) f32 scale rows;
        # scratch = (m, l) lane-broadcast stats + the f32 accumulator;
        # body = the f32 score/probability temporaries. The q-side terms
        # are charged at DECODE_MAX_CHUNK, not the 8-row decode tile: the
        # same tuned blk_k also serves prefill/verify chunks up to that
        # cap, and a candidate must fit VMEM at the worst admitted chunk.
        cq = DECODE_MAX_CHUNK * dp
        tiles = cq * io + 2 * k_t * io + 2 * blk_k * 4
        scratch = (2 * DECODE_MAX_CHUNK * LANE + cq) * 4
        body = 2 * DECODE_MAX_CHUNK * blk_k * 4
    else:
        raise ValueError(f"unknown kernel {kernel!r}")
    return 2 * tiles + scratch + body


def candidate_blocks(kernel: str, *, s: int, d: int,
                     dtype) -> list[tuple[int, int]]:
    """The sweep grid for one kernel/shape: candidate edges that divide the
    sequence and fit the VMEM budget. The decode kernel only sweeps the KV
    edge (its Q edge is the fixed sublane-padded token chunk)."""
    dp = padded_head_dim(d)
    budget = vmem_budget_bytes(kernel)
    edges = [e for e in CANDIDATE_EDGES if e <= s and s % e == 0]
    if kernel in (DECODE_KERNEL, PAGED_DECODE_KERNEL):
        bq = DECODE_CHUNK_SUBLANES
        return [
            (bq, bk) for bk in edges
            if s % bq == 0
            and kernel_vmem_bytes(kernel, bq, bk, dp, dtype) <= budget
        ]
    return [
        (bq, bk)
        for bq in edges for bk in edges
        if kernel_vmem_bytes(kernel, bq, bk, dp, dtype) <= budget
    ]


# --------------------------------------------------------------------------
# kernel runners + the sweep
# --------------------------------------------------------------------------


def kernel_operands(kernel: str, *, b: int, h: int, s: int, d: int, dtype,
                    causal: bool = True, seed: int = 0) -> tuple:
    """Kernel-layout operands for one runner — split out from
    :func:`make_kernel_runner` so a SWEEP builds them (and the backward
    residual forward pass, a full kernel compile+run) ONCE per
    (kernel, shape), not once per swept candidate."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_guide_tpu.ops import flash_attention as F

    dp = padded_head_dim(d)
    scale = 1.0 / (d ** 0.5)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)

    def mk(k_):
        x = jax.random.normal(k_, (b, h, s, dp), jnp.float32)
        if dp != d:  # padding lanes are zero, as the public API guarantees
            x = x.at[..., d:].set(0.0)
        return x.astype(dtype)

    q, k, v, do = (mk(k_) for k_ in keys)
    if kernel == "flash_fwd":
        return (q, k, v)
    if kernel == "carry_step":
        return (q, k, v, *F.carry_init(b, h, s, dp))
    if kernel in ("flash_dq", "flash_dkv"):
        # backward residuals from the forward at the DEFAULT blocks, so
        # every candidate times identical operands
        dbq, dbk = DEFAULT_BLOCKS
        out, lse = jax.jit(lambda q, k, v: F._fwd_call(
            q, k, v, scale=scale, causal=causal,
            blk_q=dbq, blk_k=dbk))(q, k, v)
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1)
        delta_b = jnp.broadcast_to(delta[..., None], (b, h, s, LANE))
        return (q, k, v, do, lse, delta_b)
    raise ValueError(f"unknown kernel {kernel!r}")


def make_kernel_runner(kernel: str, blocks: tuple[int, int], *, b: int,
                       h: int, s: int, d: int, dtype, causal: bool = True,
                       seed: int = 0,
                       operands: tuple | None = None) -> Callable[[], object]:
    """A zero-arg callable running ONE raw kernel call at ``blocks`` on
    kernel-layout operands — the unit both the sweep and the kernel-only
    microbench time. Pass ``operands`` (from :func:`kernel_operands`) to
    share them across candidates; built here when omitted."""
    import jax

    from distributed_tensorflow_guide_tpu.ops import flash_attention as F

    bq, bk = blocks
    scale = 1.0 / (d ** 0.5)
    if operands is None:
        operands = kernel_operands(kernel, b=b, h=h, s=s, d=d, dtype=dtype,
                                   causal=causal, seed=seed)
    if kernel == "flash_fwd":
        f = jax.jit(lambda q, k, v: F._fwd_call(
            q, k, v, scale=scale, causal=causal, blk_q=bq, blk_k=bk))
    elif kernel == "carry_step":
        f = jax.jit(lambda *a: F.flash_carry_step(
            *a, scale=scale, diag=causal, blk_q=bq, blk_k=bk))
    elif kernel == "flash_dq":
        f = jax.jit(lambda *a: F._bwd_dq_call(
            *a, scale=scale, causal=causal, blk_q=bq, blk_k=bk))
    elif kernel == "flash_dkv":
        f = jax.jit(lambda *a: F._bwd_dkv_call(
            *a, scale=scale, causal=causal, blk_q=bq, blk_k=bk))
    else:
        raise ValueError(f"unknown kernel {kernel!r}")
    return lambda: f(*operands)


def measure_runner(fn: Callable[[], object], *, iters: int = 20,
                   warmup: int = 2) -> float:
    """Seconds per call; the timed region is closed by ``block_until_ready``
    on the last call's outputs (the calls run in order on one device, and
    the fence is a real one on the chip: PERF.md, PR 21). Not by fetching a
    value: a flash output is tens of MB, and bringing it to the host added
    25-50 ms to every measurement, 0.5-1 ms a call at 50 calls (PR 27)."""
    import time

    import jax

    out = None
    for _ in range(max(1, warmup)):
        out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def ensure_tuned(kernel: str, *, b: int, h: int, s: int, d: int, dtype,
                 causal: bool = True, iters: int = 20,
                 measure: Callable | None = None,
                 platform: str | None = None) -> tuple[int, int]:
    """Tuned blocks for the key — from the table when present (same key →
    same blocks, NO re-sweep), else sweep-and-record. ``measure(kernel,
    blocks) -> secs_per_call`` is injectable for tests; the default times
    the real kernel via :func:`make_kernel_runner`. Refused on CPU."""
    hit = lookup(kernel, b=b, h=h, s=s, d=d, dtype=dtype, causal=causal,
                 platform=platform)
    if hit is not None:
        return hit
    plat = _platform(platform)
    if plat == "cpu":
        raise RuntimeError(
            "autotune sweep refused on the CPU platform (defaults-only "
            "path): interpret-mode timings are meaningless and tier-1 CI "
            "must stay hermetic — use blocks_for() for the fallback blocks")
    cands = candidate_blocks(kernel, s=s, d=d, dtype=dtype)
    if not cands:
        return blocks_for(kernel, b=b, h=h, s=s, d=d, dtype=dtype,
                          causal=causal, platform=plat)
    if measure is None and kernel == DECODE_KERNEL:
        # the decode kernel's operands (int8 cache + scales vs a plain
        # cache) live with the kernel — lazy import avoids the cycle
        from distributed_tensorflow_guide_tpu.ops import decode_attention

        def measure(kern, blocks):  # noqa: F811 - documented injection point
            fn = decode_attention.make_decode_runner(
                blocks[1], b=b, h=h, s=s, d=d, dtype=dtype)
            return measure_runner(fn, iters=iters)

    if measure is None:
        ops = kernel_operands(kernel, b=b, h=h, s=s, d=d, dtype=dtype,
                              causal=causal)  # once per sweep, not per cand

        def measure(kern, blocks):  # noqa: F811 - documented injection point
            fn = make_kernel_runner(kern, blocks, b=b, h=h, s=s, d=d,
                                    dtype=dtype, causal=causal,
                                    operands=ops)
            return measure_runner(fn, iters=iters)

    # Per-candidate failure isolation: the VMEM model is an estimate, and
    # one RESOURCE_EXHAUSTED compile must cost one candidate, not the
    # whole battery row (and not the later kernels' sweeps).
    timed: dict[tuple[int, int], float] = {}
    failed: list[dict] = []
    for blocks in cands:
        try:
            timed[blocks] = float(measure(kernel, blocks))
        except Exception as e:  # noqa: BLE001 - record and move on
            failed.append({"blk_q": blocks[0], "blk_k": blocks[1],
                           "error": str(e)[:200]})
    if not timed:
        return blocks_for(kernel, b=b, h=h, s=s, d=d, dtype=dtype,
                          causal=causal, platform=plat)
    best = min(timed, key=timed.get)
    detail = {
        "iters": iters, "causal": causal,
        "swept": [
            {"blk_q": bq, "blk_k": bk, "secs_per_call": round(t, 7)}
            for (bq, bk), t in sorted(timed.items())
        ],
    }
    if failed:
        detail["failed"] = failed
    record(kernel, b=b, h=h, s=s, d=d, dtype=dtype, blocks=best,
           detail=detail, causal=causal, platform=plat)
    return best


# --------------------------------------------------------------------------
# online in-situ tuning (round 21)
# --------------------------------------------------------------------------
#
# The offline story (bench --tune on the chip, winners written to the
# table) leaves every UNSEEN key — new device kind, new geometry —
# on the tested defaults until someone runs a sweep by hand. The online
# front door closes that gap: when a call site resolves a key that has no
# table entry on a sweep-capable backend, it runs the existing ensure_*
# sweep IN SITU (first trace/warmup pays it once), records the winner
# through the same crash-safe tmp+rename persistence, and every later
# resolution of the key — this process or the next — is a plain lookup
# hit. Three hard bounds keep it safe:
#
# * **default-off**: nothing sweeps unless ``DTG_ONLINE_TUNE`` is truthy
#   or a knob (``ServeEngine(online_tune=True)``,
#   ``TrainLoop(online_tune=True)``) set the process override;
# * **CPU-hermetic**: on the cpu platform the front door is bitwise the
#   fallback path — no table I/O, no sweeps (the PR-2 contract, re-pinned
#   by tests/test_online_tune.py);
# * **bounded wall-clock**: sweeps stop once the per-process budget
#   (``DTG_ONLINE_TUNE_BUDGET_S``, default 120 s) is spent, and every key
#   is attempted at most ONCE per process even when its sweep fails —
#   a key that cannot tune falls back to defaults forever, it never
#   retries in a serving loop.

_ONLINE_ENV = "DTG_ONLINE_TUNE"
_ONLINE_BUDGET_ENV = "DTG_ONLINE_TUNE_BUDGET_S"
DEFAULT_ONLINE_BUDGET_S = 120.0

_online_override: bool | None = None
_online_attempted: set = set()
_online_spent_s: float = 0.0


def set_online_tune(enabled: bool | None) -> bool | None:
    """Set (or with ``None`` clear) the process-wide online-tune override.
    The override wins over ``DTG_ONLINE_TUNE``; returns the previous
    override so callers can restore it. This is deliberately process
    state, like the table itself — an engine that opts in tunes for
    every consumer of the shared table."""
    global _online_override
    with _lock:
        prev = _online_override
        _online_override = None if enabled is None else bool(enabled)
    return prev


def online_tune_enabled() -> bool:
    """Whether the online front door may sweep: the explicit override
    when one is set, else the ``DTG_ONLINE_TUNE`` env gate (truthy =
    anything but empty/0/false/no)."""
    if _online_override is not None:
        return _online_override
    raw = os.environ.get(_ONLINE_ENV, "").strip().lower()
    return raw not in ("", "0", "false", "no", "off")


def online_tune_budget_s() -> float:
    """Per-process wall-clock budget for in-situ sweeps
    (``DTG_ONLINE_TUNE_BUDGET_S``, default 120 s)."""
    raw = os.environ.get(_ONLINE_BUDGET_ENV, "")
    try:
        return float(raw) if raw else DEFAULT_ONLINE_BUDGET_S
    except ValueError:
        return DEFAULT_ONLINE_BUDGET_S


def online_tune_stats() -> dict:
    """Observability snapshot: what the online tuner has done this
    process (benchmarks log it next to their tune rows)."""
    with _lock:
        return {
            "enabled": online_tune_enabled(),
            "attempted": len(_online_attempted),
            "spent_s": round(_online_spent_s, 3),
            "budget_s": online_tune_budget_s(),
        }


def ensure_tuned_online(kernel: str, *, measure: Callable | None = None,
                        iters: int = 20, block_size: int | None = None,
                        fallback: Callable[[], object] | None = None,
                        platform: str | None = None, **key):
    """The ONE online resolution path every tuned family routes through.

    ``kernel`` picks the family — flash fwd/dq/dkv/carry and the two
    decode kernels (key fields ``b, h, s, d, dtype, causal``; returns the
    family's resolved value: a blocks tuple for the training kernels, the
    KV edge int for decode/paged), :data:`CE_KERNEL` (``n, d, v, dtype``;
    returns the chunk) and :data:`BUCKET_KERNEL` (``param_bytes, world,
    dtype``; returns the bucket bytes). ``fallback`` is the zero-arg
    trace-safe default the caller would have used — REQUIRED for the
    decode kernels (their divisibility cascades live with the kernel),
    derived from the family ``*_for`` otherwise. It must never loop back
    into this function.

    No-sweep exits return ``fallback()`` exactly: online tuning disabled,
    cpu platform (hermeticity — not even a table read happens beyond what
    the fallback itself does), lookup hit (the fallback IS the hit), key
    already attempted, budget spent, sweep raised, or a bucket key with
    no measure (the bucket family has no self-contained runner — only
    callers that can time a real train step may sweep it)."""
    import time

    plat_arg = platform

    def _default():
        if fallback is not None:
            return fallback()
        if kernel == CE_KERNEL:
            return ce_chunk_for(platform=plat_arg, **key)
        if kernel == BUCKET_KERNEL:
            return bucket_bytes_for(platform=plat_arg, **key)
        if kernel in (DECODE_KERNEL, PAGED_DECODE_KERNEL):
            raise ValueError(
                f"{kernel} requires an explicit fallback (the divisibility "
                "cascade lives in ops/decode_attention.py)")
        return blocks_for(kernel, platform=plat_arg, **key)

    if not online_tune_enabled():
        return _default()
    plat = _platform(platform)
    if plat == "cpu":
        return _default()  # hermetic: bitwise the fallback path
    if kernel == BUCKET_KERNEL and measure is None:
        return _default()

    # lookup hit -> the fallback already resolves to the tuned entry
    if kernel == CE_KERNEL:
        hit = ce_chunk_lookup(platform=plat, **key)
    elif kernel == BUCKET_KERNEL:
        hit = bucket_lookup(platform=plat, **key)
    else:
        hit = lookup(kernel, platform=plat, **key)
    if hit is not None:
        return _default()

    akey = (kernel, plat,
            tuple(sorted((k, repr(v)) for k, v in key.items())))
    global _online_spent_s
    with _lock:
        # decide under the lock, resolve outside it: _default() may walk
        # back into table lookups that take this same (non-reentrant) lock
        blocked = (akey in _online_attempted
                   or _online_spent_s >= online_tune_budget_s())
        if not blocked:
            _online_attempted.add(akey)  # at most one attempt, even on fail
    if blocked:
        return _default()

    t0 = time.perf_counter()
    try:
        if kernel == CE_KERNEL:
            return ensure_ce_tuned(iters=iters, measure=measure,
                                   platform=plat, **key)
        if kernel == BUCKET_KERNEL:
            return ensure_bucket_tuned(measure=measure, platform=plat,
                                       **key)
        if kernel == PAGED_DECODE_KERNEL:
            from distributed_tensorflow_guide_tpu.ops import decode_attention
            kw = {k: v for k, v in key.items() if k != "causal"}
            return decode_attention.ensure_paged_decode_tuned(
                block_size=block_size, iters=iters, platform=plat, **kw)
        if kernel == DECODE_KERNEL:
            from distributed_tensorflow_guide_tpu.ops import decode_attention
            kw = {k: v for k, v in key.items() if k != "causal"}
            return decode_attention.ensure_decode_tuned(
                iters=iters, platform=plat, **kw)
        return ensure_tuned(kernel, iters=iters, measure=measure,
                            platform=plat, **key)
    except Exception:  # noqa: BLE001 - a failed sweep must not fail serving
        log.exception("online tune of %s failed; using the default", kernel)
        return _default()
    finally:
        with _lock:
            _online_spent_s += time.perf_counter() - t0
