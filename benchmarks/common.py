"""Shared benchmark harness: honest timing + the one-JSON-line contract.

Every benchmark in this directory prints exactly ONE JSON line
``{"metric", "value", "unit", "vs_baseline"}`` plus the device it ran on
(``platform``, ``device_kind``, ``device_count``) — the same contract as the
repo-root ``bench.py``. ``vs_baseline`` is ``null`` for the suite benches:
the reference published no numbers, and the only externally defined
baseline constant (A100-class ResNet-50) belongs to ``bench.py``.

A timed region is closed by :func:`fence`: value fetches that data-depend
on the last step, then ``jax.block_until_ready`` on the whole state. All
steps chain through the carried state, so that bounds the whole run.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable

from distributed_tensorflow_guide_tpu.core.device import (
    attached_peaks,
    device_fields,
    setup_compile_cache,
)


def device_setup(fake_devices: int = 0) -> None:
    """Configure devices + compilation cache (call before any other jax use).

    With ``fake_devices``: N virtual CPU devices and no persistent cache
    (AOT CPU code cached on a different machine can SIGILL on feature
    mismatch). Real-device runs get the persistent compilation cache
    (core/device.py ``setup_compile_cache``).
    """
    if fake_devices:
        # the env reaches child processes; the config reaches this one even
        # when jax was imported before the env was set
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["JAX_NUM_CPU_DEVICES"] = str(fake_devices)
        import jax

        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", fake_devices)
    else:
        setup_compile_cache()


def fence(state: Any, metrics: dict | None, fence_key: str = "loss") -> None:
    """Force completion of everything the last step produced.

    ``jax.block_until_ready`` on the state is a fence on this machine:
    chip_smoke.py's device phase times an 8192^3 bf16 matmul chain at the
    same 95 ms whether closed by it or by a dependent value fetch (my chip
    run, PR 21). The value fetches in front of it are the method rounds 2-5
    were measured with, kept so that a later row compares with an earlier
    one: the metric scalar (forward pass), and the *tails* of params and
    opt_state — tensors the gradient and the optimizer update write. They
    are pure transfers, compile nothing and cost microseconds. Changing the
    method is the benchmark PR's to do (ROADMAP S1).
    """
    import jax
    import numpy as np

    if metrics is not None:
        float(metrics[fence_key])

    # The smallest leaf of params and of opt_state: their buffers are
    # written by the fused update at the end of the step program, so the
    # transfer cannot complete before the backward/update work has run.
    # (The FIRST state leaves are bare counters, which don't depend on it.)
    def smallest_leaf(tree):
        import jax.numpy as jnp

        ls = [l for l in jax.tree.leaves(tree) if hasattr(l, "dtype")]
        # Exclude bare counters (int scalars like TrainState.step / optax's
        # count): they are minimum-size but carry no data dependence on the
        # gradient. Prefer the smallest real tensor (a bias / its moment).
        good = [l for l in ls
                if jnp.issubdtype(l.dtype, jnp.floating) and l.size > 1]
        pick = good or ls
        return min(pick, key=lambda l: l.size) if pick else None

    targets = [
        smallest_leaf(getattr(state, "params", None)),     # updated weights
        smallest_leaf(getattr(state, "opt_state", None)),  # optimizer moments
    ]
    if all(t is None for t in targets):
        targets = [smallest_leaf(state)]
    for t in targets:
        if t is not None:
            np.asarray(jax.device_get(t))
    jax.block_until_ready(state)


def time_steps(
    step: Callable[[Any, Any], tuple[Any, dict]],
    state: Any,
    batch: Any,
    *,
    warmup: int = 3,
    steps: int = 20,
    fence_key: str = "loss",
    stats: Any = None,
) -> tuple[float, Any]:
    """Run ``state, metrics = step(state, batch)`` ``steps`` times and return
    (seconds, final_state), closing the timed region with :func:`fence`.

    ``stats`` (a ``utils.profiling.DispatchStats``) additionally counts the
    timed window's dispatches and the host time between them — the
    instrument that shows what multi-step dispatch amortizes."""
    metrics = None
    for _ in range(warmup):
        state, metrics = step(state, batch)
    fence(state, metrics, fence_key)
    t0 = time.perf_counter()
    last_ret = None
    for _ in range(steps):
        if stats is not None:
            t_call = time.perf_counter()
            if last_ret is not None:
                stats.host_gap_s += t_call - last_ret
        state, metrics = step(state, batch)
        if stats is not None:
            last_ret = time.perf_counter()
            stats.dispatch_s += last_ret - t_call
            stats.dispatches += 1
    fence(state, metrics, fence_key)
    return time.perf_counter() - t0, state


def time_steps_sustained(
    step: Callable[[Any, Any], tuple[Any, dict]],
    state: Any,
    batch: Any,
    *,
    warmup: int = 3,
    dispatches_short: int = 4,
    dispatches_long: int = 15,
    steps_per_call: int = 1,
    fence_key: str = "loss",
    stats: Any = None,
) -> tuple[float, dict, Any]:
    """MEASURED sustained per-step seconds by paired-window differencing.

    A window that starts from a drained device pays a fixed cost before
    the dispatch pipeline is full again, which biases short windows low
    and can only be amortized, never removed, by one window alone. Two
    windows of different lengths, each started from a drained state, pay
    the SAME fixed cost — so the marginal per-step time

        (dt_long - dt_short) / ((dispatches_long - dispatches_short) * k)

    cancels it exactly and is a measurement, not an inference (the round-5
    verdict's objection to quoting "sustained ≈ 0.95x" from a marginal-cost
    model). How large the fixed cost is depends on the machine: ~380 ms in
    round 3, ~5 ms in bench.py's windows on this one (2.377 s for 24 steps,
    11.863 s for 120; my chip run, PR 21), where differencing therefore
    moves the result by 0.04%. ``steps_per_call=k`` composes: each dispatch
    is then a k-step compiled program, so per-dispatch host latency is
    amortized inside the windows as well.

    Returns ``(marginal_step_seconds, detail_dict, final_state)``; the
    detail dict carries both raw windows so the report can show its work.
    """
    if dispatches_long <= dispatches_short:
        raise ValueError(
            f"dispatches_long={dispatches_long} must exceed "
            f"dispatches_short={dispatches_short} (the difference is the "
            "measurement)")
    dt_short, state = time_steps(
        step, state, batch, warmup=warmup, steps=dispatches_short,
        fence_key=fence_key, stats=stats)
    dt_long, state = time_steps(
        step, state, batch, warmup=0, steps=dispatches_long,
        fence_key=fence_key, stats=stats)
    d_steps = (dispatches_long - dispatches_short) * steps_per_call
    marginal = (dt_long - dt_short) / d_steps
    detail = {
        "window_short": {"dispatches": dispatches_short,
                         "steps": dispatches_short * steps_per_call,
                         "secs": round(dt_short, 4)},
        "window_long": {"dispatches": dispatches_long,
                        "steps": dispatches_long * steps_per_call,
                        "secs": round(dt_long, 4)},
        "steps_per_call": steps_per_call,
    }
    return marginal, detail, state


# The NCCL baseline part (BASELINE.json: 8xA100). 312 TF dense bf16/chip.
A100_BF16_PEAK = 312e12


# Peaks of the attached accelerator, one accessor per resource over the one
# table in core/device.py. All share its contract: None off-TPU (a fraction
# of a CPU "peak" would be noise, so report() callers emit roofline keys
# only on real hardware), and an error for a TPU the table does not know.
# Like every peak these are ROOFLINE denominators: a measured fraction near
# 1.0 means the resource binds, near 0 means launch- or exposure-bound.


def _peak(field: str) -> float | None:
    peaks = attached_peaks()
    return getattr(peaks, field) if peaks else None


def device_peak_flops() -> float | None:
    """Dense bf16 FLOP/s."""
    return _peak("bf16_flops")


def device_hbm_peak() -> float | None:
    """HBM bytes/s."""
    return _peak("hbm_bytes")


def device_ici_peak() -> float | None:
    """Aggregate ICI bytes/s (all links, one direction)."""
    return _peak("ici_bytes")


def device_dcn_peak() -> float | None:
    """DCN bytes/s, one direction — the SLOW tier of a multi-slice
    deployment (parallel/multislice.py crosses it once per sync_period, not
    once per step, because it sits ~16-50x under ICI). An assumed class,
    not a measured one (core/device.py)."""
    return _peak("dcn_bytes")


def device_pcie_peak() -> float | None:
    """Host<->device bytes/s, one direction — the tier the KV spill
    hierarchy (serve/scheduler.py) moves blocks across; between HBM and
    DCN, which is why demotion to host RAM beats re-prefill but swap-in
    latency still bounds goodput (docs/serving.md). An assumed class, not
    a measured one (core/device.py)."""
    return _peak("pcie_bytes")


# --- closed-form per-device collective traffic (the comm_bytes_model) -----
#
# Ring-algorithm accounting, per device, per step: what bench_comm_overlap
# divides measured comm time into to get ici_gb_per_s. Like the HBM byte
# models these are MINIMAL algorithmic traffic — a sub-ring XLA picks, or
# retransmits, push the measured fraction DOWN, which is the signal.


# Every closed form below also exposes a ``*_terms`` breakdown — the same
# number split into its algorithmic components — and computes its total AS
# the sum of those terms, so the headline model and its breakdown can never
# diverge. The cost auditor (analysis/cost.py) diffs its derived per-axis
# collective bytes against these term-by-term; a drifted model is a lint
# failure, not a stale doc.


def _wire_payload_bytes(payload_bytes: float, compress: str | None) -> float:
    """The bytes a FLOAT32-denominated payload actually puts on the wire
    under the gradient-compression knob: ``compress="int8"`` sends 1
    byte/elem instead of 4 (ops/quant.int8_pmean — the per-bucket f32
    scale side-channel is priced separately at the call sites, where the
    bucket count is known)."""
    if compress in (None, "off", "none"):
        return float(payload_bytes)
    if compress == "int8":
        return float(payload_bytes) / 4.0
    raise ValueError(
        f"compress must be None/'off' or 'int8', got {compress!r}")


def dp_allreduce_terms(grad_bytes: float, world: int,
                       compress: str | None = None) -> dict:
    """Ring all-reduce split into its two one-way passes (each moves
    (n−1)/n of the buffer per device). ``grad_bytes`` is always the FLOAT
    gradient size; ``compress`` rescales it to the wire format."""
    if world <= 1:
        return {"reduce_scatter": 0.0, "all_gather": 0.0}
    frac = (world - 1) / world
    wire = _wire_payload_bytes(grad_bytes, compress)
    return {"reduce_scatter": wire * frac,
            "all_gather": wire * frac}


def dp_allreduce_bytes(grad_bytes: float, world: int,
                       compress: str | None = None) -> float:
    """Sync-DP gradient all-reduce: ring = reduce-scatter + all-gather,
    each moving (n−1)/n of the buffer per device — 2·P·(n−1)/n. Zero on a
    1-device axis (lax.pmean compiles to a no-op there).
    ``compress="int8"`` prices the int8 wire format (P/4); callers add
    ``n_buckets * dp_allreduce_bytes(4, world)`` for the shared-scale
    pmax side-channel."""
    return sum(dp_allreduce_terms(grad_bytes, world, compress).values())


def fsdp_comm_terms(sharded_param_bytes: float, world: int,
                    replicated_grad_bytes: float = 0.0) -> dict:
    """ZeRO-3 traffic split: the forward param all-gather, the backward
    grad reduce-scatter (one one-way pass each over the sharded leaves),
    and the plain 2-pass all-reduce the replicated leaves still pay."""
    if world <= 1:
        return {"param_all_gather": 0.0, "grad_reduce_scatter": 0.0,
                "replicated_grad_allreduce": 0.0}
    frac = (world - 1) / world
    return {"param_all_gather": sharded_param_bytes * frac,
            "grad_reduce_scatter": sharded_param_bytes * frac,
            "replicated_grad_allreduce": 2.0 * replicated_grad_bytes * frac}


def fsdp_comm_bytes(sharded_param_bytes: float, world: int,
                    replicated_grad_bytes: float = 0.0) -> float:
    """ZeRO-3 per-step traffic AS THIS REPO SCHEDULES IT: all-gather the
    sharded params for the forward — the gathered copies then live as
    autodiff residuals through the backward (parallel/overlap.py
    gather_shard saves no residual of its own; the downstream matmul VJPs
    hold the full params, trading memory for the re-gather classic
    ZeRO-3 pays) — and reduce-scatter the gradients = 2 one-way passes at
    (n−1)/n each; replicated leaves' gradients still pay the plain 2-pass
    all-reduce. Pinned against the traced schedule (one all_gather + one
    reduce_scatter per sharded leaf) in tests/test_overlap.py."""
    return sum(fsdp_comm_terms(sharded_param_bytes, world,
                               replicated_grad_bytes).values())


def pipeline_ppermute_terms(act_bytes: float, num_microbatches: int,
                            stages: int) -> dict:
    """Pipeline traffic split into the forward activation hops and the
    backward activation-gradient hops (M·act·(P−1)/P each)."""
    if stages <= 1:
        return {"fwd_activations": 0.0, "bwd_activation_grads": 0.0}
    one_way = num_microbatches * act_bytes * (stages - 1) / stages
    return {"fwd_activations": one_way, "bwd_activation_grads": one_way}


def pipeline_ppermute_bytes(act_bytes: float, num_microbatches: int,
                            stages: int) -> float:
    """Pipeline-parallel traffic: each microbatch's activation crosses
    every stage boundary once forward, its gradient once backward —
    2·M·act·(P−1)/P per device, ring-averaged (the P-th hop is the wrap
    that carries no payload). Matches
    ``PipelinedLM.ppermute_bytes_per_step`` (pinned)."""
    return sum(pipeline_ppermute_terms(
        act_bytes, num_microbatches, stages).values())


def outer_sync_terms(float_state_bytes: float, n_slices: int,
                     compress: str | None = None) -> dict:
    """Outer DCN ring all-reduce split into its two one-way passes.
    ``float_state_bytes`` is always the f32 state size; ``compress``
    rescales it to the wire format (int8 = 1 byte/elem)."""
    if n_slices <= 1:
        return {"reduce_scatter": 0.0, "all_gather": 0.0}
    frac = (n_slices - 1) / n_slices
    wire = _wire_payload_bytes(float_state_bytes, compress)
    return {"reduce_scatter": wire * frac,
            "all_gather": wire * frac}


def moe_all_to_all_bytes(dispatch_buffer_bytes: float,
                         expert_world: int,
                         n_layers: int = 1,
                         passes: int = 4) -> float:
    """Expert-parallel routing traffic per device per step: each MoE layer
    crosses the expert axis ``passes`` times — the training default is 4
    (dispatch + return in the forward, the same pair again for the
    gradients in the backward); forward-only serving (decode, prefill)
    pays only the forward pair, ``passes=2``.  Each crossing is an
    all_to_all keeping the local 1/e share, so passes·L·B·(e−1)/e where B
    is the per-device dispatch buffer (e_global · capacity · d_model ·
    itemsize; ``parallel/expert.py`` sizes capacity as
    ceil(top_k · t_local · capacity_factor / e_global))."""
    if expert_world <= 1:
        return 0.0
    return (float(passes) * n_layers * dispatch_buffer_bytes
            * (expert_world - 1) / expert_world)


def outer_sync_bytes(float_state_bytes: float, n_slices: int,
                     compress: str | None = None) -> float:
    """Two-tier outer sync (parallel/multislice.py): the per-round DCN
    traffic per participating device. The outer collective is a ring
    all-reduce ACROSS SLICES of the float param delta + float inner
    optimizer state — same 2·P·(n−1)/n ring accounting as
    :func:`dp_allreduce_bytes`, with n = the slice count and P = the float
    state bytes (``MultiSliceLocalSGD.outer_float_bytes``). Zero at one
    slice (the pmean compiles to a no-op). Divide by ``sync_period``
    inner steps for the amortized per-step DCN load. ``compress="int8"``
    prices the int8 wire format (P/4); add ``2 * dp_allreduce_bytes(4,
    n_slices)`` for the two shared-scale pmax scalars (delta +
    opt-state)."""
    return sum(outer_sync_terms(float_state_bytes, n_slices,
                                compress).values())


def dcn_extras(comm_bytes: float, comm_secs: float | None = None,
               assumed_gbytes_per_s: float | None = None) -> dict:
    """Extra report() keys for DCN-tier-honest benches, mirroring
    :func:`ici_extras`: the closed-form per-device outer-sync bytes, and —
    when the caller measured the outer-sync time — the achieved wire rate
    plus the fraction of the attached part's DCN peak (real hardware
    only). ``assumed_gbytes_per_s`` substitutes an assumed peak off-TPU so
    CPU runs can still emit a MODELED fraction; the key is then suffixed
    ``_model`` and the assumption echoed, so it can never be read as a
    capture."""
    out: dict = {"dcn_comm_bytes": round(float(comm_bytes), 1),
                 "dcn_comm_gb": round(comm_bytes / 1e9, 4)}
    peak = device_dcn_peak()
    if comm_secs is not None and comm_secs > 0 and comm_bytes > 0:
        achieved = comm_bytes / comm_secs
        out["dcn_gb_per_s"] = round(achieved / 1e9, 3)
        if peak:
            out["dcn_roofline_frac"] = round(achieved / peak, 4)
        elif assumed_gbytes_per_s:
            out["dcn_roofline_frac_model"] = round(
                achieved / (assumed_gbytes_per_s * 1e9), 4)
    if peak is None and assumed_gbytes_per_s:
        out["dcn_peak_gb_per_s_assumed"] = assumed_gbytes_per_s
    return out


def spill_block_bytes_terms(num_layers: int, num_heads: int,
                            block_size: int, head_dim: int,
                            kv_dtype: str | None = None, *,
                            activation_dtype_bytes: int = 2) -> dict:
    """Per-KV-block host<->device payload bytes, split into terms.

    One demotion (d2h) or swap-in (h2d) of a paged-cache block moves, for
    each of the ``num_layers`` layers, a K row and a V row of shape
    ``[num_heads, block_size, head_dim]`` — at the activation dtype
    (``cfg.dtype``, bf16 default, hence ``activation_dtype_bytes=2``)
    when ``kv_dtype`` is None, int8 payload plus the per-(head, head_dim)
    f32 scale rows when ``kv_dtype == "int8"`` (the quantized cache
    stores one f32 scale vector per block, amortized over its
    ``block_size`` positions, so int8 spills just over half the bf16
    bytes, not exactly half). These terms are the EXACT nbytes of the
    leaf rows the engine copies (engine ``_cache_d2h``) — the
    reconciliation against the traced ``spill_d2h_bytes`` counter is
    equality, not a bound."""
    if kv_dtype not in (None, "int8"):
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
    elems = 2 * num_layers * num_heads * block_size * head_dim  # k and v
    if kv_dtype is None:
        return {"kv_payload_bytes": float(activation_dtype_bytes) * elems}
    return {"kv_payload_bytes": 1.0 * elems,
            "kv_scale_bytes": 4.0 * 2 * num_layers * num_heads * head_dim}


def spill_bytes_per_swap(num_layers: int, num_heads: int, block_size: int,
                         head_dim: int, kv_dtype: str | None = None, *,
                         activation_dtype_bytes: int = 2) -> float:
    """Headline total of :func:`spill_block_bytes_terms` — the modeled
    bytes one block moves per demotion or swap-in."""
    return sum(spill_block_bytes_terms(
        num_layers, num_heads, block_size, head_dim, kv_dtype,
        activation_dtype_bytes=activation_dtype_bytes).values())


def spill_extras(d2h_bytes: float, h2d_bytes: float,
                 swap_secs: float | None = None,
                 assumed_gbytes_per_s: float | None = None) -> dict:
    """Extra report() keys for spill-tier-honest benches, mirroring
    :func:`dcn_extras`: the traced host<->device swap traffic both ways,
    and — when the caller measured the swap time — the achieved wire rate
    plus the fraction of the attached part's PCIe peak (real hardware
    only). ``assumed_gbytes_per_s`` substitutes an assumed peak off-TPU so
    CPU runs can still emit a MODELED fraction; the key is then suffixed
    ``_model`` and the assumption echoed, so it can never be read as a
    capture."""
    total = float(d2h_bytes) + float(h2d_bytes)
    out: dict = {"spill_d2h_bytes": round(float(d2h_bytes), 1),
                 "spill_h2d_bytes": round(float(h2d_bytes), 1),
                 "spill_gb": round(total / 1e9, 4)}
    peak = device_pcie_peak()
    if swap_secs is not None and swap_secs > 0 and total > 0:
        achieved = total / swap_secs
        out["pcie_gb_per_s"] = round(achieved / 1e9, 3)
        if peak:
            out["pcie_roofline_frac"] = round(achieved / peak, 4)
        elif assumed_gbytes_per_s:
            out["pcie_roofline_frac_model"] = round(
                achieved / (assumed_gbytes_per_s * 1e9), 4)
    if peak is None and assumed_gbytes_per_s:
        out["pcie_peak_gb_per_s_assumed"] = assumed_gbytes_per_s
    return out


def kv_migration_bytes_terms(n_blocks: int, num_layers: int,
                             num_heads: int, block_size: int,
                             head_dim: int,
                             kv_dtype: str | None = None, *,
                             activation_dtype_bytes: int = 2) -> dict:
    """Closed-form payload bytes of migrating ``n_blocks`` written KV
    blocks between fleet replicas (disaggregated prefill->decode
    handoff, PR 18), split into terms.

    A migration ships exactly the rows one demotion of the same blocks
    would spill (:func:`spill_block_bytes_terms` — the engine's fused
    d2h gather produces the payload for both paths), so the per-block
    term is shared and the reconciliation against the fleet's traced
    ``migration_bytes`` counter is equality, not a bound.  The same
    total prices the compiled-side DCN model: the
    ``serve_kv_block_transfer_dcn`` program's ``collective_bytes`` pin
    is this closed form divided by the slice count (the cost walker's
    per-device ppermute convention)."""
    per_block = spill_block_bytes_terms(
        num_layers, num_heads, block_size, head_dim, kv_dtype,
        activation_dtype_bytes=activation_dtype_bytes)
    return {k: float(n_blocks) * v for k, v in per_block.items()}


def kv_migration_bytes(n_blocks: int, num_layers: int, num_heads: int,
                       block_size: int, head_dim: int,
                       kv_dtype: str | None = None, *,
                       activation_dtype_bytes: int = 2) -> float:
    """Headline total of :func:`kv_migration_bytes_terms`."""
    return sum(kv_migration_bytes_terms(
        n_blocks, num_layers, num_heads, block_size, head_dim, kv_dtype,
        activation_dtype_bytes=activation_dtype_bytes).values())


def ici_extras(comm_bytes: float, comm_secs: float | None) -> dict:
    """Extra report() keys for interconnect-honest benches: the closed-form
    per-device comm bytes, and — when the caller measured the comm time
    (e.g. overlap-off minus compute-floor) — the achieved wire rate and
    the fraction of the attached part's ICI peak (emitted only on real
    hardware, like :func:`mfu_extras`)."""
    out: dict = {"comm_bytes": round(float(comm_bytes), 1),
                 "comm_gb": round(comm_bytes / 1e9, 4)}
    if comm_secs is not None and comm_secs > 0 and comm_bytes > 0:
        achieved = comm_bytes / comm_secs
        out["ici_gb_per_s"] = round(achieved / 1e9, 2)
        peak = device_ici_peak()
        if peak:
            out["ici_roofline_frac"] = round(achieved / peak, 4)
    return out


def roofline_extras(flops_per_step: float | None,
                    hbm_bytes_per_step: float | None,
                    steps: int, dt: float, n_devices: int = 1) -> dict:
    """Extra report() keys for roofline-honest benches: achieved TFLOP/s
    and/or HBM GB/s from the caller's per-step models, plus the fraction of
    the attached part's peak (keys emitted only on real hardware, like
    :func:`mfu_extras`). The byte model is the caller's MINIMAL algorithmic
    traffic — so ``hbm_roofline_frac`` is an efficiency measure: re-reads
    the kernel/program performs beyond the ideal push it DOWN, which is
    the tuning signal, not an accounting error."""
    out: dict = {}
    if flops_per_step:
        achieved_f = flops_per_step * steps / dt
        out["tflops_per_sec"] = round(achieved_f / 1e12, 3)
        peak_f = device_peak_flops()
        if peak_f:
            out["flop_roofline_frac"] = round(
                achieved_f / (peak_f * n_devices), 4)
    if hbm_bytes_per_step:
        achieved_b = hbm_bytes_per_step * steps / dt
        out["hbm_gb_per_s"] = round(achieved_b / 1e9, 2)
        peak_b = device_hbm_peak()
        if peak_b:
            out["hbm_roofline_frac"] = round(
                achieved_b / (peak_b * n_devices), 4)
    return out


def lm_model_flops_per_step(cfg, global_batch: int) -> float:
    """Logical model FLOPs of ONE training step of a Transformer config:
    3x the traced forward pass (backward = 2x forward, PaLM App. B).

    This is the MFU numerator of record — the *model* FLOP convention:
    remat recomputation is deliberately NOT counted (that is scheduled
    overhead, not model work), which is why the forward is traced with
    ``remat=False``. Attention is traced ``dense`` so the flash-kernel path
    (whose Pallas grid the jaxpr walker cannot expand) counts its logical
    dot_generals instead. Tracing is abstract (ShapeDtypeStruct) — no
    device, no compile.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_guide_tpu.models.transformer import (
        Transformer,
        make_cls_loss_fn,
        make_lm_loss_fn,
    )

    # tp_axis=None strips the manual f/g collectives from the trace;
    # override_head_dim stays — a tp_local per-shard config must count its
    # true per-shard shapes (callers then scale by n_devices in mfu_extras).
    # remat cleared at BOTH spellings (legacy bool + precision-policy
    # remat_mode): recompute is scheduled overhead, not model work.
    flop_cfg = dataclasses.replace(
        cfg, attn_impl="dense", remat=False, remat_mode=None, tp_axis=None)
    model = Transformer(flop_cfg)
    tokens = jax.ShapeDtypeStruct((global_batch, flop_cfg.max_len), jnp.int32)
    params = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), tokens)["params"]
    if flop_cfg.num_classes is None:
        # fused_ce pinned off: the MFU numerator is the LOGICAL model (the
        # chunked loop does the same matmul work, but the convention traces
        # the naive head so the numerator can never move with a loss-path
        # A/B knob)
        loss_fn = make_lm_loss_fn(model, fused_ce=False)
        batch = {"tokens": tokens}
    else:
        loss_fn = make_cls_loss_fn(model)
        batch = {"tokens": tokens,
                 "label": jax.ShapeDtypeStruct((global_batch,), jnp.int32)}
    return model_flops_per_step(loss_fn, params, batch)


def model_flops_per_step(loss_fn, *abstract_args) -> float:
    """One train step's model FLOPs from a traced forward: owns the
    3x-forward convention (backward = 2x forward, PaLM App. B) so every
    bench reports MFU on the same numerator."""
    from distributed_tensorflow_guide_tpu.utils.flop_accounting import (
        traced_matmul_flops,
    )

    return 3.0 * traced_matmul_flops(loss_fn, *abstract_args)


def loss_bytes_model(batch: int, seq: int, vocab: int, d_model: int, *,
                     chunk: int | None = None, act_bytes: int = 2,
                     param_bytes: int = 4) -> float:
    """Closed-form HBM traffic (bytes) of ONE training step's LM-head loss
    — the naive-vs-chunked model behind the fused-CE diet, mirroring
    ``models/generation.py decode_hbm_bytes_per_step``.

    N = batch·(seq−1) next-token positions; head intermediates are f32.

    * ``chunk=None`` (naive): the (N, V) logits round-trip HBM ~7 times —
      matmul out write, log_softmax read + logp write, backward logp read +
      dz write, dz read by each of the two grad matmuls — plus the common
      terms (x read fwd, W read fwd + bwd-dx matmul, dx/dW writes).
    * chunked (fused CE): the (N, chunk) score tile is assumed VMEM-
      resident (the tuner's candidate filter targets exactly that), so the
      full-logit passes VANISH; what remains is the common terms plus one
      extra read each of x and W for the backward recompute.

    Like every roofline model here this is MINIMAL algorithmic traffic —
    spills push the measured fraction down, which is the tuning signal.
    """
    return sum(loss_bytes_terms(
        batch, seq, vocab, d_model, chunk=chunk, act_bytes=act_bytes,
        param_bytes=param_bytes).values())


def loss_bytes_terms(batch: int, seq: int, vocab: int, d_model: int, *,
                     chunk: int | None = None, act_bytes: int = 2,
                     param_bytes: int = 4) -> dict:
    """:func:`loss_bytes_model` split into its traffic components (the
    naive path's dominant term — seven (N, V) f32 logit passes — gets its
    own key so the auditor can point at exactly what the fused path
    deletes)."""
    n = batch * (seq - 1)
    x_bytes = n * d_model * act_bytes
    w_bytes = d_model * vocab * param_bytes
    terms = {
        "w_read_fwd_bwd": 2.0 * w_bytes,     # W read fwd + by the dx matmul
        "x_read_fwd": float(x_bytes),
        "dx_write": float(x_bytes),
        "dw_write": float(d_model * vocab * 4),  # f32 grad out
    }
    if chunk is None or chunk >= vocab:
        terms["logit_passes"] = 7.0 * n * vocab * 4
    else:
        # fused: +1 x read and +1 W read for the bwd recompute; per-chunk
        # f32 tiles stay on chip
        terms["x_read_recompute"] = float(x_bytes)
        terms["w_read_recompute"] = float(w_bytes)
    return terms


def fused_ce_trace_terms(n_rows: int, d_model: int, vocab: int, chunk: int,
                         *, act_bytes: int = 2, param_bytes: int = 2,
                         accum_bytes: int = 4) -> dict:
    """Fusion-BOUNDARY traffic of the fused-CE value_and_grad trace — the
    model the static cost auditor pins, NOT the VMEM-ideal
    :func:`loss_bytes_model`. The auditor charges every chunk matmul's
    operands and f32 accumulator at the HBM boundary (it cannot see XLA
    keeping a score tile resident), so per chunk it counts: the forward
    logit dot, the target-logit gather, and three backward dots (forward
    recompute, dx, dW). The gap between this and ``loss_bytes_model`` is
    exactly the VMEM-residency benefit the fused-CE tuner chases."""
    n_chunks = -(-vocab // chunk)
    x = n_rows * d_model * act_bytes          # activations, compute dtype
    w_c = d_model * chunk * param_bytes       # one weight chunk
    dz_c = n_rows * chunk * act_bytes         # score-grad chunk, cast down
    score_c = n_rows * chunk * accum_bytes    # f32 score tile
    return {
        "fwd_dot_read": float(n_chunks * (x + w_c)),
        "fwd_dot_write": float(n_chunks * score_c),
        "target_gather": float(n_chunks * 2 * n_rows * accum_bytes),
        "bwd_recompute_read": float(n_chunks * (x + w_c)),
        "bwd_recompute_write": float(n_chunks * score_c),
        "dx_dot_read": float(n_chunks * (dz_c + w_c)),
        "dx_dot_write": float(n_chunks * n_rows * d_model * accum_bytes),
        "dw_dot_read": float(n_chunks * (x + dz_c)),
        "dw_dot_write": float(n_chunks * d_model * chunk * accum_bytes),
    }


def fused_ce_trace_bytes(n_rows: int, d_model: int, vocab: int, chunk: int,
                         *, act_bytes: int = 2, param_bytes: int = 2,
                         accum_bytes: int = 4) -> float:
    """Sum of :func:`fused_ce_trace_terms` — the ``hbm_bytes`` pin of the
    ``fused_ce_loss_grad`` program contract."""
    return sum(fused_ce_trace_terms(
        n_rows, d_model, vocab, chunk, act_bytes=act_bytes,
        param_bytes=param_bytes, accum_bytes=accum_bytes).values())


def mfu_extras(model_flops_per_step: float, steps: int, dt: float,
               n_devices: int = 1,
               a100_mfu: float | None = 0.37) -> dict:
    """Extra report() keys: achieved model TFLOP/s, MFU vs the attached
    part's peak x ``n_devices`` (pass the mesh size when
    ``model_flops_per_step`` covers a global batch executed across the whole
    mesh — dividing mesh-wide FLOP/s by one chip's peak would inflate MFU
    by the device count), and — when ``a100_mfu`` is given — the
    A100-equivalent step time from the SAME FLOP count at that utilization.
    The 0.37 default is the transformer-LM figure (nanoGPT-class GPT-2 124M
    sustains ~37% MFU on A100; docs/performance.md); pass ``None`` for
    workloads with their own measured A100 baseline (ResNet's MLPerf-class
    img/s constant in bench.py works out to ~11% MFU — the 37% constant
    would contradict it ~3x)."""
    achieved = model_flops_per_step * steps / dt
    out: dict = {
        "model_tflops_per_sec": round(achieved / 1e12, 2),
        "flops_per_step": model_flops_per_step,
    }
    peak = device_peak_flops()
    if peak:
        peak *= n_devices
        out["mfu"] = round(achieved / peak, 4)
        out["peak_tflops"] = round(peak / 1e12, 1)
        if a100_mfu:
            a100_step_s = model_flops_per_step / (
                a100_mfu * A100_BF16_PEAK * n_devices)
            out["a100_equiv_step_s"] = round(a100_step_s, 4)
            out["a100_mfu_assumed"] = a100_mfu
            out["vs_a100_equal_chips"] = round((a100_step_s * steps) / dt, 3)
    return out


def report(metric: str, value: float, unit: str,
           baseline: float | None = None, **extra) -> None:
    """Print the single JSON result line, device named.

    ``extra`` keys are appended after the contract keys — benches use them
    to mark non-judged configurations (e.g. ``steps_per_call=8``) so an A/B
    run can never be mistaken for the number of record.
    """
    print(json.dumps({
        "metric": metric,
        "value": round(value, 1),
        "unit": unit,
        "vs_baseline": round(value / baseline, 3) if baseline else None,
        **device_fields(),
        **extra,
    }))
