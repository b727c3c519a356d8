"""Autotune layer (ops/autotune.py): table roundtrip determinism, the CPU
defaults-only hermeticity contract, numerical parity of every swept block
candidate against the XLA reference, and the plumbing that carries tuned
blocks from the table to the flash/carry call sites.

The sweep itself is exercised with an INJECTED measure function (platform
forced to "tpu", table redirected to a tmp path): the mechanism — candidate
enumeration, winner selection, persistence, no-re-sweep — is what CI can
pin; real timings only mean something on chip (bench_flash_kernel --tune).
"""

import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_guide_tpu.ops import autotune
from distributed_tensorflow_guide_tpu.ops import flash_attention as F
from distributed_tensorflow_guide_tpu.ops.attention import dense_attention
from distributed_tensorflow_guide_tpu.ops.flash_attention import (
    flash_attention,
)


@pytest.fixture(autouse=True)
def _isolated_table(isolated_autotune_table):
    """Shared isolation (tests/conftest.py): every test gets an empty
    in-memory table and a tmp table file — nothing leaks between tests or
    to the user's cache."""
    yield


SHAPE = dict(b=1, h=1, s=256, d=64)


def _qkv(s=256, d=64, b=1, h=2, seed=0):
    r = np.random.RandomState(seed)

    def mk():
        return jnp.asarray(r.randn(b, s, h, d), jnp.float32)

    return mk(), mk(), mk()


# ---- table mechanics --------------------------------------------------------


def test_roundtrip_determinism_no_resweep():
    """Same key -> same blocks, sweep runs ONCE; the persisted table
    survives a simulated process restart (in-memory state dropped)."""
    calls = []

    def measure(kernel, blocks):
        calls.append(blocks)
        return 1.0 / (blocks[0] * blocks[1])  # favors the largest blocks

    kw = dict(**SHAPE, dtype=jnp.float32, platform="tpu")
    first = autotune.ensure_tuned("flash_fwd", measure=measure, **kw)
    n_swept = len(calls)
    cands = autotune.candidate_blocks("flash_fwd", s=SHAPE["s"],
                                      d=SHAPE["d"], dtype=jnp.float32)
    assert n_swept == len(cands) and first == (256, 256)

    again = autotune.ensure_tuned("flash_fwd", measure=measure, **kw)
    assert again == first and len(calls) == n_swept  # no re-sweep

    autotune.reset()  # "restart": reload from the persisted file
    reloaded = autotune.ensure_tuned("flash_fwd", measure=measure, **kw)
    assert reloaded == first and len(calls) == n_swept

    # the batch/head-generic entry serves nearby shapes without a sweep
    assert autotune.blocks_for("flash_fwd", b=4, h=8, s=256, d=64,
                               dtype=jnp.float32, platform="tpu") == first
    # ...but a different seq/dtype misses back to the tested default
    assert autotune.blocks_for("flash_fwd", b=1, h=1, s=512, d=64,
                               dtype=jnp.float32,
                               platform="tpu") == autotune.DEFAULT_BLOCKS


def test_cpu_is_defaults_only_no_table_io():
    """The tier-1 hermeticity contract: under the CPU platform the table
    file is neither read (a stray host table must not change what CI
    traces) nor written, and sweeps are refused outright."""
    path = Path(os.environ["DTG_AUTOTUNE_TABLE"])
    seeded = {autotune._key("flash_fwd", 0, 0, 256, 64, "float32", True, "cpu"):
              {"blk_q": 64, "blk_k": 64}}
    path.write_text(json.dumps(seeded))

    # default platform resolves to the test backend (cpu): file ignored
    assert autotune.blocks_for(
        "flash_fwd", **SHAPE, dtype=jnp.float32) == autotune.DEFAULT_BLOCKS
    with pytest.raises(RuntimeError, match="defaults-only"):
        autotune.ensure_tuned("flash_fwd", **SHAPE, dtype=jnp.float32,
                              measure=lambda *a: 0.0)
    with pytest.raises(RuntimeError, match="defaults-only"):
        autotune.record("flash_fwd", **SHAPE, dtype=jnp.float32,
                        blocks=(64, 64))
    assert json.loads(path.read_text()) == seeded  # file untouched


def test_stale_or_invalid_entries_fall_back_to_default():
    # 96 is a sublane multiple but does not divide 256 — a stale entry
    # (e.g. hand-edited table or a shape change) must not reach the kernel
    autotune._mem[autotune._key("flash_fwd", 0, 0, 256, 64, "float32",
                                True, "tpu")] = {"blk_q": 96, "blk_k": 96}
    assert autotune.blocks_for(
        "flash_fwd", **SHAPE, dtype=jnp.float32,
        platform="tpu") == autotune.DEFAULT_BLOCKS
    with pytest.raises(ValueError, match="invalid"):
        autotune.record("flash_fwd", **SHAPE, dtype=jnp.float32,
                        blocks=(96, 96), platform="tpu")


def test_candidates_all_valid_and_within_vmem_budget():
    for kern in autotune.KERNELS:
        for s in (128, 256, 1024):
            cands = autotune.candidate_blocks(kern, s=s, d=64,
                                              dtype=jnp.bfloat16)
            assert cands, (kern, s)
            for bq, bk in cands:
                assert s % bq == 0 and s % bk == 0 and bq % 8 == 0
                assert autotune.kernel_vmem_bytes(
                    kern, bq, bk, 128, jnp.bfloat16
                ) <= autotune.vmem_budget_bytes(kern)


def test_roofline_models_sanity():
    # non-causal: every block pair is live -> closed-form FLOPs
    kw = dict(b=2, h=3, s=256, d=64, blocks=(128, 128))
    f = autotune.kernel_flops("flash_fwd", causal=False, **kw)
    assert f == 2.0 * 2 * 128 * 128 * 128 * 4 * 2 * 3  # 2 passes, 4 live
    # causal at 2x2 blocks: 3 of 4 live (one strictly above the diagonal)
    assert autotune.kernel_flops(
        "flash_fwd", causal=True, **kw) == f * 3 / 4
    # dkv does 4 MXU passes per block to fwd's 2
    assert autotune.kernel_flops("flash_dkv", causal=False, **kw) == 2 * f
    # byte model: block-independent (minimal algorithmic traffic), and
    # bf16 IO halves the head-dim tensors but not the f32 stats
    b32 = autotune.kernel_hbm_bytes("flash_fwd", b=1, h=1, s=256, d=64,
                                    dtype=jnp.float32)
    b16 = autotune.kernel_hbm_bytes("flash_fwd", b=1, h=1, s=256, d=64,
                                    dtype=jnp.bfloat16)
    t, lane = 256 * 128, 256 * 128
    assert b32 == 4 * t * 4 + lane * 4
    assert b16 == 4 * t * 2 + lane * 4


# ---- numerical parity of the sweep space ------------------------------------


def test_every_swept_block_pair_matches_dense_forward():
    """Every candidate the sweep may ever pick must be numerically exact —
    the sweep optimizes time, never correctness."""
    q, k, v = _qkv()
    ref = dense_attention(q, k, v, causal=True)
    cands = autotune.candidate_blocks("flash_fwd", s=256, d=64,
                                      dtype=jnp.float32)
    assert (64, 64) in cands and (256, 256) in cands
    for bq, bk in cands:
        out = flash_attention(q, k, v, causal=True, blk_q=bq, blk_k=bk)
        np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4,
                                   err_msg=f"blocks ({bq}, {bk})")


@pytest.mark.parametrize("blocks", [(64, 64), (64, 256), (256, 64),
                                    (256, 256)])
def test_swept_blocks_gradient_parity(blocks):
    """Backward kernels at non-default blocks (incl. asymmetric pairs —
    the dq/dkv grids transpose) against the dense-attention gradients."""
    q, k, v = _qkv(h=1)

    def loss(fn, **kw):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True, **kw) ** 2)

    g_flash = jax.grad(
        loss(flash_attention, blk_q=blocks[0], blk_k=blocks[1]),
        argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss(dense_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_dense):
        scale = float(jnp.max(jnp.abs(b))) + 1e-6
        np.testing.assert_allclose(a / scale, b / scale, atol=1e-3)


# ---- call-site plumbing -----------------------------------------------------


def test_flash_attention_resolves_all_three_kernels_from_table(monkeypatch):
    """With no explicit blocks, flash_attention consults the table once per
    kernel (fwd, dq, dkv) — the no-hardcoded-blocks contract."""
    seen = []
    real = autotune.blocks_for

    def spy(kernel, **kw):
        out = real(kernel, **kw)
        seen.append(kernel)
        return out

    monkeypatch.setattr(autotune, "blocks_for", spy)
    q, k, v = _qkv()
    flash_attention(q, k, v, causal=True)
    assert {"flash_fwd", "flash_dq", "flash_dkv"} <= set(seen)


def test_recorded_blocks_change_resolution_and_stay_exact():
    """An in-memory table entry redirects the default resolution (here on
    the cpu platform key, which only tests can seed — the file path is
    closed by the hermeticity contract) and the result stays exact."""
    for kern in ("flash_fwd", "flash_dq", "flash_dkv"):
        autotune._mem[autotune._key(kern, 0, 0, 256, 64, "float32",
                                    True, "cpu")] = {"blk_q": 64, "blk_k": 64}
    assert autotune.blocks_for("flash_fwd", b=1, h=2, s=256, d=64,
                               dtype=jnp.float32) == (64, 64)
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=True)  # resolves 64x64
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


def test_carry_blocks_consults_table():
    autotune._mem[autotune._key("carry_step", 0, 0, 256, 64, "float32",
                                True, "cpu")] = {"blk_q": 64, "blk_k": 128}
    assert F.carry_blocks(2, 2, 256, 64, jnp.float32) == (64, 128)
    # and the default fallback holds on a miss
    assert F.carry_blocks(2, 2, 512, 64,
                          jnp.float32) == autotune.DEFAULT_BLOCKS


def test_kernel_runners_execute_and_agree_with_reference():
    """The sweep/microbench runners drive the REAL kernels: the forward
    runner's normalized output must match dense attention on the same
    operands (guards the runner harness itself against drift)."""
    kw = dict(b=1, h=1, s=128, d=64, dtype=jnp.float32, causal=True)
    fn = autotune.make_kernel_runner("flash_fwd", (64, 64), **kw)
    out, lse = fn()
    # rebuild the runner's operands (same seed path) for the oracle
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    ops = []
    for k_ in keys:
        x = jax.random.normal(k_, (1, 1, 128, 128), jnp.float32)
        ops.append(x.at[..., 64:].set(0.0))
    q, k, v, _ = ops
    # kernel layout (B, H, S, Dp) -> public layout (B, S, H, D)
    to_pub = lambda x: jnp.transpose(x, (0, 2, 1, 3))[..., :64]  # noqa: E731
    ref = dense_attention(to_pub(q), to_pub(k), to_pub(v), causal=True)
    np.testing.assert_allclose(to_pub(out), ref, atol=1e-4, rtol=1e-4)
    secs = autotune.measure_runner(fn, iters=1, warmup=1)
    assert secs > 0.0
    # the backward/carry runners at least execute end to end
    for kern in ("flash_dq", "flash_dkv", "carry_step"):
        rfn = autotune.make_kernel_runner(kern, (64, 128), **kw)
        jax.block_until_ready(rfn())


# ---- structural pin via the analysis walker (round 13) ----------------------


def test_cpu_flash_trace_structure_via_walker():
    """The analysis walker's census over the CPU flash trace: the dense
    interpret-path fallback must contain matmuls but NO pallas_call and NO
    collectives — the same hermeticity the autotune CPU contract promises,
    pinned structurally rather than by string-matching trace text."""
    from distributed_tensorflow_guide_tpu.analysis import walker

    q, k, v = _qkv(s=64, d=64)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: flash_attention(q, k, v, causal=True))(q, k, v)
    census = walker.primitive_census(jaxpr)
    assert census["dot_general"] >= 2  # qk^T and pv
    assert census["pallas_call"] == 0
    assert not walker.collective_census(jaxpr)


# ---- the tracked table and the resolution counter (PR 27) ------------------

V5E = "tpu:tpu-v5-lite"
TRAIN_CELL = dict(b=8, h=16, s=1024, d=64, dtype=jnp.bfloat16)  # gpt2-medium
FLASH_KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


@pytest.fixture()
def tracked_table(monkeypatch):
    """The table git tracks, read as a v5e reads it."""
    monkeypatch.delenv("DTG_AUTOTUNE_TABLE")
    autotune.reset()
    assert autotune.table_path() == autotune.TRACKED_TABLE
    return json.loads(autotune.TRACKED_TABLE.read_text())


@pytest.mark.parametrize("kernel", FLASH_KERNELS)
@pytest.mark.parametrize("shape", ["exact", "generic"])
def test_tracked_table_holds_the_train_cells_flash_entries(
        tracked_table, kernel, shape):
    """The sweep's winners at (8, 16, 1024, 64, bfloat16, causal) on a v5e
    are committed, under the exact key and the batch/head-generic one, and
    each is a tile the sweep may pick."""
    kw = dict(TRAIN_CELL, causal=True, platform=V5E)
    if shape == "generic":
        kw.update(b=3, h=5)  # only the b0|h0 entry can serve this
    b, h = (kw["b"], kw["h"]) if shape == "exact" else (0, 0)
    assert autotune._key(kernel, b, h, 1024, 64, "bfloat16", True,
                         V5E) in tracked_table
    blocks = autotune.lookup(kernel, **kw)
    assert blocks is not None
    assert 1024 % blocks[0] == 0 and 1024 % blocks[1] == 0
    assert blocks in autotune.candidate_blocks(kernel, s=1024, d=64,
                                               dtype=jnp.bfloat16)
    assert blocks != autotune.DEFAULT_BLOCKS  # or the sweep bought nothing


def test_tracked_table_is_not_read_on_the_cpu(tracked_table):
    """Even with no $DTG_AUTOTUNE_TABLE in the way, the platform the tests
    run on resolves the train cell's shape to the default."""
    assert tracked_table  # parsed, and not empty
    for kernel in FLASH_KERNELS:
        assert autotune.blocks_for(
            kernel, **TRAIN_CELL) == autotune.DEFAULT_BLOCKS
    assert autotune._loaded_from is None


def test_resolution_stats_tell_table_generic_and_default(caplog):
    import logging

    kw = dict(s=256, d=64, dtype=jnp.float32, platform="tpu")
    autotune.record("flash_fwd", b=2, h=4, blocks=(64, 128), **kw)
    with caplog.at_level(logging.INFO, logger="dtg.ops.autotune"):
        assert autotune.blocks_for("flash_fwd", b=2, h=4, **kw) == (64, 128)
        assert autotune.blocks_for("flash_fwd", b=1, h=1, **kw) == (64, 128)
        assert autotune.blocks_for(
            "flash_dq", b=2, h=4, **kw) == autotune.DEFAULT_BLOCKS
        autotune.blocks_for("flash_fwd", b=2, h=4, **kw)  # seen: no new line

    def key(kernel, b, h):
        return kernel, autotune._key(kernel, b, h, 256, 64, "float32", True,
                                     "tpu")

    assert autotune.resolution_stats() == {
        key("flash_fwd", 2, 4): {"blocks": (64, 128), "source": "table"},
        key("flash_fwd", 1, 1): {"blocks": (64, 128), "source": "generic"},
        key("flash_dq", 2, 4): {"blocks": (128, 128), "source": "default"},
    }
    lines = [(r.levelname, r.getMessage()) for r in caplog.records]
    assert [lv for lv, _ in lines] == ["INFO", "INFO", "WARNING"]
    assert all(s in m for (_, m), s in zip(
        lines, ("(table)", "(generic)", "(default)")))
    # a key that resolves differently later (a sweep recorded it) says so
    autotune.record("flash_dq", b=2, h=4, blocks=(256, 64), **kw)
    autotune.blocks_for("flash_dq", b=2, h=4, **kw)
    assert autotune.resolution_stats()[key("flash_dq", 2, 4)] == {
        "blocks": (256, 64), "source": "table"}
    autotune.reset()
    assert autotune.resolution_stats() == {}
