"""The trace reduction on a synthetic trace worked by hand, and on one
small trace recorded on the chip (``yardstick/testdata``)."""

from __future__ import annotations

import json

import pytest

from yardstick import harness
from yardstick import reduce as reduction
from yardstick.readers import (
    device_idle,
    host_ms_per_tick,
    kernel_roofline,
    program_ms,
    span_ms,
    token_gap_ms,
)
from yardstick.readers import mfu as mfu_reader

MS = 1e6  # ns
KERNEL = "attn.72 pallas:3->bf16+f32"  # how a trace labels flash forward


def synthetic():
    """A window of 100 ms. Two runs of ``jit_step`` (10-40, 50-90 ms), ops
    inside them with a 2 ms hole in the first, one stray op before the
    window; host spans on the same clock."""
    ops = [["fusion.1", 10 * MS, 10 * MS], [KERNEL, 22 * MS, 8 * MS],
           ["fusion.2", 30 * MS, 10 * MS],
           ["fusion.1", 50 * MS, 20 * MS], [KERNEL, 70 * MS, 20 * MS],
           ["copy.9", -5 * MS, 2 * MS]]
    return {
        "devices": {"0": {
            "programs": [["jit_step", 10 * MS, 30 * MS],
                         ["jit_step", 50 * MS, 40 * MS]],
            "ops": sorted(ops, key=lambda r: r[1])}},
        "host": [["window_open", 0.0, 0.0],
                 ["input_wait", 1 * MS, 8 * MS],
                 ["dispatch", 9 * MS, 1 * MS],
                 ["engine_step", 9 * MS, 33 * MS],
                 ["read_loss", 40 * MS, 11 * MS],
                 ["engine_step", 49 * MS, 45 * MS],
                 ["fence", 90 * MS, 9 * MS],
                 ["window_close", 100 * MS, 0.0]],
    }


def test_union_clip_gaps_by_hand():
    u = reduction.union([(5, 7), (1, 3), (2, 4), (7, 8), (9, 9)])
    assert u == [(1, 4), (5, 8)]
    assert reduction.total(u) == 6
    assert reduction.clip(u, 2, 6) == [(2, 4), (5, 6)]
    assert reduction.gaps(u, 0, 10) == [(0, 1), (4, 5), (8, 10)]
    assert reduction.gaps([], 0, 10) == [(0, 10)]


def test_busy_window_and_idle_share():
    trace = synthetic()
    assert reduction.window_ns(trace) == (0.0, 100 * MS)
    busy_s, window_s = reduction.busy_and_window_s(trace)
    # 10-20, 22-40, 50-90: 10 + 18 + 40 ms; the op before the window is out
    assert busy_s == pytest.approx(0.068)
    assert window_s == pytest.approx(0.100)
    facts = {"busy_s": busy_s, "window_s": window_s}
    assert device_idle.read(facts) == pytest.approx(32.0)


def test_program_and_kernel_time():
    trace = synthetic()
    runs = reduction.program_events(trace, "0", "step")
    assert [r[2] for r in runs] == [30 * MS, 40 * MS]
    facts = {"trace": trace, "program": "step"}
    assert program_ms.read(facts) == pytest.approx(35.0)
    assert program_ms.read(facts, stat="median") == pytest.approx(35.0)
    assert program_ms.read(facts, program="absent") is None
    inside = reduction.ops_within(trace, "0", [(50 * MS, 90 * MS)])
    assert [r[0] for r in inside] == ["fusion.1", KERNEL]
    assert reduction.family("fusion.123") == "fusion"
    assert reduction.family("fusion.84.remat") == "fusion.remat"
    assert reduction.family(KERNEL) == "attn pallas:3->bf16+f32"


def test_op_label_from_hlo_text():
    text = ('%attn.72 = (bf16[8,16,1024,128]{3,2,1,0:T(8,128)(2,1)S(1)}, '
            'f32[8,16,1024,128]{3,2,1,0:T(8,128)}) custom-call('
            'bf16[8,16,1024,128]{3,2,1,0} %pad.1, bf16[8,16,1024,128]{3,2,1,0}'
            ' %pad.2, bf16[8,16,1024,128]{3,2,1,0} %pad.3), '
            'custom_call_target="tpu_custom_call", operand_layout={}')
    assert reduction.op_label(text) == "attn.72 pallas:3->bf16+f32"
    assert reduction.op_label(
        "%fusion.12 = bf16[8,1024]{1,0} fusion(bf16[8]{0} %p.1), "
        "kind=kLoop") == "fusion.12"
    assert reduction.op_label("plain-name") == "plain-name"


def test_gap_attribution_and_breakdown():
    trace = synthetic()
    gaps = dict(reduction.idle_gaps_by_span(trace))
    # 0-10 ms: input_wait covers 8 of it; 20-22: engine_step (the only
    # span there); 40-50: read_loss; 90-100: fence
    assert gaps["input_wait"] == pytest.approx(0.010)
    assert gaps["read_loss"] == pytest.approx(0.010)
    assert gaps["fence"] == pytest.approx(0.010)
    assert gaps["engine_step"] == pytest.approx(0.002)
    assert sum(gaps.values()) == pytest.approx(0.032)
    top = reduction.top_device_ops(trace)
    assert top[0] == ["fusion", pytest.approx(0.040)]
    assert top[1] == ["attn pallas:3->bf16+f32", pytest.approx(0.028)]
    assert len(top) <= 10


def test_host_time_per_tick_and_span_means():
    trace = synthetic()
    # ticks of 33 and 45 ms that held 30 and 40 ms of programs
    assert host_ms_per_tick.read({"trace": trace}) == pytest.approx(4.0)
    spans = [("input_wait", 1.0, 1.002), ("input_wait", 2.0, 2.004),
             ("input_wait", 0.1, 0.9)]  # the last lies before the window
    facts = {"spans": spans, "t_open": 0.95, "t_close": 3.0, "steps": 3}
    assert span_ms.read(facts, span="input_wait", per="steps") == (
        pytest.approx(2.0))
    assert span_ms.read(facts, span="input_wait") == pytest.approx(3.0)
    assert span_ms.read(facts, span="absent") is None


def test_roofline_share_and_mfu_from_the_synthetic_trace(capsys):
    trace = synthetic()
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    facts = {"trace": trace, "peaks": peaks, "batch": 8, "seq": 1024,
             "sizes": {"h": 16, "hd": 64}, "window_s": 0.1,
             "model_flops": 0.25 * 0.1 * 197e12}
    share = kernel_roofline.read(facts, kernels=["flash_fwd"])
    # two calls, 28 ms of kernel time; the least time is microseconds
    assert 0.0 < share < 100.0
    capsys.readouterr()
    # a listed kernel that matches nothing while Pallas calls ran: silent
    # in the result, loud on standard error (the kernels carry no names,
    # and one that gained an operand would otherwise just vanish)
    assert kernel_roofline.read(facts, kernels=["flash_bwd_dq"]) is None
    said = capsys.readouterr().err
    assert "flash_bwd_dq" in said and "attn pallas:3->bf16+f32" in said
    assert mfu_reader.read(facts) == pytest.approx(25.0)
    assert mfu_reader.read({**facts, "model_flops": 0}) is None


def test_token_gap_percentile():
    times = {1: [0.1 * i for i in range(30)], 2: [0.0, 0.5]}
    # 29 gaps of 0.1 s and one of 0.5 s
    p95 = token_gap_ms.read({"token_times": times}, percentile=95)
    assert 100.0 <= p95 <= 500.0
    assert token_gap_ms.read({"token_times": {1: [0.0, 0.1]}}) is None


def test_a_reader_with_nothing_to_read_is_left_out(capsys):
    assert reduction.read_metric("flash_roofline.train", {
        "trace": {"devices": {"0": {"programs": [], "ops": []}},
                  "host": synthetic()["host"]},
        "peaks": {}, "sizes": {}, "batch": 1, "seq": 1}) is None
    assert capsys.readouterr().err == ""  # no kernel ran: nothing to say


def test_memory_peak_counts_the_programs_temporaries():
    """The TPU runtime keeps them under ``bytes_reserved`` (PERF.md)."""
    class Chip:
        def __init__(self, **stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    chips = [Chip(peak_bytes_in_use=5, peak_bytes_reserved=9, bytes_in_use=1),
             Chip(peak_bytes_in_use=11)]
    assert harness.memory_peak_bytes(chips) == 14
    assert harness.memory_peak_bytes(chips[1:]) == 11


@pytest.fixture(scope="module")
def recorded():
    path = harness.HERE / "testdata" / "train_small_trace.json"
    assert path.stat().st_size < 1_000_000
    with open(path) as f:
        return json.load(f)


def test_recorded_trace_reduces(recorded):
    """A few steps of a small train step on the v5e, as ``load_xplane``
    gave it: planes, programs and kernels are where the reduction looks."""
    trace, want = recorded["trace"], recorded["expected"]
    busy_s, window_s = reduction.busy_and_window_s(trace)
    assert 0.0 < busy_s <= window_s
    assert busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert window_s == pytest.approx(want["window_s"], rel=1e-9)
    runs = reduction.program_events(trace, "0", "sm_step")
    assert len(runs) == want["program_runs"] > 0
    ops = reduction.device_rows(trace, "ops", "0")
    from yardstick.kernels import flash_bwd_dkv, flash_bwd_dq, flash_fwd
    for kernel, key in ((flash_fwd, "fwd"), (flash_bwd_dq, "dq"),
                        (flash_bwd_dkv, "dkv")):
        assert sum(kernel.matches(r[0]) for r in ops) == want[key] > 0
    gaps = reduction.idle_gaps_by_span(trace)
    assert sum(s for _, s in gaps) == pytest.approx(window_s - busy_s,
                                                    rel=1e-6)
