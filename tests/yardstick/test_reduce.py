"""The trace reduction on a synthetic trace worked by hand, and on one
small trace recorded on the chip (``yardstick/testdata``)."""

from __future__ import annotations

import json

import pytest

from yardstick import harness
from yardstick import reduce as reduction
from yardstick.readers import (
    device_idle,
    kernel_roofline,
    program_ms,
    program_ms_per_stat,
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


def program_row(name, start_ms, end_ms, line=1, **attrs):
    return [name, start_ms * MS, (end_ms - start_ms) * MS, attrs, line]


def test_a_gap_goes_to_the_innermost_span_that_covers_it():
    """The synthetic window with the program's own spans in it: the gap
    at 20-22 ms lies under ``engine.fetch`` inside ``ys.engine_step``, and
    goes to the fetch; the gap at 40-50 under no program span keeps its
    ``ys`` name; a leaf on another thread covers 0-10 only in part and
    still takes it; a tick's span is no leaf and names nothing."""
    trace = synthetic()
    trace["program"] = [
        program_row("loop.data_wait", 2, 4, line=2),
        program_row("engine.tick", 9, 41, tick=1),
        program_row("engine.dispatch", 9, 10, tick=1),
        program_row("engine.fetch", 19, 39, tick=1),
        program_row("engine.tick", 91, 95, tick=2),  # under fence: no leaf
    ]
    gaps = dict(reduction.idle_gaps_by_span(trace))
    assert gaps["loop.data_wait"] == pytest.approx(0.010)
    assert gaps["engine.fetch"] == pytest.approx(0.002)
    assert gaps["read_loss"] == pytest.approx(0.010)
    assert gaps["engine.tick"] == pytest.approx(0.010)  # the leaf of line 1
    assert "engine_step" not in gaps and "input_wait" not in gaps
    assert sum(gaps.values()) == pytest.approx(0.032)


def test_a_trace_without_the_programs_spans_names_the_benchmarks():
    """Without ``dtg.`` rows (a program before PR 26, or the recorded
    trace) attribution is what it was: the benchmark's spans alone."""
    trace = synthetic()
    trace["program"] = []
    assert reduction.idle_gaps_by_span(trace) == (
        reduction.idle_gaps_by_span(synthetic()))
    assert reduction.events_held(trace) is None


def serve_trace(dropped_ops=False, dropped_run=False):
    """Four launches in a window of 100 ms: decode 10-30, prefill of 3
    chunks 32-44, decode 46-66, prefill of 2 chunks 70-80; each launch's
    dispatch span starts 2 ms before its run. ``dropped_ops`` keeps half
    of the last decode's operations; ``dropped_run`` loses the first
    prefill's run."""
    runs = [("decode_step", 10, 30), ("prefill_chunk_step", 32, 44),
            ("decode_step", 46, 66), ("prefill_chunk_step", 70, 80)]
    programs = [[f"jit_{p}", a * MS, (b - a) * MS] for p, a, b in runs]
    ops = [[f"fusion.{i}", a * MS, (b - a) * MS]
           for i, (_, a, b) in enumerate(runs)]
    if dropped_ops:
        ops[2][2] /= 2
    if dropped_run:
        del programs[1]
    spans = []
    for tick, ((program, a, _), chunks) in enumerate(zip(runs,
                                                         (0, 3, 0, 2))):
        attrs = {"program": program, "overlapped": 1}
        if chunks:
            attrs.update(chunks=chunks, width=4)
        spans.append(program_row("engine.dispatch", a - 2, a - 1.5,
                                 tick=tick, **attrs))
    return {"devices": {"0": {"programs": programs, "ops": ops}},
            "host": [["window_open", 0.0, 0.0],
                     ["window_close", 100 * MS, 0.0]],
            "program": spans}


def test_the_share_of_the_launches_the_trace_holds():
    """What ``events_held`` prints for a run: the launches by the program's
    dispatch spans against the runs and operations the device's lines
    hold. A profiler that stops keeping device events partway through a
    window (Phi-4-mini-flash's, PERF.md section 7) shows as runs missing
    from a time on, with their operations."""
    held = reduction.events_held(serve_trace())
    assert held == {"launches": 4, "programs_held": 1.0,
                    "ops_held": pytest.approx(1.0),
                    "held_s": pytest.approx(0.080)}
    held = reduction.events_held(serve_trace(dropped_ops=True))
    assert held["ops_held"] == pytest.approx(52 / 62)
    cut = serve_trace()
    for key in ("programs", "ops"):  # nothing after 45 ms
        cut["devices"]["0"][key] = cut["devices"]["0"][key][:2]
    held = reduction.events_held(cut)
    assert held["programs_held"] == 0.5 and held["ops_held"] == 1.0
    assert held["held_s"] == pytest.approx(0.044)
    # the idle share reads what the trace holds, as it did
    assert reduction.busy_and_window_s(cut)[0] == pytest.approx(0.032)


def test_prefill_device_time_per_chunk_pairs_runs_with_their_launches():
    facts = {"trace": serve_trace()}
    # 12 + 10 ms of prefill runs for 3 + 2 chunks
    got = program_ms_per_stat.read(facts, program="prefill_chunk_step",
                                   stat="chunks")
    assert got == pytest.approx(22 / 5)
    # a dropped run: its launch pairs with nothing and counts nothing
    facts = {"trace": serve_trace(dropped_run=True)}
    assert program_ms_per_stat.read(
        facts, program="prefill_chunk_step", stat="chunks") == (
            pytest.approx(10 / 2))
    # a dropped dispatch: its run would pair with the launch before,
    # which has its own run, so it is left out
    trace = serve_trace()
    del trace["program"][3]
    assert program_ms_per_stat.read(
        {"trace": trace}, program="prefill_chunk_step",
        stat="chunks") == pytest.approx(12 / 3)
    assert program_ms_per_stat.read(
        facts, program="decode_step", stat="chunks") is None
    facts["trace"]["program"] = []  # a program before the spans
    assert program_ms_per_stat.read(
        facts, program="prefill_chunk_step", stat="chunks") is None


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
    # recorded before the program's rows were kept: read as none
    assert "program" not in trace
    assert reduction.leaf_spans(trace) == []
    assert reduction.events_held(trace) is None
    assert reduction.read_metric("data_wait_ms.train",
                                 {"trace": trace, "steps": 2}) is None
