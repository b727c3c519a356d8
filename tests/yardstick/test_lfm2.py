"""The LFM2 cell's own pieces: the configuration file against the source's
``config.json`` by hand, ``counts_lfm2`` against a count by hand, the
driver end to end at a size the CPU holds (the program correct, the int8
control not), and the readers this PR adds on made-up traces."""

from __future__ import annotations

import copy
import json
import struct

import jax
import numpy as np
import pytest

from yardstick import compare, control_lfm2, counts_lfm2, harness
from yardstick import scoped_ops, weights_lfm2
from yardstick import run as command
from yardstick.readers import routed_roofline, scope_share, span_stat

CELL = "lfm2-24b-a2b.serve.sharegpt-backlog"
_load_cell = harness.load_cell

#: huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json, by hand
PUBLISHED = dict(
    conv_L_cache=3, conv_bias=False, hidden_size=2048,
    intermediate_size=11776, max_position_embeddings=128000,
    model_type="lfm2_moe", moe_intermediate_size=1536, norm_eps=1e-05,
    norm_topk_prob=True, num_attention_heads=32, num_experts=64,
    num_experts_per_tok=4, num_hidden_layers=40, num_key_value_heads=8,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    routed_scaling_factor=1, use_expert_bias=True, vocab_size=65536)
PERIOD = ["full_attention", "conv", "conv", "conv"]

#: a tiny cell's, not the chip's: at width 64 with weights drawn at 0.1 a
#: bfloat16 run reads 0-0.012 over seeds and the int8 control 0.029-0.052
LIMITS = {"served_logit_gap_mean": 0.02}


def tiny_cell(name: str = CELL, *args, **kwargs) -> harness.Cell:
    c = copy.deepcopy(_load_cell(name))
    c.config.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=96, moe_intermediate_size=32, num_experts=8,
        num_experts_per_tok=2, vocab_size=512, num_dense_layers=1,
        layer_types=["conv", "full_attention", "conv", "conv"])
    c.config["assumed"]["drawn"].update(initializer_range=0.1,
                                        router_std=0.5)
    c.config["deployment"].update(slots=4, block_size=8, num_blocks=33,
                                  prefill_chunk=8, max_positions=64)
    c.traffic.update(
        requests=24, vocab_below=512, sizes=8, checked_requests=4,
        prompt={"mean": 13, "sigma": 0.5, "min": 4, "max": 30},
        output={"mean": 11, "sigma": 0.4, "min": 4, "max": 24})
    return c


@pytest.fixture()
def tiny(monkeypatch):
    monkeypatch.setattr(harness, "load_cell", tiny_cell)
    monkeypatch.setattr(compare, "load_limits", lambda name: LIMITS)
    monkeypatch.setattr(harness, "setup_compile_cache", lambda: "off")
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda devices: 1)


# ---- the configuration ----------------------------------------------------


def test_the_configuration_holds_the_rows_values_key_by_key():
    manifest = harness.load_json(harness.MANIFEST)
    entry = {c["name"]: c for c in manifest["configs"]}["lfm2-24b-a2b"]
    held = harness.load_json(harness.ROOT / entry["file"])
    assert entry["source"] == held["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    for key, value in PUBLISHED.items():
        assert held[key] == value, key
    # the one cut, in depth, under names the width pattern leaves alone
    assert held["reduced"] == entry["reduced"] == ["layer_types",
                                                   "num_dense_layers"]
    assert held["layer_types"] == ["conv"] + PERIOD + PERIOD
    assert held["num_dense_layers"] == 1
    assert held["published"]["num_dense_layers"] == {"published": 2,
                                                     "held": 1}
    assert set(held["published"]) == {"what", "layer_types",
                                      "num_dense_layers"}
    # what the row does not give is assumed, each said
    assert {"head_dim", "tied_head", "drawn"} <= set(held["assumed"])
    drawn = held["assumed"]["drawn"]
    assert drawn["initializer_range"] == 0.02
    assert {"router_std", "expert_bias_std", "conv_std"} <= set(drawn)
    dep = held["deployment"]
    assert (dep["compute_dtype"], dep["weights_dtype"]) == ("bfloat16",) * 2
    assert (dep["slots"], dep["block_size"], dep["num_blocks"],
            dep["prefill_chunk"], dep["max_positions"],
            dep["temperature"]) == (64, 128, 1024, 128, 2048, 0.0)
    assert dep["chips_that_share_a_layer"] == 1
    # the pool holds the worst case: every slot at the longest request
    mix = _load_cell(CELL).traffic
    longest = mix["prompt"]["max"] + mix["output"]["max"]
    assert longest <= dep["max_positions"]
    assert (dep["slots"] * dep["max_positions"] // dep["block_size"]
            < dep["num_blocks"] + 1)
    assert mix["vocab_below"] == held["vocab_size"]


def test_the_sizes_and_the_parameter_count_of_the_cell():
    z = weights_lfm2.sizes_of(_load_cell(CELL).config)
    assert (z["d"], z["h"], z["kv"], z["hd"]) == (2048, 32, 8, 64)
    assert (z["ff"], z["eff"], z["E"], z["k"], z["taps"]) == (
        11776, 1536, 64, 4, 3)
    assert z["layers"][0] == ("short_conv", "dense")
    assert z["layers"][1] == ("attention", "routed")
    assert sum(m == "attention" for m, _ in z["layers"]) == 2
    assert sum(f == "routed" for _, f in z["layers"]) == 8
    count = sum(int(np.prod(shape(z)))
                for kinds in z["layers"]
                for shape, _ in weights_lfm2.layer_spec(kinds).values())
    count += sum(int(np.prod(shape(z)))
                 for shape, _ in weights_lfm2._TOP.values())
    assert 5.30e9 < count < 5.32e9  # ISSUE 28: 5.31 B, 10.6 GB in bfloat16


def test_counts_against_a_count_by_hand():
    z = dict(d=8, h=2, kv=1, hd=4, ff=16, eff=4, E=4, k=2, taps=3, vocab=32,
             layers=(("short_conv", "dense"), ("attention", "routed")))
    conv = 2 * (8 * 24 + 8 * 8 + 3 * 8)           # in, out, taps
    dense = 3 * 2 * 8 * 16
    proj = 2 * (8 * (2 + 2) * 4 + 8 * 8)          # q, k, v; output
    routed = 2 * 8 * 4 + 2 * (3 * 2 * 8 * 4)      # router; 2 experts
    assert counts_lfm2.conv_mixer_flops(z) == conv == 560
    assert counts_lfm2.routed_ffn_flops(z) == routed == 448
    # one token at position 5 attends 6 keys: scores and the weighted sum
    attend = 2 * 2 * 8 * 6
    assert counts_lfm2.token_flops(z, position=5) == (
        conv + dense + proj + attend + routed)
    # positions [2, 5) attend 3 + 4 + 5 keys
    assert counts_lfm2.span_flops(z, start=2, stop=5) == (
        3 * (conv + dense + proj + routed) + 2 * 2 * 8 * 12)
    assert counts_lfm2.head_flops(z, rows=3) == 3 * 2 * 8 * 32
    flops, nbytes = counts_lfm2.routed_layer(z, rows=5, experts_touched=3)
    assert flops == 5 * routed
    assert nbytes == 3 * 3 * 8 * 4 * 2 + 8 * 4 * 4 + 2 * 5 * 8 * 2
    flops, nbytes = counts_lfm2.paged_decode(z, live_keys=40, rows=5)
    assert flops == 2 * 2 * 8 * 40
    assert nbytes == 2 * 40 * 1 * 4 * 2 + 2 * 5 * 8 * 2  # 1 pool head, 2 query


# ---- the driver at a size the CPU holds ------------------------------------


def test_a_whole_run_is_correct_and_the_control_is_not(tiny, capsys):
    seed = 2 ** 31 + 2828
    rc = command.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       "0.4", "--trace", "0"], devices=jax.devices()[:1])
    assert rc == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True, out.err[-2000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s"}
    assert set(line["compared"]) == {"served_logit_gap_mean"}

    cell = harness.load_cell(CELL)
    row = control_lfm2.readings(cell, seed, jax.devices()[:1], 0.2)
    verdicts = control_lfm2.control.verdicts(row, LIMITS)
    assert verdicts == {"program": True, "control": False}, row
    assert row["control"]["served_logit_gap_mean"] > row["program"][
        "served_logit_gap_mean"]
    assert 0 <= row["choices_moved"] <= row["choices_checked"]
    assert row["choices_checked"] == 3 * row["checked_tokens"]


def test_the_windows_work_counts_valid_tokens_only(tiny):
    from yardstick.spans import Spans

    cell = harness.load_cell(CELL)
    driver = cell.driver.Driver(cell, 77, jax.devices()[:1], Spans())
    ran = driver.run(0.3, command.Window(Spans(), None))
    facts = ran["facts"]
    z = driver.sizes
    assert facts["decode_launches"] and facts["model_flops"] > 0
    slots = cell.config["deployment"]["slots"]
    assert all(1 <= rows <= slots and keys >= rows
               for rows, keys in facts["decode_launches"])
    # every token handed back costs at least the head and a token's trunk
    floor = facts["tokens"] * (counts_lfm2.head_flops(z)
                               + counts_lfm2.token_flops(z, position=0))
    assert facts["model_flops"] >= floor
    driver.release()


# ---- the readers, on made-up traces ----------------------------------------


def _varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def test_scope_paths_reads_an_operations_scope_out_of_the_bytes(tmp_path):
    """An XSpace written field by field as ``xplane.proto`` numbers them:
    one device plane whose two operations carry ``tf_op``, one as a string
    and one as a reference into the stat names, and a host plane."""
    stat_names = {7: "tf_op", 9: "flops", 11: "jit(f)/dtg.routed/dot"}
    stat_meta = b"".join(
        _field(5, _field(1, k) + _field(2, _field(1, k) + _field(2, v)))
        for k, v in stat_names.items())
    op_a = (_field(1, 1) + _field(2, "%fusion.1 = f32[8] fusion()")
            + _field(5, _field(1, 9) + _field(4, 64))
            + _field(5, _field(1, 7)
                     + _field(5, "jit(f)/block_1/dtg.short_conv/mul")))
    op_b = (_field(1, 2) + _field(2, "%dot.2 = f32[8] dot()")
            + _field(5, _field(1, 7) + _field(7, 11)))
    line = _field(2, "XLA Ops") + _field(4, _field(1, 1) + _field(3, 5000))
    device = (_field(2, "/device:TPU:0") + _field(3, line) + stat_meta
              + _field(4, _field(1, 1) + _field(2, op_a))
              + _field(4, _field(1, 2) + _field(2, op_b))
              + _field(6, _field(1, 9) + _field(2, 1.5)))
    host = (_field(2, "/host:CPU")
            + _field(4, _field(1, 1) + _field(2, _field(2, "%fusion.1"))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host))
    assert scoped_ops.scope_paths(path) == {
        "%fusion.1 = f32[8] fusion()": "jit(f)/block_1/dtg.short_conv/mul",
        "%dot.2 = f32[8] dot()": "jit(f)/dtg.routed/dot"}


def made_up_facts():
    """Two decode launches and one prefill launch in a window of 100 us."""
    programs = [["jit_decode_step", 10_000.0, 20_000.0],
                ["jit_prefill_chunk_step", 40_000.0, 10_000.0],
                ["jit_decode_step", 60_000.0, 20_000.0]]
    trace = {"devices": {"0": {"programs": programs, "ops": []}},
             "host": [["window_open", 0.0, 1.0],
                      ["window_close", 100_000.0, 1.0]]}
    z = dict(d=8, h=2, kv=1, hd=4, ff=16, eff=4, E=4, k=2, taps=3, vocab=32,
             layers=(("short_conv", "dense"), ("attention", "routed")))
    return {"trace": trace, "sizes": z,
            "peaks": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e8}}


def made_up_rows():
    path = "jit(decode_step)/Transformer/block_1/"
    scoped = {"0": [
        ["%a", 11_000.0, 4_000.0, path + "dtg.routed/mlp/dtg.routed.route/x"],
        ["%b", 16_000.0, 6_000.0, path + "dtg.attn/attn/y"],
        ["%p", 41_000.0, 9_000.0, path + "dtg.routed/mlp/z"],  # prefill
        ["%c", 61_000.0, 8_000.0, path + "dtg.routed/mlp/dtg.routed.experts/w"],
        ["%d", 70_000.0, 2_000.0, "jit(decode_step)/Transformer/lm_head/dot"],
        # the compiler's own grouped product: its path is its name
        ["ragged-dot-none.7 pallas:7->bf16", 72_000.0, 4_000.0,
         "ragged-dot-none"],
    ]}
    spans = []
    for tick, (start, program, kind, rows, stats) in enumerate([
            (9_000.0, "decode_step", "decode", 5,
             {"experts_touched": 3.0, "load_ratio": 2.0}),
            (39_000.0, "prefill_chunk_step", "prefill", 1,
             {"experts_touched": 4.0, "load_ratio": 1.0}),
            (59_000.0, "decode_step", "decode", 4,
             {"experts_touched": 2.0, "load_ratio": 1.5})]):
        spans += [
            ["engine.tick", start, 25_000.0, {"tick": tick}, 1],
            ["engine.build", start + 100, 100.0,
             {"tick": tick, "kind": kind, "rows": rows}, 1],
            ["engine.dispatch", start + 300, 500.0,
             {"tick": tick, "program": program}, 1],
            ["engine.apply", start + 24_000, 500.0, {"tick": tick, **stats},
             1]]
    return scoped, spans


@pytest.fixture()
def made_up(monkeypatch):
    scoped, spans = made_up_rows()
    monkeypatch.setattr(scoped_ops, "load", lambda trace: scoped)
    facts = made_up_facts()
    facts["trace"]["program"] = spans
    return facts


def test_scope_share_is_the_scopes_part_of_the_programs_device_time(made_up):
    share = scope_share.read(made_up, program="decode_step",
                             scope="dtg.routed")
    assert share == pytest.approx(100.0 * 12_000 / 24_000)
    share = scope_share.read(made_up, program="decode_step",
                             scope="dtg.routed", also_named=["ragged-dot"])
    assert share == pytest.approx(100.0 * 16_000 / 24_000)
    assert scope_share.read(made_up, program="decode_step",
                            scope="dtg.short_conv") is None  # nothing there
    assert scope_share.read(made_up, program="no_such_program",
                            scope="dtg.routed") is None


def test_span_stat_means_a_spans_number_over_one_programs_ticks(made_up):
    args = dict(span="engine.apply", stat="experts_touched")
    assert span_stat.read(made_up, program="decode_step", **args) == 2.5
    assert span_stat.read(made_up, **args) == 3.0
    assert span_stat.read(made_up, span="engine.apply",
                          stat="no_such_stat") is None


def test_routed_roofline_is_least_time_over_the_scopes_time(made_up):
    z, peaks = made_up["sizes"], made_up["peaks"]
    least = 0.0
    for rows, touched in ((5, 3.0), (4, 2.0)):  # the two decode launches
        flops, nbytes = counts_lfm2.routed_layer(z, rows=rows,
                                                 experts_touched=touched)
        least += max(flops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])  # one routed layer
    got = routed_roofline.read(made_up)
    assert got == pytest.approx(100.0 * least / 12e-6)
    got = routed_roofline.read(made_up, also_named=["ragged-dot"])
    assert got == pytest.approx(100.0 * least / 16e-6)
    # a program whose spans carry no census leaves the metric out
    for row in made_up["trace"]["program"]:
        row[3].pop("experts_touched", None)
    assert routed_roofline.read(made_up) is None


def test_the_new_kernel_file_counts_the_pools_heads():
    from yardstick.kernels import paged_decode_gqa as kernel

    assert kernel.matches("attn._paged_decode_attend.85 pallas:5->bf16")
    assert not kernel.matches("ragged-dot-none.3 pallas:7->bf16")
    assert not kernel.matches("attn._paged_decode_attend.85")
    facts = made_up_facts()
    facts["decode_launches"] = [(3, 100), (5, 300)]
    events = [["k", 0.0, 1.0]]  # one attention layer: the last launch's
    flops, nbytes = counts_lfm2.paged_decode(facts["sizes"], live_keys=300,
                                             rows=5)
    assert kernel.least_seconds(facts, events) == pytest.approx(
        max(flops / 1e9, nbytes / 1e8))
