"""The Nemotron-H cell's own pieces: the configuration file against the
catalog's row key by key, ``counts_nemotron`` against a count by hand, the
driver end to end at a size the CPU holds (the program correct, the int8
control not), and the reader this PR adds on a made-up trace."""

from __future__ import annotations

import copy
import json
import os
import re

import jax
import numpy as np
import pytest

from tests.yardstick.test_lfm2 import made_up_facts
from yardstick import compare, control, control_nemotron, counts_nemotron
from yardstick import harness, scoped_ops, weights_nemotron
from yardstick import run as command
from yardstick.readers import scope_roofline

CELL = "nemotron-3-nano-30b-a3b.serve.sharegpt-backlog"
NAME = "nemotron-3-nano-30b-a3b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
_load_cell = harness.load_cell

#: huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/
#: config.json, by hand (the catalog's row, where this machine has it, is
#: compared key by key below)
PUBLISHED = dict(
    attention_bias=False, chunk_size=128, conv_kernel=4, expand=2,
    head_dim=128, hidden_size=2688, intermediate_size=1856,
    layer_norm_epsilon=1e-05, mamba_head_dim=64, mamba_hidden_act="silu",
    mamba_num_heads=64, mamba_proj_bias=False,
    max_position_embeddings=262144, mlp_bias=False, mlp_hidden_act="relu2",
    model_type="nemotron_h", moe_intermediate_size=1856,
    moe_shared_expert_intermediate_size=3712, n_group=1, n_groups=8,
    n_shared_experts=1, norm_eps=1e-05, norm_topk_prob=True,
    num_attention_heads=32, num_experts_per_tok=6, num_hidden_layers=52,
    num_key_value_heads=2, partial_rotary_factor=1, rope_theta=10000,
    routed_scaling_factor=2.5, ssm_state_size=128,
    tie_word_embeddings=False, time_step_floor=0.0001, time_step_max=0.1,
    time_step_min=0.001, topk_group=1, use_bias=False, use_conv_bias=True)
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

#: a tiny cell's, not the chip's: at width 48 with weights drawn at 0.1,
#: over all 24 requests' 459 served tokens, a bfloat16 run reads
#: 0.0005-0.0035 over six seeds and the int8 control 0.0052-0.0166 (3 to 14
#: times its seed's program); the seed the test uses 0.0024 and 0.0117
LIMITS = {"served_logit_gap_mean": 0.005}
#: longer than the tiny backlog takes: the window closes when the last of
#: the 24 requests is served, so what is checked does not depend on how
#: fast the machine is that day
WINDOW_S = 5.0


def tiny_cell(name: str = CELL, *args, **kwargs) -> harness.Cell:
    c = copy.deepcopy(_load_cell(name))
    c.config.update(
        hidden_size=48, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, intermediate_size=40, moe_intermediate_size=24,
        moe_shared_expert_intermediate_size=40, n_routed_experts=4,
        num_experts_per_tok=3, mamba_num_heads=4, mamba_head_dim=8,
        n_groups=2, ssm_state_size=16, chunk_size=8, vocab_size=512,
        hybrid_override_pattern="MEM*E")
    c.config["published"]["n_routed_experts"]["published"] = 8
    c.config["assumed"]["drawn"].update(initializer_range=0.1,
                                        router_std=0.5)
    c.config["deployment"].update(
        slots=4, block_size=8, num_blocks=33, prefill_chunk=8,
        max_positions=64, experts_held=[0, 4], expert_width_stored=32)
    c.traffic.update(
        requests=24, vocab_below=512, sizes=8, checked_requests=24,
        prompt={"mean": 13, "sigma": 0.5, "min": 4, "max": 30},
        output={"mean": 20, "sigma": 0.4, "min": 8, "max": 30})
    return c


@pytest.fixture()
def tiny(monkeypatch):
    monkeypatch.setattr(harness, "load_cell", tiny_cell)
    monkeypatch.setattr(compare, "load_limits", lambda name: LIMITS)
    monkeypatch.setattr(harness, "setup_compile_cache", lambda: "off")
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda devices: 1)


# ---- the configuration ----------------------------------------------------


def test_the_configuration_holds_the_published_values_key_by_key():
    manifest = harness.load_json(harness.MANIFEST)
    entry = {c["name"]: c for c in manifest["configs"]}[NAME]
    held = harness.load_json(harness.ROOT / entry["file"])
    assert entry["source"] == held["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
        "/blob/main/config.json")
    for key, value in PUBLISHED.items():
        assert held[key] == value, key
    # the three cuts: depth, and the two things a layer's two chips divide
    assert held["reduced"] == entry["reduced"] == [
        "hybrid_override_pattern", "n_routed_experts", "vocab_size"]
    assert held["hybrid_override_pattern"] == PATTERN[:13] == "MEMEM*EMEMEM*"
    assert (held["n_routed_experts"], held["vocab_size"]) == (64, 65536)
    pub = held["published"]
    assert set(pub) == {"what", *held["reduced"]}
    assert pub["hybrid_override_pattern"]["published"] == PATTERN
    assert pub["hybrid_override_pattern"]["held"].startswith(PATTERN[:13])
    assert pub["n_routed_experts"] == {"published": 128, "held": 64}
    assert pub["vocab_size"] == {"published": 131072, "held": 65536}
    # what the row does not decide is assumed, each said
    assumed = held["assumed"]
    assert {"positions", "state_dtype", "drawn", "sampling",
            "precision"} <= set(assumed)
    assert "float32" in assumed["state_dtype"]
    assert assumed["positions"].startswith("none")
    assert {"initializer_range", "router_std", "expert_bias_std", "conv_std",
            "a_max"} <= set(assumed["drawn"])
    dep = held["deployment"]
    assert (dep["chips"], dep["chips_in_the_deployment"],
            dep["chips_that_share_a_layer"]) == (1, 8, 2)
    assert dep["experts_held"] == [0, 64]
    assert (dep["compute_dtype"], dep["weights_dtype"],
            dep["state_dtype"]) == ("bfloat16", "bfloat16", "float32")
    assert (dep["slots"], dep["block_size"], dep["num_blocks"],
            dep["prefill_chunk"], dep["max_positions"],
            dep["temperature"]) == (128, 128, 2048, 128, 2048, 0.0)
    assert dep["prefill_chunk"] == held["chunk_size"]
    # the banks' stored width: whole lanes, and said to be storage only
    assert dep["expert_width_stored"] == 1920 == -(-1856 // 128) * 128
    assert "relu(0)^2" in dep["expert_width_stored_why"]
    # the pool holds the worst case: every slot at the longest request
    mix = _load_cell(CELL).traffic
    assert mix["prompt"]["max"] + mix["output"]["max"] <= dep["max_positions"]
    assert (dep["slots"] * dep["max_positions"] // dep["block_size"]
            < dep["num_blocks"] + 1)
    assert mix["vocab_below"] == held["vocab_size"]


@pytest.mark.parametrize("key", ["hybrid_override_pattern",
                                 "n_routed_experts", "vocab_size"])
def test_a_reduced_key_states_published_and_held(key):
    """``test_manifest.py``'s ``PUBLISHED`` case for a configuration that
    holds a share of the model (that file is the accepted benchmark's and
    is not edited): every key outside ``reduced`` equals the catalog row's
    value, and each reduced key is held at the share's value with the
    published one said beside it."""
    manifest = harness.load_json(harness.MANIFEST)
    entry = {c["name"]: c for c in manifest["configs"]}[NAME]
    held = harness.load_json(harness.ROOT / entry["file"])
    published = dict(PUBLISHED)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        (row,) = [r for r in rows
                  if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"]
        assert entry["source"] == held["source"] == row["source_url"]
        assert {k: v for k, v in row["config"].items()
                if k in published} == published
        published = row["config"]
    for other, value in published.items():
        if other not in held["reduced"]:
            assert held[other] == value, other
    assert key in held["reduced"] and key in entry["reduced"]
    value = {"hybrid_override_pattern": PATTERN, "n_routed_experts": 128,
             "vocab_size": 131072}[key]
    assert published.get(key, value) == value
    stated = held["published"][key]
    assert stated["published"] == value != held[key]
    assert str(stated["held"]).startswith(str(held[key]))
    # no reduced key is a width (test_manifest.py's pattern)
    width = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|head_size"
                       r"|n_embd|n_inner|d_model|d_ff|experts_per_tok")
    assert not width.search(key)


def test_the_sizes_and_the_bytes_of_the_cell():
    z = weights_nemotron.sizes_of(_load_cell(CELL).config)
    assert (z["d"], z["h"], z["kv"], z["hd"]) == (2688, 32, 2, 128)
    assert (z["H"], z["P"], z["G"], z["N"], z["inner"], z["wide"],
            z["taps"]) == (64, 64, 8, 128, 4096, 6144, 4)
    assert (z["eff"], z["sff"], z["E"], z["first"], z["held"], z["k"],
            z["scale"]) == (1856, 3712, 128, 0, 64, 6, 2.5)
    kinds = [m or f for m, f in z["layers"]]
    assert (kinds.count("mamba2"), kinds.count("routed"),
            kinds.count("attention")) == (6, 5, 2)
    assert all((m is None) != (f is None) for m, f in z["layers"])

    def params(kind):
        return sum(int(np.prod(shape(z))) for shape, _ in
                   weights_nemotron.layer_spec(kind).values())

    # ISSUE 33: a Mamba-2 layer 38.7 M, an attention layer 23.4 M, a
    # routed layer 64 x 9.98 M + 19.96 M shared + the router
    assert 38.7e6 < params(("mamba2", None)) < 38.8e6
    assert 23.3e6 < params(("attention", None)) < 23.5e6
    routed = params((None, "routed"))
    assert routed == (64 * 2 * 2688 * 1856 + 2 * 2688 * 3712 + 2688 * 128
                      + 128 + 2688)
    total = sum(params(k) for k in z["layers"]) + sum(
        int(np.prod(shape(z))) for shape, _ in weights_nemotron._TOP.values())
    assert 3.91e9 < total < 3.94e9  # 7.85 GB in bfloat16
    # the state beside the pool: 2 MB a slot a layer in float32, plus the
    # convolution's inputs; three times the KV pool
    dep = _load_cell(CELL).config["deployment"]
    state = 6 * dep["slots"] * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    pool = 2 * 2 * dep["num_blocks"] * 2 * 128 * 128 * 2
    assert 1.63e9 < state < 1.65e9 and 0.53e9 < pool < 0.54e9


def test_counts_against_a_count_by_hand():
    z = dict(d=8, h=2, kv=1, hd=4, ff=16, eff=4, sff=6, E=4, held=2, k=2,
             H=2, P=2, G=1, N=4, inner=4, wide=12, taps=4, vocab=32,
             layers=(("mamba2", None), (None, "routed"),
                     ("attention", None), (None, "routed")))
    mamba = 2 * (8 * (4 + 12 + 2) + 4 * 12 + 2 * 16 + 4 + 4 * 8)
    proj = 2 * (8 * (2 + 2) * 4 + 8 * 8)
    fixed = 2 * 8 * 4 + 2 * 2 * 8 * 6  # router over all 4, shared expert
    assert counts_nemotron.mamba_mixer_flops(z) == mamba == 520
    assert counts_nemotron.routed_fixed_flops(z) == fixed == 256
    assert counts_nemotron.expert_flops(z) == 2 * 2 * 8 * 4
    attend = 2 * 2 * 8 * 6
    assert counts_nemotron.token_flops(z, position=5) == (
        mamba + proj + attend + 2 * fixed)
    assert counts_nemotron.span_flops(z, start=2, stop=5) == (
        3 * (mamba + proj + 2 * fixed) + 2 * 2 * 8 * 12)
    assert counts_nemotron.head_flops(z, rows=3) == 3 * 2 * 8 * 32
    # a launch of 5 rows: 12 of its 20 assignments fell to held experts,
    # over 1.5 distinct held experts a layer
    flops, nbytes, layers = counts_nemotron.routed_layer(
        z, rows=5, experts_touched=1.5, held_assignments=12)
    assert layers == 2 and flops == 5 * 2 * 8 * 4 + 6 * 2 * 2 * 8 * 4
    assert nbytes == 1.5 * 2 * 8 * 4 * 2 + 8 * 4 * 4 + 2 * 5 * 8 * 2
    flops, nbytes, layers = counts_nemotron.ssm_layer(z, rows=3)
    assert layers == 1 and flops == 3 * mamba
    weights = (8 * 18 + 4 * 8 + 5 * 12 + 4) * 2 + 3 * 2 * 4
    assert nbytes == 3 * 2 * (2 * 2 * 4 * 4 + 3 * 12 * 2) + weights + (
        2 * 3 * 8 * 2)


# ---- the driver at a size the CPU holds ------------------------------------


def test_a_whole_run_is_correct_and_the_control_is_not(tiny, capsys):
    seed = 2 ** 31 + 3333
    rc = command.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       str(WINDOW_S), "--trace", "0"],
                      devices=jax.devices()[:1])
    assert rc == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True, out.err[-2000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s"}
    assert set(line["compared"]) == {"served_logit_gap_mean"}

    cell = harness.load_cell(CELL)
    row = control_nemotron.readings(cell, seed, jax.devices()[:1], WINDOW_S)
    verdicts = control.verdicts(row, LIMITS)
    assert verdicts == {"program": True, "control": False}, row
    assert row["control"]["served_logit_gap_mean"] > row["program"][
        "served_logit_gap_mean"]
    assert 0 <= row["choices_moved"] <= row["choices_checked"]
    assert row["choices_checked"] == 2 * row["checked_tokens"]


def test_the_windows_work_counts_held_assignments_and_valid_tokens(tiny):
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
    # half of the 8 experts are held: of 3 assignments a token in each of
    # 2 routed layers some, not all, fell to them
    assert 0 < facts["held_assignments"] < 2 * 3 * (
        facts["tokens"] + 30 * 24)
    floor = facts["tokens"] * (counts_nemotron.head_flops(z)
                               + counts_nemotron.token_flops(z, position=0))
    assert facts["model_flops"] >= floor
    assert facts["state_bytes"] == 2 * 4 * (4 * 8 * 16 * 4 + 3 * 96 * 2)
    assert facts["pool_bytes"] == 2 * 33 * 2 * 16 * 8 * 2
    driver.release()


# ---- the reader, on a made-up trace -----------------------------------------


def test_scope_roofline_is_least_time_over_the_scopes_time(monkeypatch):
    """Two decode launches and a prefill launch (``test_lfm2``'s made-up
    window): the routed layers by their census, the state-space layers by
    their rows alone, each over the time under its own scope."""
    path = "jit(decode_step)/Transformer/block_1/"
    scoped = {"0": [
        ["%a", 11_000.0, 4_000.0, path + "dtg.routed/mlp/dtg.routed.route/x"],
        ["%s", 16_000.0, 6_000.0, path + "dtg.ssm/ssm/dtg.ssm.scan/y"],
        ["%p", 41_000.0, 9_000.0, path + "dtg.ssm/ssm/z"],  # prefill
        ["%c", 61_000.0, 8_000.0, path + "dtg.routed/mlp/w"],
        ["%e", 70_000.0, 1_000.0, path + "dtg.shared_expert/shared/dot"],
        ["ragged-dot-none.7 pallas:7->bf16", 72_000.0, 4_000.0,
         "ragged-dot-none"],
        ["%t", 77_000.0, 2_000.0, path + "dtg.ssm/ssm/dtg.ssm.conv/v"],
    ]}
    spans = []
    for tick, (start, program, kind, rows, stats) in enumerate([
            (9_000.0, "decode_step", "decode", 5,
             {"experts_touched": 1.5, "held_assignments": 12}),
            (39_000.0, "prefill_chunk_step", "prefill", 1,
             {"experts_touched": 2.0, "held_assignments": 9}),
            (59_000.0, "decode_step", "decode", 4,
             {"experts_touched": 1.0, "held_assignments": 6})]):
        spans += [
            ["engine.tick", start, 25_000.0, {"tick": tick}, 1],
            ["engine.build", start + 100, 100.0,
             {"tick": tick, "kind": kind, "rows": rows}, 1],
            ["engine.dispatch", start + 300, 500.0,
             {"tick": tick, "program": program}, 1],
            ["engine.apply", start + 24_000, 500.0, {"tick": tick, **stats},
             1]]
    monkeypatch.setattr(scoped_ops, "load", lambda trace: scoped)
    facts = made_up_facts()
    facts["trace"]["program"] = spans
    z = facts["sizes"] = dict(
        d=8, h=2, kv=1, hd=4, ff=16, eff=4, sff=6, E=4, held=2, k=2, H=2,
        P=2, G=1, N=4, inner=4, wide=12, taps=4, vocab=32,
        layers=(("mamba2", None), (None, "routed"), (None, "routed")))
    peaks = facts["peaks"]

    def least(work, launches):
        total = 0.0
        for kw in launches:
            flops, nbytes, layers = work(z, **kw)
            total += layers * max(flops / peaks["bf16_flops_per_s"],
                                  nbytes / peaks["hbm_bytes_per_s"])
        return total

    routed = dict(scope="dtg.routed", counts="counts_nemotron",
                  work="routed_layer",
                  stats=["experts_touched", "held_assignments"])
    want = least(counts_nemotron.routed_layer, [
        dict(rows=5, experts_touched=1.5, held_assignments=12),
        dict(rows=4, experts_touched=1.0, held_assignments=6)])
    assert scope_roofline.read(facts, **routed) == pytest.approx(
        100.0 * want / 12e-6)
    assert scope_roofline.read(
        facts, also_named=["ragged-dot"], **routed) == pytest.approx(
            100.0 * want / 16e-6)
    ssm = dict(scope="dtg.ssm", counts="counts_nemotron",
               work="ssm_layer")
    want = least(counts_nemotron.ssm_layer, [dict(rows=5), dict(rows=4)])
    assert scope_roofline.read(facts, **ssm) == pytest.approx(
        100.0 * want / 8e-6)  # the prefill launch's 9 us are not decode's
    # nothing under the scope (a program without it) leaves the metric out
    assert scope_roofline.read(facts, **{**ssm, "scope": "dtg.none"}) is None
    # a program whose spans carry no census leaves the routed one out
    for row in spans:
        row[3].pop("held_assignments", None)
    assert scope_roofline.read(facts, **routed) is None
    assert scope_roofline.read(facts, **ssm) is not None


def test_every_metric_of_the_cell_has_its_file_and_names_the_cell():
    """The cell's metrics as this test knows them are there, wherever they
    stand in ``per_layer``; one a later PR appends passes too, so no count
    and no set is pinned (``test_manifest.py`` states the rule)."""
    manifest = harness.load_json(harness.MANIFEST)
    mine = [m for m in manifest["per_layer"] if m["name"].endswith(
        ".nemotron")]
    assert {m["name"].rpartition(".")[0] for m in mine} >= {
        "decode_step_ms", "mfu", "device_idle", "hbm_peak_gb",
        "ssm_share", "routed_share", "shared_expert_share",
        "routed_roofline", "ssm_roofline", "paged_decode_roofline",
        "experts_touched", "expert_load_ratio", "overlapped_launches",
        "chunks_per_prefill", "prefill_ms_per_chunk"}
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        spec = harness.load_json(
            harness.HERE / "layer_metrics" / f"{m['name']}.json")
        assert spec["name"] == m["name"]
        assert "cell" not in spec["args"]  # its cell is its ``workloads``
    cell = _load_cell(CELL, manifest)
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                    "serve_tokens_per_s"}
    assert {m["name"] for m in mine} <= {m["name"] for m in cell.per_layer}
    # the grouped-heads kernel file reads this configuration's sizes as is
    from yardstick.kernels import paged_decode_gqa as kernel

    z = weights_nemotron.sizes_of(cell.config)
    facts = {"sizes": z, "decode_launches": [(3, 100), (5, 300)],
             "peaks": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e8}}
    events = [["k", 0.0, 1.0]] * 2  # two attention layers: one launch
    keys = 2 * 300 * 2 * 128 * 2 + 2 * 5 * 32 * 128 * 2  # 2 pool heads
    assert kernel.least_seconds(facts, events) == pytest.approx(
        2 * max(2 * 2 * 32 * 128 * 300 / 1e9, keys / 1e8))
