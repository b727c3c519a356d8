"""The openPangu-Ultra-MoE cell's own pieces: the configuration file against
the catalog's row key by key, ``counts_pangu`` against a count by hand, the
driver end to end at a size the CPU holds (the program correct; the int8
control and the three planted faults not), the scopes the new metrics read
in the program's operations, and ``latent_attn_roofline`` on a made-up
trace."""

from __future__ import annotations

import copy
import json
import re

import jax
import pytest

from tests.yardstick.test_lfm2 import made_up_facts
from yardstick import compare, control, control_pangu, counts_pangu
from yardstick import harness, scoped_ops, weights_pangu
from yardstick import run as command
from yardstick.readers import scope_roofline, scope_share
from yardstick.reference.pangu_moe import FAULTS

CELL = "openpangu-ultra-moe-718b.serve.reasoning-backlog"
NAME = "openpangu-ultra-moe-718b"
_load_cell = harness.load_cell

#: huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B/blob/main/
#: config.json, by hand
PUBLISHED = dict(
    attention_bias=False, first_k_dense_replace=3, hidden_act="silu",
    hidden_size=7680, intermediate_size=18432, kv_lora_rank=512,
    max_position_embeddings=131072, model_type="pangu_ultra_moe",
    moe_intermediate_size=2048, n_routed_experts=256, n_shared_experts=1,
    norm_topk_prob=True, num_attention_heads=128, num_experts_per_tok=8,
    num_hidden_layers=61, num_key_value_heads=128,
    num_nextn_predict_layers=1, q_lora_rank=1536, qk_nope_head_dim=128,
    qk_rope_head_dim=64, rms_norm_eps=1e-05, rope_theta=25600000,
    routed_scaling_factor=2.5, sandwich_norm=True, tie_word_embeddings=False,
    v_head_dim=128, vocab_size=153600)
HELD = dict(first_k_dense_replace=1, n_routed_experts=8, vocab_size=19200,
            num_nextn_predict_layers=0)

#: a tiny cell's, not the chip's: at width 64 with weights drawn at 0.1,
#: over all 24 requests' ~460 served tokens, a bfloat16 run reads
#: 0.0008-0.0044 over three seeds and the int8 control 0.013-0.027; the
#: faults no_rope 0.17-0.20, no_kv_norm 0.08-0.10, no_post_norm 0.59-0.68
LIMITS = {"served_logit_gap_mean": 0.008}
#: longer than the tiny backlog takes: the window closes when the last of
#: the 24 requests is served
WINDOW_S = 5.0


def tiny_cell(name: str = CELL, *args, **kwargs) -> harness.Cell:
    c = copy.deepcopy(_load_cell(name))
    c.config.update(
        hidden_size=64, num_attention_heads=4, q_lora_rank=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
        num_experts_per_tok=4, vocab_size=512)
    c.config["published"]["n_routed_experts"]["published"] = 16
    c.config["assumed"]["drawn"].update(initializer_range=0.1,
                                        router_std=0.5, post_norm_gain=0.5)
    c.config["deployment"].update(
        slots=4, block_size=8, num_blocks=33, prefill_chunk=8,
        max_positions=64, experts_held=[0, 8], layers_held=[0, 3, 4])
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


def _held():
    manifest = harness.load_json(harness.MANIFEST)
    entry = {c["name"]: c for c in manifest["configs"]}[NAME]
    return entry, harness.load_json(harness.ROOT / entry["file"])


def test_the_configuration_holds_the_published_values_key_by_key():
    entry, held = _held()
    assert entry["source"] == held["source"] == (
        "https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B"
        "/blob/main/config.json")
    for key, value in PUBLISHED.items():
        assert held[key] == HELD.get(key, value), key
    assert held["reduced"] == entry["reduced"] == list(HELD)
    # the depth: published layer 0 (dense) and 3-6 (routed); the layer
    # count itself is published as it is
    dep = held["deployment"]
    assert held["num_hidden_layers"] == 61
    assert dep["layers_held"] == [0, 3, 4, 5, 6]
    z = weights_pangu.sizes_of(held)
    assert z["layers"] == (("latent_attention", "dense"),) + (
        ("latent_attention", "routed"),) * 4
    assert (dep["chips"], dep["chips_that_share_a_layer"]) == (1, 32)
    assert dep["experts_held"] == [0, 8]
    assert (dep["compute_dtype"], dep["weights_dtype"]) == ("bfloat16",
                                                            "bfloat16")
    assert (dep["slots"], dep["block_size"], dep["num_blocks"],
            dep["prefill_chunk"], dep["max_positions"],
            dep["temperature"]) == (128, 128, 8192, 128, 8192, 0.0)
    assert dep["latent_row"] == 512 + 64
    assumed = held["assumed"]
    assert {"router", "rotary", "sandwich_norm", "drawn", "precision",
            "sampling"} <= set(assumed)
    assert assumed["drawn"]["post_norm_gain"] == pytest.approx(61 ** -0.5)
    # the pool holds the worst case: every slot at the longest request
    mix = _load_cell(CELL).traffic
    assert mix["prompt"]["max"] + mix["output"]["max"] <= dep["max_positions"]
    assert (dep["slots"] * dep["max_positions"] // dep["block_size"]
            == dep["num_blocks"])
    assert mix["vocab_below"] == held["vocab_size"] == 153600 // 8


@pytest.mark.parametrize("key", list(HELD))
def test_a_reduced_key_states_published_and_held(key):
    """Every key outside ``reduced`` equals the published value, and each
    reduced key is held at the share's value with the published one said
    beside it; no reduced key is a width (``test_manifest.py``'s
    pattern)."""
    entry, held = _held()
    assert key in held["reduced"] and key in entry["reduced"]
    stated = held["published"][key]
    assert stated["published"] == PUBLISHED[key] != held[key]
    assert stated["held"] == held[key] == HELD[key]
    width = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|head_size"
                       r"|n_embd|n_inner|d_model|d_ff|experts_per_tok")
    assert not width.search(key)


def test_the_sizes_and_the_bytes_of_the_cell():
    config = _load_cell(CELL).config
    z = weights_pangu.sizes_of(config)
    assert (z["d"], z["h"], z["q_rank"], z["kv_rank"], z["nope"], z["rope"],
            z["v"]) == (7680, 128, 1536, 512, 128, 64, 128)
    assert (z["ff"], z["eff"], z["sff"], z["E"], z["first"], z["held"],
            z["k"], z["scale"]) == (18432, 2048, 2048, 256, 0, 8, 8, 2.5)
    parts = weights_pangu.parameters(z)
    # the published widths' counts: MLA 196.6 M a layer, the dense feed-forward
    # 424.7 M, a routed layer's experts, shared expert and router 426.6 M
    assert 196.5e6 < parts["mla"] < 196.6e6
    assert parts["dense"] == 3 * 7680 * 18432
    assert parts["routed"] == (9 * 3 * 7680 * 2048 + 7680 * 256 + 256)
    assert parts["embedding"] == parts["head"] == 19200 * 7680
    assert 3.40e9 < parts["total"] < 3.42e9  # 6.82 GB in bfloat16
    # the latent pool: one row of 576 a position a layer, 1,152 B
    dep = config["deployment"]
    row = counts_pangu.latent_row(z) * 2
    assert row == 1152
    pool = len(z["layers"]) * dep["num_blocks"] * dep["block_size"] * row
    assert 6.03e9 < pool < 6.05e9
    # a live key a layer at decode: 1,152 B and 278,528 operations, the
    # kernel's ridge (v5e: 197e12 / 819e9 = 240 flop/B)
    assert counts_pangu.latent_key_flops(z) == 278528
    assert round(278528 / 1152) == 242


def test_counts_against_a_count_by_hand():
    z = dict(d=8, h=2, q_rank=4, kv_rank=6, nope=3, rope=2, v=5, ff=16,
             eff=4, sff=4, E=4, held=2, k=2, vocab=32,
             layers=(("latent_attention", "dense"),
                     ("latent_attention", "routed")))
    # q_a 8x4, q_b 4x(2x5), kv_a 8x(6+2), absorb 2x3x6, 2x6x5, o (2x5)x8
    mla = 2 * (32 + 40 + 64 + 36 + 60 + 80)
    assert counts_pangu.mla_token_flops(z) == mla == 624
    key = 2 * 2 * (8 + 6)
    assert counts_pangu.latent_key_flops(z) == key == 56
    dense = 2 * 3 * 8 * 16
    fixed = 2 * 8 * 4 + 2 * 3 * 8 * 4  # router over all 4, shared expert
    assert counts_pangu.routed_fixed_flops(z) == fixed
    assert counts_pangu.expert_flops(z) == 2 * 3 * 8 * 4
    assert counts_pangu.token_flops(z, position=5) == (
        2 * mla + 2 * 6 * key + dense + fixed)
    assert counts_pangu.span_flops(z, start=2, stop=5) == (
        3 * (2 * mla + dense + fixed) + 2 * (3 + 4 + 5) * key)
    assert counts_pangu.head_flops(z, rows=3) == 3 * 2 * 8 * 32
    flops, nbytes, layers = counts_pangu.latent_attn(z, rows=3,
                                                     live_keys=40)
    assert layers == 2 and flops == 40 * key
    assert nbytes == 40 * 8 * 2 + 3 * 2 * (8 + 6) * 2
    flops, nbytes, layers = counts_pangu.routed_layer(
        z, rows=5, experts_touched=1.5, held_assignments=6)
    assert layers == 1 and flops == 5 * 2 * 8 * 4 + 6 * 2 * 3 * 8 * 4
    assert nbytes == 1.5 * 3 * 8 * 4 * 2 + 8 * 4 * 4 + 2 * 5 * 8 * 2


# ---- the driver at a size the CPU holds ------------------------------------


def test_a_whole_run_is_correct_and_the_control_and_faults_are_not(
        tiny, capsys):
    seed = 2 ** 31 + 4141
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
    row = control_pangu.readings(cell, seed, jax.devices()[:1], WINDOW_S)
    verdicts = control.verdicts(row, LIMITS)
    assert verdicts == {"program": True, "control": False,
                        **{fault: False for fault in FAULTS}}, row
    for group in [row["control"], *row["faults"].values()]:
        assert group["served_logit_gap_mean"] > 2 * row["program"][
            "served_logit_gap_mean"]
    assert 0 <= row["choices_moved"] <= row["choices_checked"]
    assert row["choices_checked"] == 2 * row["checked_tokens"]


def test_a_program_alone_row_holds_no_control(tiny, monkeypatch):
    monkeypatch.setattr(control_pangu, "WITH_FAULTS", False)
    cell = harness.load_cell(CELL)
    row = control_pangu.readings(cell, 77, jax.devices()[:1], WINDOW_S)
    assert set(row) >= {"program", "checked_tokens"}
    assert "control" not in row and "faults" not in row
    assert control_pangu.program_verdict(row, LIMITS) == {"program": True}


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
    # half of the 16 experts are held: of 4 assignments a token in each of
    # 2 routed layers some, not all, fell to them
    assert 0 < facts["held_assignments"] < 2 * 4 * (
        facts["tokens"] + 30 * 24)
    floor = facts["tokens"] * (counts_pangu.head_flops(z)
                               + counts_pangu.token_flops(z, position=0))
    assert facts["model_flops"] >= floor
    # 3 layers x 33 blocks x a row of 32 + 8 x 8 positions, bfloat16
    assert facts["pool_bytes"] == 3 * 33 * 40 * 8 * 2
    driver.release()


def test_the_program_names_the_scopes_the_metrics_read():
    """The tiny model's decode program, lowered as the chip would run it
    (the Pallas kernel, forced here): the operations a trace names carry
    ``dtg.mla`` over the whole mixer and ``dtg.latent_attn`` inside it
    around the kernel alone, and the cache write is under the first and
    not the second."""
    import jax.numpy as jnp

    from distributed_tensorflow_guide_tpu.serve import engine as E
    from yardstick.drivers.serve_pangu import model_config

    cell = tiny_cell()
    z, dep = weights_pangu.sizes_of(cell.config), cell.config["deployment"]
    import dataclasses

    cfg = dataclasses.replace(model_config(z, jnp.bfloat16),
                              decode_impl="pallas")
    fns = E.build_step_fns(cfg, slots=4, num_blocks=dep["num_blocks"],
                           block_size=8, prefill_chunk=8)
    params = jax.eval_shape(lambda: weights_pangu.flax_tree(1, z))
    pool = E.paged_cache_shapes(fns.cfg, 4)
    state = E._serving_shapes(fns.cfg, 4).get("state", {})
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    text = fns.decode.lower(
        params, pool, state, i32(4, fns.n_blk), i32(4), i32(4),
        jax.ShapeDtypeStruct((4, 2), jnp.uint32)).as_text(debug_info=True)
    paths = set(re.findall(r'"([^"]*dtg\.[^"]*)"', text))
    for b in range(3):
        mine = [p for p in paths if f"block_{b}" in p]
        assert any("dtg.mla" in p.split("/") for p in mine), b
        kernel = [p for p in mine if "dtg.latent_attn" in p.split("/")]
        assert kernel and all("dtg.mla" in p.split("/") for p in kernel)
        assert not any("paged_write" in p for p in kernel)
    assert any("paged_write" in p and "dtg.mla" in p.split("/")
               for p in paths)


# ---- the readers, on a made-up trace ----------------------------------------


def test_the_new_readings_on_a_made_up_trace(monkeypatch):
    """Two decode launches and a prefill launch (``test_lfm2``'s made-up
    window): ``mla_share`` is the mixer's part of the decode launches'
    time, ``latent_attn_roofline`` the kernel's least time by the launch's
    live keys over the time under ``dtg.latent_attn``."""
    path = "jit(decode_step)/Transformer/block_1/dtg.mla/attn/"
    scoped = {"0": [
        ["%q", 11_000.0, 2_000.0, path + "q_a_proj/dot"],
        ["%k", 13_000.0, 5_000.0, path + "dtg.latent_attn/pallas"],
        ["%w", 18_000.0, 1_000.0, path + "paged_write/pallas"],
        ["%p", 41_000.0, 9_000.0, path + "dtg.latent_attn/pallas"],
        ["%r", 61_000.0, 6_000.0,
         "jit(decode_step)/Transformer/block_1/dtg.routed/mlp/x"],
        ["%k2", 70_000.0, 3_000.0, path + "dtg.latent_attn/pallas"],
    ]}
    spans = []
    for tick, (start, program, kind, rows, keys) in enumerate([
            (9_000.0, "decode_step", "decode", 5, 900.0),
            (39_000.0, "prefill_chunk_step", "prefill", 1, 0.0),
            (59_000.0, "decode_step", "decode", 4, 700.0)]):
        spans += [
            ["engine.tick", start, 25_000.0, {"tick": tick}, 1],
            ["engine.build", start + 100, 100.0,
             {"tick": tick, "kind": kind, "rows": rows}, 1],
            ["engine.dispatch", start + 300, 500.0,
             {"tick": tick, "program": program}, 1],
            ["engine.apply", start + 24_000, 500.0,
             {"tick": tick, "live_keys": keys}, 1]]
    monkeypatch.setattr(scoped_ops, "load", lambda trace: scoped)
    facts = made_up_facts()
    facts["trace"]["program"] = spans
    z = facts["sizes"] = dict(
        d=8, h=2, q_rank=4, kv_rank=6, nope=3, rope=2, v=5, ff=16, eff=4,
        sff=4, E=4, held=2, k=2, vocab=32,
        layers=(("latent_attention", "dense"),
                ("latent_attention", "routed")))
    peaks = facts["peaks"]
    assert scope_share.read(facts, program="decode_step",
                            scope="dtg.mla") == pytest.approx(
        100.0 * 11_000 / 17_000)
    want = 0.0
    for rows, keys in ((5, 900.0), (4, 700.0)):
        flops, nbytes, layers = counts_pangu.latent_attn(
            z, rows=rows, live_keys=keys)
        want += layers * max(flops / peaks["bf16_flops_per_s"],
                             nbytes / peaks["hbm_bytes_per_s"])
    got = scope_roofline.read(facts, scope="dtg.latent_attn",
                              counts="counts_pangu", work="latent_attn",
                              stats=["live_keys"])
    assert got == pytest.approx(100.0 * want / 8e-6)  # not prefill's 9 us
    # a program without the scope (the parent's) leaves both out
    assert scope_share.read(facts, program="decode_step",
                            scope="dtg.none") is None


def test_every_metric_of_the_cell_has_its_file_and_names_the_cell():
    """The cell's metrics as this test knows them are there, wherever they
    stand in ``per_layer``; one a later PR appends passes too, so no count
    and no set is pinned (``test_manifest.py`` states the rule)."""
    manifest = harness.load_json(harness.MANIFEST)
    mine = [m for m in manifest["per_layer"] if m["name"].endswith(".pangu")]
    assert {m["name"].rpartition(".")[0] for m in mine} >= {
        "mla_share", "latent_attn_roofline", "decode_step_ms", "mfu",
        "routed_share", "routed_roofline", "experts_touched",
        "expert_load_ratio", "prefill_ms_per_chunk", "chunks_per_prefill",
        "overlapped_launches", "device_idle", "hbm_peak_gb", "gc_ms_per_s",
        "compile_ms_per_s"}
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        spec = harness.load_json(
            harness.HERE / "layer_metrics" / f"{m['name']}.json")
        assert spec["name"] == m["name"]
        assert "cell" not in spec["args"]
    cell = _load_cell(CELL, manifest)
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                    "serve_tokens_per_s"}
    assert {m["name"] for m in mine} <= {m["name"] for m in cell.per_layer}
    (entry,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert entry["chips"] == 1 and entry["traffic"] == "reasoning-backlog-2048"


def test_the_traffic_is_reasoning_backlogs_lengths_at_2048_requests():
    """``reasoning-backlog-2048`` differs from ``reasoning-backlog`` in the
    number of requests and the vocabulary's slice alone; every seed offers
    the same round of lengths."""
    base = harness.load_json(harness.HERE / "traffic"
                             / "reasoning-backlog.json")
    mix = _load_cell(CELL).traffic
    for key in ("generator", "sizes", "prompt", "output",
                "checked_requests"):
        assert mix[key] == base[key], key
    assert (mix["requests"], mix["vocab_below"]) == (2048, 19200)
    gen = _load_cell(CELL).generator
    a, b = gen.requests(mix, 37444861), gen.requests(mix, 2 ** 31 + 5)
    assert len(a) == 2048 and all(r.due == 0.0 for r in a)
    assert (sorted(len(r.prompt) for r in a[:16])
            == sorted(len(r.prompt) for r in b[:16]))
    assert max(int(r.prompt.max()) for r in a) < 19200
