"""The Phi-4-mini-flash cell's own pieces: the configuration file against
the catalog's row key by key, ``counts_phi4flash`` against a count by hand,
the driver end to end at a size the CPU holds (the program correct; the
int8 control and each of the three planted faults not), and every metric
of the cell against its file."""

from __future__ import annotations

import copy
import json
import os
import re

import jax
import numpy as np
import pytest

from yardstick import compare, control, control_phi4flash, counts
from yardstick import counts_phi4flash, harness, weights_phi4flash
from yardstick import run as command
from yardstick.reference import phi4flash

CELL = "phi-4-mini-flash-reasoning.serve.reasoning-backlog"
NAME = "phi-4-mini-flash-reasoning"
SOURCE = ("https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob"
          "/main/config.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
_load_cell = harness.load_cell

#: the source's config.json as the catalog's row holds it, by hand (the
#: row itself, where this machine has it, is compared key by key below)
PUBLISHED = dict(
    embd_pdrop=0, hidden_act="silu", hidden_size=2560,
    intermediate_size=10240, layer_norm_eps=1e-05,
    max_position_embeddings=262144, mb_per_layer=2, model_type="phi4flash",
    num_attention_heads=40, num_hidden_layers=32, num_key_value_heads=20,
    resid_pdrop=0, sliding_window=512, tie_word_embeddings=True,
    mlp_bias=False, lm_head_bias=False, vocab_size=200064)

#: a tiny cell's, not the chip's: at width 64 with weights drawn at 0.1,
#: over all 24 requests' served tokens, a bfloat16 run reads 0.001-0.004
#: over six seeds, the int8 control 0.011-0.024 and the faults 0.09 and more
#: (no_memory 0.011-0.03); the seed the test uses is in the test
LIMITS = {"served_logit_gap_mean": 0.006}
#: longer than the tiny backlog takes: the window closes when the last of
#: the 24 requests is served
WINDOW_S = 5.0


def tiny_cell(name: str = CELL, *args, **kwargs) -> harness.Cell:
    c = copy.deepcopy(_load_cell(name))
    c.config.update(
        hidden_size=64, num_attention_heads=8, num_key_value_heads=4,
        intermediate_size=96, sliding_window=8, vocab_size=512,
        num_hidden_layers=8)
    c.config["assumed"]["layout"].update(memory_layer=4, full_layer=5)
    c.config["assumed"]["mamba"].update(dt_rank=4)
    c.config["assumed"]["drawn"].update(
        initializer_range=0.1, bias_std=0.1, lambda_std=0.3, dt_std=0.5)
    c.config["deployment"].update(
        slots=4, block_size=4, num_blocks=65, prefill_chunk=4,
        max_positions=64)
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
    assert entry["source"] == held["source"] == SOURCE
    published = dict(PUBLISHED)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        (row,) = [r for r in rows if r["name"] == "Phi-4-mini-flash-reasoning"]
        assert row["source_url"] == SOURCE
        assert row["config"] == published
        published = row["config"]
    for key, value in published.items():
        assert held[key] == value and type(held[key]) is type(value), key
    # nothing is cut: every layer, the whole vocabulary, every width
    assert held["reduced"] == entry["reduced"] == []
    assert "departures_forced_by_the_program" not in held  # the head is tied
    assumed = held["assumed"]
    assert {"layout", "mamba", "attention", "window", "gmu", "positions",
            "norm", "state_dtype", "drawn", "precision",
            "sampling"} <= set(assumed)
    assert (assumed["layout"]["memory_layer"],
            assumed["layout"]["full_layer"]) == (16, 17)
    assert {k: assumed["mamba"][k] for k in (
        "d_state", "d_conv", "expand", "dt_rank")} == {
            "d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 160}
    assert assumed["mamba"]["dt_rank"] == -(-held["hidden_size"] // 16)
    for said in ("layout", "mamba", "drawn"):
        assert assumed[said]["why"]
    assert "0.8 - 0.6 exp(-0.3 i)" in assumed["attention"]
    assert "including its own" in assumed["window"]
    assert "float32" in assumed["state_dtype"]
    dep = held["deployment"]
    assert (dep["chips"], dep["chips_in_the_deployment"]) == (1, 1)
    assert (dep["compute_dtype"], dep["weights_dtype"],
            dep["state_dtype"]) == ("bfloat16", "bfloat16", "float32")
    assert (dep["slots"], dep["block_size"], dep["num_blocks"],
            dep["prefill_chunk"], dep["max_positions"],
            dep["temperature"]) == (64, 128, 4096, 128, 8192, 0.0)
    # the pool holds the worst case: every slot at the longest request
    mix = _load_cell(CELL).traffic
    assert mix["prompt"]["max"] + mix["output"]["max"] <= dep["max_positions"]
    assert (dep["slots"] * dep["max_positions"] // dep["block_size"]
            == dep["num_blocks"])
    assert mix["vocab_below"] == held["vocab_size"]


def test_the_traffic_is_the_issues_but_for_the_round():
    manifest = harness.load_json(harness.MANIFEST)
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "reasoning-backlog", 1)
    mix = _load_cell(CELL).traffic
    assert (mix["generator"], mix["requests"], mix["sizes"],
            mix["vocab_below"], mix["checked_requests"]) == (
                "request_mix", 512, 16, 200064, 12)
    # ISSUE 35's lengths letter for letter; one thing moved after the first
    # six seeds on the chip spread 3.1% (the file's ``assumed`` says why): a
    # round of 16 for 64
    assert mix["prompt"] == {"mean": 1024, "sigma": 1.0, "min": 32,
                             "max": 4096}
    assert mix["output"] == {"mean": 1024, "sigma": 0.8, "min": 32,
                             "max": 4096}
    # the slots hold whole rounds: every seed opens its window on the same
    # multiset of prompts
    assert _load_cell(CELL).config["deployment"]["slots"] % mix["sizes"] == 0
    assert {"shape", "scale", "clip", "round"} <= set(mix["assumed"])
    offered = _load_cell(CELL).generator.requests(mix, 2 ** 31 + 7)
    assert len(offered) == 512 and all(r.due == 0.0 for r in offered)
    first = sorted(len(r.prompt) for r in offered[:16])
    assert first == sorted(len(r.prompt) for r in offered[16:32])
    # 16 quantiles of the ISSUE's log-normals: the clips cut nothing, and
    # the longest request stays inside the deployment's positions
    assert (first[0], first[-1]) == (96, 4001)
    outputs = sorted(r.max_new_tokens for r in offered[:16])
    assert (outputs[0], outputs[-1]) == (168, 3300)
    assert first[-1] + outputs[-1] <= _load_cell(CELL).config["deployment"][
        "max_positions"]


def test_the_written_out_product_is_float32s():
    """``reference.phi4flash._product``: three bfloat16 parts are a float32
    (24 bits), so six passes read within float32's rounding of a float64
    product, where one pass (the chip's default) reads a hundred times
    further off; and with a bfloat16 weight three passes are exact pair by
    pair."""
    rng = np.random.default_rng(3)
    a = rng.standard_normal((37, 96)).astype(np.float32)
    b = rng.standard_normal((96, 41)).astype(np.float32)
    parts = phi4flash._parts(jax.numpy.asarray(a))
    assert [x.dtype for x in parts] == [jax.numpy.bfloat16] * 3
    np.testing.assert_array_equal(
        sum(np.asarray(x, np.float64) for x in parts), a.astype(np.float64))
    exact = a.astype(np.float64) @ b.astype(np.float64)
    six = np.asarray(phi4flash._product("mk,kn->mn", a, b), np.float64)
    one = np.asarray(jax.numpy.matmul(
        a.astype(jax.numpy.bfloat16), b.astype(jax.numpy.bfloat16),
        preferred_element_type=np.float32), np.float64)
    scale = np.abs(a).astype(np.float64) @ np.abs(b).astype(np.float64)
    assert np.max(np.abs(six - exact) / scale) < 2e-6
    assert np.max(np.abs(one - exact) / scale) > 2e-4
    weight = np.asarray(jax.numpy.asarray(b).astype(jax.numpy.bfloat16),
                        np.float32)
    three = np.asarray(phi4flash._dot(a, weight, "float32"), np.float64)
    exact = a.astype(np.float64) @ weight.astype(np.float64)
    assert np.max(np.abs(three - exact) / scale) < 2e-7
    with pytest.raises(ValueError, match="float32 or int8"):
        phi4flash._dot(a, weight, "bfloat16")


def test_every_multiplied_leaf_is_bfloat16s_and_the_tree_is_the_same_draws():
    """What ``_dot`` counts on, and what keeps the draws out of the
    comparison's compile time: a leaf that is multiplied is rounded to
    bfloat16; ``flax_tree`` holds ``drawn``'s leaves, cast, so the program
    and the reference are given the same numbers by the same compiled
    draws."""
    from tests.test_phi4flash import Z as z

    tree = weights_phi4flash.flax_tree(5, z)
    top = weights_phi4flash.drawn(z)(5)
    np.testing.assert_array_equal(
        np.asarray(tree["tok_emb"]["embedding"], np.float32), top["wte"])
    multiplied = {"wte", "in_w", "x_w", "dt_w", "out_w", "qkv_w", "q_w",
                  "proj_w", "gate_w", "up_w", "down_w"}
    for i, kinds in enumerate(z["layers"]):
        for half in kinds:
            leaves = weights_phi4flash.drawn(z, half)(5, i)
            assert set(leaves) == set(weights_phi4flash.half_spec(half))
            for name, value in leaves.items():
                assert value.dtype == np.float32
                rounded = np.asarray(
                    value.astype(jax.numpy.bfloat16), np.float32)
                if name in multiplied:
                    np.testing.assert_array_equal(rounded, value)
                node = tree[f"block_{i}"]
                for part in weights_phi4flash.flax_path(name, kinds):
                    node = node[part]
                np.testing.assert_array_equal(
                    np.asarray(node, np.float32),
                    value if node.dtype == np.float32 else rounded)
    # one compiled draw a (sizes, half), found again: top, 5 mixers, dense
    mine = {k: v for k, v in weights_phi4flash._DRAWN.items()
            if k[0] == tuple(sorted(z.items()))}
    assert len(mine) == 7
    weights_phi4flash.drawn(z, "dense")(6, 0)
    assert all(weights_phi4flash._DRAWN[k] is v for k, v in mine.items())


def test_the_sizes_and_the_bytes_of_the_cell():
    config = _load_cell(CELL).config
    z = weights_phi4flash.sizes_of(config)
    assert (z["d"], z["h"], z["kv"], z["hd"], z["ff"]) == (
        2560, 40, 20, 64, 10240)
    assert (z["inner"], z["N"], z["R"], z["taps"], z["window"]) == (
        5120, 16, 160, 4, 512)
    kinds = [m for m, _ in z["layers"]]
    assert kinds[:17:2] == ["mamba1"] * 9
    assert kinds[1:16:2] == ["window_attention"] * 8
    assert kinds[17] == "attention"
    assert kinds[18::2] == ["gmu"] * 7
    assert kinds[19::2] == ["cross_attention"] * 7
    assert all(ffn == "dense" for _, ffn in z["layers"])

    def params(kind, skip=()):
        return sum(int(np.prod(shape(z))) for name, (shape, _) in
                   weights_phi4flash.layer_spec(kind).items()
                   if name not in skip)

    ffn = 3 * 2560 * 10240
    norms = 4 * 2560
    # ISSUE 35's arithmetic: the mixers alone, then a layer with its
    # feed-forward and norms
    assert params(("mamba1", "dense")) - ffn - norms == 41_241_600
    assert params(("attention", "dense")) - ffn - norms == (
        2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 4 * 64 + 128)
    assert params(("cross_attention", "dense")) - ffn - norms == (
        2 * (2560 * 2560 + 2560) + 4 * 64 + 128)
    assert params(("gmu", "dense")) - ffn - norms == 2 * 2560 * 5120
    total = sum(params(k) for k in z["layers"]) + sum(
        int(np.prod(shape(z))) for shape, _ in
        weights_phi4flash._TOP.values())
    assert round(total / 1e6, 1) == 3852.6  # 7.71 GB in bfloat16, head tied
    # a position: the full layer's 5,120 B once, the window layers' 40,960
    assert counts_phi4flash.key_bytes(z) == 5120
    assert counts_phi4flash.layers_of(z, "window_attention") * 5120 == 40960
    dep = config["deployment"]
    pool = dep["num_blocks"] * dep["block_size"] * 5120
    rings = 8 * (dep["slots"] * 640 + 128) * 5120
    state = 9 * dep["slots"] * (5120 * 16 * 4 + 3 * 5120 * 2)
    assert 2.68e9 < pool < 2.69e9 and 1.68e9 < rings < 1.69e9
    assert 0.20e9 < state < 0.21e9


def test_counts_against_a_count_by_hand():
    z = dict(d=8, h=4, kv=2, hd=2, ff=16, inner=16, N=4, R=2, taps=4,
             window=3, vocab=32, L=6,
             layers=(("mamba1", "dense"), ("window_attention", "dense"),
                     ("mamba1", "dense"), ("attention", "dense"),
                     ("gmu", "dense"), ("cross_attention", "dense")))
    mamba = 2 * (8 * 32 + 4 * 16 + 16 * (2 + 8) + 2 * 16 + 2 * 16 * 4 + 16
                 + 16 * 8)
    assert counts_phi4flash.mamba_mixer_flops(z) == mamba == 1568
    own = 2 * (8 * 8 * 2 + 8 * 8)  # q, k, v out of one kernel, and out
    cross = 2 * (8 * 4 * 2 + 8 * 8)
    assert counts_phi4flash.projection_flops(z, "attention") == own
    assert counts_phi4flash.projection_flops(z, "cross_attention") == cross
    gmu = 2 * (2 * 8 * 16 + 16)
    ffn = 2 * 3 * 8 * 16
    assert counts_phi4flash.gmu_flops(z) == gmu
    assert counts_phi4flash.ffn_flops(z) == ffn
    fixed = 6 * ffn + 2 * mamba + 2 * own + cross + gmu
    # position 5 sees 6 keys in the full layer and its reader, 3 in the
    # window layer; a key costs 2 x 2 x (4 heads x 2) operations a query
    assert counts_phi4flash.token_flops(z, position=5) == (
        fixed + 32 * (6 + 6 + 3))
    assert counts_phi4flash.token_flops(z, position=1) == (
        fixed + 32 * (2 + 2 + 2))
    # positions 1..4: 2 + 3 + 4 + 5 keys, 2 + 3 + 3 + 3 in the window
    assert counts_phi4flash.window_keys(z, start=1, stop=5) == 11
    assert counts_phi4flash.span_flops(z, start=1, stop=5) == (
        4 * fixed + 32 * (14 + 14 + 11))
    assert counts_phi4flash.head_flops(z, rows=3) == 3 * 2 * 8 * 32
    # a launch of 3 rows
    flops, nbytes, layers = counts_phi4flash.ssm_layer(z, rows=3)
    assert layers == 2 and flops == 3 * mamba
    weights = (8 * 32 + 16 * 10 + 2 * 16 + 16 * 8 + 5 * 16) * 2 + (
        16 * 4 + 2 * 16) * 4
    assert nbytes == 3 * 2 * (16 * 4 * 4 + 3 * 16 * 2) + weights + (
        2 * 3 * 8 * 2)
    key = 2 * 2 * 2 * 2  # a key and a value of 2 heads of 2, bfloat16
    assert counts_phi4flash.key_bytes(z) == key
    flops, nbytes, layers = counts_phi4flash.window_layer(
        z, rows=3, window_keys=8)
    assert layers == 1 and flops == 3 * own + 32 * 8
    assert nbytes == own + 8 * key + 3 * key + 2 * 3 * 8 * 2
    # the shared cache: read by the full layer AND by the cross layer, each
    # once; the mean of the two layers
    flops, nbytes, layers = counts_phi4flash.shared_kv_layers(
        z, rows=3, live_keys=20)
    assert layers == 2
    assert flops == (3 * own + 3 * cross + 2 * 32 * 20) / 2
    assert nbytes == ((own + 20 * key + 3 * key + 96)
                      + (cross + 20 * key + 96)) / 2
    peaks = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}
    assert layers * counts.least_seconds(flops, nbytes, peaks) == (
        pytest.approx((own + cross + 40 * key + 3 * key + 192) / 1e6))


# ---- the driver at a size the CPU holds ------------------------------------


def test_a_whole_run_is_correct_and_the_control_and_the_faults_are_not(
        tiny, capsys):
    seed = 2 ** 31 + 3535
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
    row = control_phi4flash.readings(cell, seed, jax.devices()[:1], WINDOW_S)
    assert set(row["faults"]) == set(phi4flash.FAULTS) == {
        "no_lambda", "no_window", "no_memory"}
    verdicts = control.verdicts(row, LIMITS)
    assert verdicts == {"program": True, "control": False,
                        "no_lambda": False, "no_window": False,
                        "no_memory": False}, row
    assert row["control"]["served_logit_gap_mean"] > row["program"][
        "served_logit_gap_mean"]


def test_the_windows_work_counts_valid_tokens_and_the_three_stores(tiny):
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
    floor = facts["tokens"] * (counts_phi4flash.head_flops(z)
                               + counts_phi4flash.token_flops(z, position=0))
    assert facts["model_flops"] >= floor
    key = 2 * 4 * 8 * 2  # a key and a value of 4 heads of 8, bfloat16
    assert facts["pool_bytes"] == 65 * 4 * key
    assert facts["window_bytes"] == 2 * (4 * 12 + 4) * key
    assert facts["state_bytes"] == 3 * 4 * (128 * 16 * 4 + 3 * 128 * 2)
    driver.release()


# ---- the cell's metrics ----------------------------------------------------


def test_every_metric_of_the_cell_has_its_file_and_names_the_cell_alone():
    """The cell's metrics as this test knows them are there, wherever they
    stand in ``per_layer``; one a later PR appends passes too, so no count
    and no set is pinned (``test_manifest.py`` states the rule)."""
    manifest = harness.load_json(harness.MANIFEST)
    mine = [m for m in manifest["per_layer"] if m["name"].endswith(
        ".phi4flash")]
    assert {m["name"].rpartition(".")[0] for m in mine} >= {
        "decode_step_ms", "mfu", "hbm_peak_gb",
        "overlapped_launches", "ssm_share", "window_attn_share",
        "full_attn_share", "cross_attn_share", "gmu_share", "ssm_roofline",
        "window_attn_roofline", "shared_kv_roofline",
        # the host's side of a tick, by the program's own spans alone: the
        # profiler keeps this cell's device events for the first ~22 s of
        # the window and none after (PERF.md section 7), so no metric here
        # subtracts device time, and ``device_idle`` is not read
        "schedule_ms_per_tick", "build_ms_per_tick", "apply_ms_per_tick",
        "tick_max_ms", "chunks_per_prefill", "prefill_ms_per_chunk"}
    scopes = {}
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        spec = harness.load_json(
            harness.HERE / "layer_metrics" / f"{m['name']}.json")
        assert spec["name"] == m["name"]
        assert "cell" not in spec["args"]  # its cell is its ``workloads``
        assert spec["reader"] != "device_idle"
        assert not spec["args"].get("less_device")
        if spec["reader"] in ("scope_share", "scope_roofline"):
            assert spec["args"]["program"] == "decode_step"
            scopes[m["name"].rpartition(".")[0]] = spec["args"]["scope"]
        if spec["reader"] == "scope_roofline":
            assert spec["args"]["counts"] == "counts_phi4flash"
            work = getattr(counts_phi4flash, spec["args"]["work"])
            assert set(spec["args"].get("stats", ())) <= {"live_keys",
                                                          "window_keys"}
            assert callable(work)
    assert scopes == {
        "ssm_share": "dtg.ssm", "window_attn_share": "dtg.window_attn",
        "full_attn_share": "dtg.attn", "cross_attn_share": "dtg.cross_attn",
        "gmu_share": "dtg.gmu", "ssm_roofline": "dtg.ssm",
        "window_attn_roofline": "dtg.window_attn",
        "shared_kv_roofline": "dtg.shared_kv"}
    # no other cell's metrics name this cell, and it reports no other's
    for m in manifest["per_layer"]:
        if not m["name"].endswith(".phi4flash"):
            assert CELL not in m.get("workloads", [CELL]), m["name"]
    cell = _load_cell(CELL, manifest)
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                    "serve_tokens_per_s"}
    assert {m["name"] for m in mine} <= {m["name"] for m in cell.per_layer}


def test_the_program_names_the_scopes_the_metrics_read():
    """The decode program of the tiny model, lowered: every scope a metric
    file names is a component of some operation's path, the full layer's
    and the cross layers' under ``dtg.shared_kv``, the window layers' not."""
    import jax.numpy as jnp

    from distributed_tensorflow_guide_tpu.serve import engine as E

    cell = tiny_cell()
    driver = cell.driver.Driver(cell, 1, jax.devices()[:1], None)
    z, dep = driver.sizes, cell.config["deployment"]
    from tests.test_phi4flash import config

    fns = E.build_step_fns(
        config(jnp.bfloat16, z=z), slots=4, num_blocks=dep["num_blocks"],
        block_size=4, prefill_chunk=4)
    params = jax.eval_shape(lambda: weights_phi4flash.flax_tree(1, z))
    pool = E.paged_cache_shapes(fns.cfg, 4)
    state = E._serving_shapes(fns.cfg, 4)["state"]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    text = fns.decode.lower(
        params, pool, state, i32(4, fns.n_blk), i32(4), i32(4),
        jax.ShapeDtypeStruct((4, 2), jnp.uint32)).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]+)"', text))
    for scopes in ("dtg.ssm/ssm/dtg.ssm.conv", "dtg.ssm/ssm/dtg.ssm.scan",
                   "dtg.window_attn/attn", "dtg.shared_kv/dtg.attn/attn",
                   "dtg.gmu/gmu", "dtg.shared_kv/dtg.cross_attn/attn"):
        assert any(scopes in p for p in paths), scopes
    by_block = {b: {p for p in paths if f"/block_{b}._hybrid_mixer/" in p}
                for b in range(8)}
    assert all("dtg.shared_kv" in p.split("/") for b in (5, 7)
               for p in by_block[b]) and by_block[5] and by_block[7]
    assert not any("dtg.shared_kv" in p for b in (1, 3) for p in by_block[b])
    assert by_block[1] and all("dtg.window_attn" in p.split("/")
                               for p in by_block[1])
