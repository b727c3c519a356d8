"""``BENCHMARK.json`` against the driver's rules for it, before a chip
second is spent: PR 22 built four cells and was refused for one layer's
name with a space in it."""

from __future__ import annotations

import json
import re

import pytest

from yardstick import harness, weights

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
LAYERS = {"train_loop", "serve_engine", "model_step", "kernels", "device"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|head_size"
                   r"|n_embd|n_inner|d_model|d_ff|experts_per_tok")


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(harness.MANIFEST)


def line_ok(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def metrics(manifest):
    return manifest["end_to_end"] + manifest["per_layer"]


def test_top_level(manifest):
    assert set(manifest) == TOP_KEYS
    assert harness.MANIFEST.stat().st_size <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["command"]) <= 32
    assert all(line_ok(w) for w in manifest["command"])
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (harness.ROOT / p).is_dir()
    # the command names no file of the repo outside paths
    for word in manifest["command"][1:]:
        if (harness.ROOT / word).exists():
            assert any(word.startswith(p + "/") for p in manifest["paths"])


def test_every_name_unit_and_layer_is_in_the_drivers_alphabet(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), group,
                          entry["name"]))
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in manifest["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
    for m in metrics(manifest):
        assert UNIT.match(m["unit"]), (m["name"], m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["per_layer"]:
        assert NAME.match(m["layer"]), (m["name"], m["layer"])
        assert m["layer"] in LAYERS
    metric_names = [n for is_metric, _, n in names if is_metric]
    assert len(set(metric_names)) == len(metric_names)
    for group in ("configs", "workloads"):
        own = [n for _, g, n in names if g == group]
        assert len(set(own)) == len(own)


def test_entries_have_just_the_contracts_keys(manifest):
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert line_ok(c["why"]) and line_ok(c["source"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert line_ok(w["why"]) and w["chips"] in (1, 4)
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}


def test_cells_configs_and_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = manifest["workloads"]
    assert 1 <= len(cells) <= 24 and 1 <= len(configs) <= 24
    assert {w["config"] for w in cells} == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    files = [c["file"] for c in configs.values()]
    assert len(set(files)) == len(files)
    for c in configs.values():
        assert PATH.match(c["file"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        held = harness.load_json(harness.ROOT / c["file"])
        assert held["reduced"] == c["reduced"]
        assert not any(WIDTH.search(k) for k in c["reduced"])
        assert c["source"].startswith("https://")
    for w in cells:
        cell = harness.load_cell(w["name"], manifest)
        assert cell.driver.Driver and cell.generator
        assert (harness.HERE / "limits" / f"{w['name']}.json").is_file()


#: the sources' config.json, by hand (huggingface.co/openai-community)
PUBLISHED = {
    "gpt2-medium": dict(n_layer=24, n_embd=1024, n_head=16),
    "gpt2-xl": dict(n_layer=48, n_embd=1600, n_head=25),
}
PUBLISHED_BY_ALL = dict(
    n_ctx=1024, n_positions=1024, vocab_size=50257, attn_pdrop=0.1,
    embd_pdrop=0.1, resid_pdrop=0.1, layer_norm_epsilon=1e-05,
    initializer_range=0.02, activation_function="gelu_new")


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_a_configuration_holds_the_published_values(manifest, name):
    """No key is changed from the source, so ``reduced`` is empty; where
    the program cannot run the published value the file says so in one
    named block, with both values, and the harness runs the ``run`` one."""
    entry = {c["name"]: c for c in manifest["configs"]}[name]
    held = harness.load_json(harness.ROOT / entry["file"])
    for key, value in {**PUBLISHED[name], **PUBLISHED_BY_ALL}.items():
        assert held[key] == value, key
    assert held["n_inner"] == 4 * held["n_embd"] and "n_inner" in held[
        "assumed"]
    assert held["reduced"] == entry["reduced"] == []
    departures = held["departures_forced_by_the_program"]
    for key, d in departures.items():
        if key == "what":
            continue
        assert set(d) == {"published", "run", "why"} and d["published"] != d[
            "run"], key
        if key in held:
            assert held[key] == d["published"]
            assert weights.as_run(held, key) == d["run"]
    assert weights.as_run(held, "layer_norm_epsilon") == 1e-06
    assert weights.sizes_of(held)["vocab"] == 50304
    assert weights.as_run(held, "n_layer") == held["n_layer"]


def test_metrics_and_the_cells_that_report_them(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    assert "workloads" not in e2e["setup_s"]
    reports = {c: {n for n, m in e2e.items()
                   if c in m.get("workloads", cells)} for c in cells}
    for c in cells:
        assert len(reports[c]) >= 2, f"{c}: setup_s and one more"
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for c in m.get("workloads", cells):
            assert c in cells
            assert m["moves"] in reports[c], (m["name"], c)
        spec = harness.load_json(
            harness.HERE / "layer_metrics" / f"{m['name']}.json")
        assert (harness.HERE / "readers" / f"{spec['reader']}.py").is_file()
    for c in cells:
        assert any(c in m.get("workloads", cells)
                   for m in manifest["per_layer"])
    # a share of a roofline or of a peak is a percentage, and where
    # kernels' rooflines move a metric the whole step's mfu does too
    for m in manifest["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"].split("."):
            assert m["unit"] == "%" and m["better"] == "higher"
        if "roofline" in m["name"]:
            assert any("mfu" in o["name"].split(".")
                       and o["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(o["workloads"])
                       for o in manifest["per_layer"]), m["name"]


def test_a_name_with_a_space_is_caught():
    assert not NAME.match("train loop")
    assert not NAME.match(".hidden") and not NAME.match("-x")
    assert not UNIT.match("tokens per second") and UNIT.match("tokens/s")
    assert NAME.match("gpt2-medium.train.seq1024")


def test_full_check_fits_the_drivers_day(manifest):
    runs = 2 + 14 * 24
    total = runs * (manifest["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_manifest_is_plain_json():
    json.loads(harness.MANIFEST.read_text())
