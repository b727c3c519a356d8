"""Benchmark-suite smoke tests: every judged-config bench runs end to end on
fake CPU devices and prints a well-formed JSON result line.

(The numbers only mean something on the real chip; these tests pin the
contract — the scripts stay runnable and the one-line JSON schema stays
intact — which is what the driver and judge consume.)
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
BENCH = REPO / "benchmarks"

from benchmarks.run_all import SMOKE  # noqa: E402  (one source of smoke cfgs)

CASES = sorted(SMOKE.items())


@pytest.mark.parametrize("script,args", CASES,
                         ids=[c[0].removeprefix("bench_").removesuffix(".py")
                              for c in CASES])
def test_bench_smoke(script, args):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # benches set their own device counts
    r = subprocess.run(
        [sys.executable, str(BENCH / script), *args],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    line = r.stdout.strip().splitlines()[-1]
    result = json.loads(line)
    # the contract keys must be present; benches may add evidence keys
    # (bench.py itself adds trials/spread_pct, fsdp_memory adds the
    # replicated-DP comparison)
    assert {"metric", "value", "unit", "vs_baseline"} <= set(result)
    assert result["value"] > 0


# ---- run_battery empty-artifact guard (ADVICE round 5) ----------------------
# A zero-byte battery_*.jsonl got committed as if it were capture evidence;
# run_battery now refuses to create a record-free artifact.


def test_run_battery_refuses_empty_artifact(tmp_path, monkeypatch):
    from benchmarks import run_battery

    out = tmp_path / "battery_empty.jsonl"
    monkeypatch.setattr(run_battery, "BATTERY", [])
    monkeypatch.setattr(sys, "argv",
                        ["run_battery.py", "--out", str(out)])
    with pytest.raises(SystemExit) as e:
        run_battery.main()
    assert "empty" in str(e.value)
    assert not out.exists()


# ---- the seam between the program and the machine (PR 21) --------------------
# The chip belongs to one process; a path whose numbers mean something only
# on the chip fails without one; every result line names its device; the
# compile cache is placed from outside or under the checkout, nowhere else.

import importlib.util

import jax

from distributed_tensorflow_guide_tpu.core import device

_spec = importlib.util.spec_from_file_location("bench_root", REPO / "bench.py")
bench_root = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_root)


def test_chip_smoke_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120, env=env,
                       cwd=REPO)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr and "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout  # no result line, whatever else it said


def test_chip_smoke_alone_is_not_the_program(tmp_path):
    """The script proves the repo runs; without the repo it has nothing to
    prove and must not print a result."""
    (tmp_path / "chip_smoke.py").write_bytes(
        (REPO / "chip_smoke.py").read_bytes())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], capture_output=True,
                       text=True, timeout=120, env=env, cwd=tmp_path)
    assert r.returncode != 0 and '"ok"' not in r.stdout


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch):
    monkeypatch.setenv(device.CACHE_ENV, "/x")
    before = jax.config.jax_compilation_cache_dir
    assert device.setup_compile_cache() == "/x"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv(device.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert device.setup_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("runner", ["run_battery", "run_all"])
def test_runner_parents_stay_off_the_chip(runner):
    """A parent that touched jax would hold the chip its bench
    subprocesses need: importing a runner and preparing its history
    context must leave no backend initialized."""
    code = (
        f"from benchmarks import {runner} as r\n"
        "from distributed_tensorflow_guide_tpu.analysis import regress\n"
        "e = regress.make_entry('row', {'metric': 'm', 'value': 1.0, "
        "'unit': 'u', 'device_kind': 'TPU v5 lite'}, "
        "git_rev=regress.git_sha())\n"
        "assert e['device_kind'] == 'TPU v5 lite', e\n"
        "assert regress.make_entry('row', None, git_rev='s')"
        "['device_kind'] == 'unknown'\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr


def test_run_battery_exits_nonzero_when_a_row_failed(tmp_path, monkeypatch):
    from benchmarks import run_battery

    monkeypatch.setattr(run_battery, "BATTERY", [
        ("fine", ["-c", "print('{\"metric\": \"m\", \"value\": 1}')"], 60),
        ("broken", ["-c", "raise SystemExit(3)"], 60)])
    monkeypatch.setattr(sys, "argv", [
        "run_battery.py", "--no-history", "--out", str(tmp_path / "b.jsonl")])
    with pytest.raises(SystemExit) as e:
        run_battery.main()
    assert e.value.code == 1
    recs = [json.loads(ln) for ln in
            (tmp_path / "b.jsonl").read_text().splitlines()]
    assert [r.get("rc") for r in recs[1:]] == [0, 3]  # both rows still ran


def test_unknown_tpu_device_kind_raises():
    assert device.peaks_for("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(ValueError, match="TPU v9000"):
        device.peaks_for("TPU v9000")


def test_peaks_are_none_off_tpu_and_raise_for_an_unknown_tpu(monkeypatch):
    from types import SimpleNamespace

    from benchmarks import common

    assert common.device_peak_flops() is None  # cpu: no peak, no fraction
    monkeypatch.setattr(jax, "devices", lambda: [
        SimpleNamespace(platform="tpu", device_kind="TPU v9000")])
    with pytest.raises(ValueError, match="TPU v9000"):
        common.device_hbm_peak()


def test_report_names_its_device(capsys):
    from benchmarks import common

    common.report("m", 12.34, "u", baseline=10.0, extra_key=1)
    line = json.loads(capsys.readouterr().out)
    assert line["value"] == 12.3 and line["vs_baseline"] == 1.234
    assert (line["platform"], line["device_count"]) == ("cpu", 8)
    assert line["device_kind"] == jax.devices()[0].device_kind
    assert line["extra_key"] == 1


def test_bench_line_names_its_device(monkeypatch, capsys):
    """bench.py in this process, with a two-layer stand-in for ResNet-50
    and the timed windows stubbed out (nothing here is a measurement): it
    prints one JSON line, and the line says where it ran."""
    import flax.linen as nn

    from benchmarks import common
    from distributed_tensorflow_guide_tpu.models import resnet

    class StandIn(nn.Module):
        num_classes: int
        dtype: object = None
        remat: bool = False
        fused_bn: bool = False

        @nn.compact
        def __call__(self, x, train=True):
            x = nn.BatchNorm(use_running_average=not train)(x.mean((1, 2)))
            return nn.Dense(self.num_classes)(x)

    monkeypatch.setattr(resnet, "ResNet50", StandIn)
    monkeypatch.setattr(
        common, "time_steps",
        lambda step, state, batch, *, steps, stats=None, **kw: (1.0, state))
    monkeypatch.setattr(common, "model_flops_per_step", lambda *a: 1e9)
    for k, v in {"BENCH_BATCH": "1", "BENCH_MODE": "windows",
                 device.CACHE_ENV: "/nonexistent"}.items():
        monkeypatch.setenv(k, v)
    bench_root.run_bench(fused_bn=False, overlap="off")
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    line = json.loads(out[0])
    assert {"metric", "value", "unit", "vs_baseline"} <= set(line)
    assert line["platform"] == "cpu" and line["device_count"] == 8
    assert line["device_kind"] and line["value"] == 120.0  # 120 steps / 1 s


def test_launcher_refuses_a_platform_it_cannot_give_each_child():
    from distributed_tensorflow_guide_tpu import launch

    with pytest.raises(SystemExit):
        launch.main(["--platform", "tpu", "x.py"])
