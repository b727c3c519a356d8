"""What every cell shares: the manifest, the files a name stands for, the
device, the result line. Nothing here knows a model, a mix or a metric: a
cell's configuration, traffic, driver, generator and per-layer readers are
files found by the names ``BENCHMARK.json`` gives.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import math
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
MANIFEST = ROOT / "BENCHMARK.json"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything its names stand for."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def driver(self):
        return importlib.import_module(
            f"yardstick.drivers.{self.config['driver']}")

    @property
    def generator(self):
        return importlib.import_module(
            f"yardstick.generators.{self.traffic['generator']}")


def _reported_in(metric: dict, cell: str, all_cells: list[str]) -> bool:
    return cell in metric.get("workloads", all_cells)


def load_cell(workload: str, manifest: dict | None = None,
              root: Path = ROOT) -> Cell:
    manifest = manifest or load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "yardstick" / "traffic"
                        / f"{w['traffic']}.json")
    names = list(cells)
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=traffic,
        end_to_end=[m for m in manifest["end_to_end"]
                    if _reported_in(m, workload, names)],
        per_layer=[m for m in manifest["per_layer"]
                   if _reported_in(m, workload, names)])


def peaks_for(device_kind: str) -> dict:
    """The peaks row of a ``device_kind``; a kind with no row raises."""
    table = load_json(HERE / "peaks.json")["rows"]
    kind = device_kind.lower()
    for key, row in table.items():
        if key in kind:
            return row
    raise ValueError(f"no peaks for device_kind {device_kind!r} in "
                     "yardstick/peaks.json: add its published row")


def require_chips(n: int):
    """The ``n`` TPU devices the cell runs on, or ``SystemExit``: JAX falls
    back to the CPU with a warning, and a number from there is not one."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"no TPU: jax.devices()[0] is {devs[0].platform!r} "
            f"({devs[0].device_kind!r}); the benchmark runs on the chip only")
    if len(devs) < n:
        raise SystemExit(f"the cell asks for {n} chips, JAX found "
                         f"{len(devs)}")
    return devs[:n]


def setup_compile_cache() -> str:
    """The program's own placement of JAX's persistent cache (the
    environment's directory, else ``<checkout>/.jax_cache``), and the small
    programs JAX leaves out by default kept too: a warm start compiled 15.7
    s of them again (PR 21)."""
    import jax

    from distributed_tensorflow_guide_tpu.core.device import (
        setup_compile_cache as place,
    )

    path = place()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def memory_peak_bytes(devices) -> int:
    """The peak on the fullest chip: buffers and programs' temporaries.
    The TPU runtime keeps a running program's temporaries under
    ``bytes_reserved`` and leaves them out of ``bytes_in_use`` (probe, PR
    25: cell 1's step has 9.41 GB of them by ``memory_analysis()``;
    ``peak_bytes_in_use`` read 5.09 GB and ``peak_bytes_reserved`` 9.35 GB,
    and at twice the batch the compiler refused the step for memory)."""
    def peak(stats: dict) -> int:
        return int(stats["peak_bytes_in_use"]) + int(
            stats.get("peak_bytes_reserved", 0))
    return max(peak(d.memory_stats()) for d in devices)


@dataclasses.dataclass
class Compared:
    """One number the correctness comparison read, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def verdict(compared: list[Compared]) -> bool:
    return bool(compared) and all(c.ok for c in compared)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, units: dict, device: dict,
                compared: list[Compared], breakdown: dict | None) -> str:
    """The contract's one line. ``compared`` comes last."""
    out = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {k: {"value": float(v), "unit": units[k]}
                    for k, v in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {c.name: {"value": _finite(c.value),
                                "limit": float(c.limit)} for c in compared}
    return json.dumps(out, allow_nan=False)


def _finite(x: float):
    """JSON has no NaN: a reading that is not a number prints as null
    (and has failed its limit)."""
    return float(x) if math.isfinite(x) else None


def say(**facts) -> None:
    """A line of facts on standard error (standard output's last line is
    the result, and nothing else there is read)."""
    print(json.dumps(facts, default=str), file=sys.stderr, flush=True)


def say_compared(compared: list[Compared]) -> None:
    for c in compared:
        print(f"compared {c.name}: {c.value:.6g} (limit {c.limit:.6g}) "
              f"{'ok' if c.ok else 'OVER'}", file=sys.stderr, flush=True)
