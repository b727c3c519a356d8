"""From a profiler trace and the benchmark's spans to per-layer metrics.

``load_xplane`` turns the profiler's ``.xplane.pb`` into a small plain
structure (what ``yardstick/testdata`` keeps a recorded copy of):

    {"devices": {"0": {"programs": [[name, start_ns, dur_ns], ...],
                       "ops": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}      # the benchmark's spans

On a TPU each chip is a plane ``/device:TPU:<n>``; its line ``XLA Modules``
has one event for each run of a jitted program (``jit_<name>(<id>)``) and
its line ``XLA Ops`` one for each operation inside, named by its whole HLO
text (``%fusion.12 = bf16[...] fusion(...)``). :func:`op_label` keeps the
instruction's name and, for a Pallas kernel (a ``tpu_custom_call``), how
many operands it takes and the types it returns, since the program gives
its kernels no names of their own: ``attn.72 pallas:3->bf16+f32``. The
benchmark's ``TraceAnnotation`` spans are events named ``ys.<name>`` on the
host plane's thread lines, on the same clock (looked at by hand, PR 25).
Everything below works on that structure alone.
"""

from __future__ import annotations

import importlib
import re
from pathlib import Path

from yardstick import harness
from yardstick.spans import PREFIX

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
PROGRAM_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
_ID = re.compile(r"\(\d+\)$")
_SUFFIX = re.compile(r"\.\d+")
_DTYPE = re.compile(r"\b([a-z]+\d+)\[")


def op_label(text: str) -> str:
    """A device operation's short name from its HLO text."""
    head, sep, rest = text.partition(" = ")
    name = head.lstrip("%")
    if not sep or 'custom_call_target="tpu_custom_call"' not in rest:
        return name
    outs, _, call = rest.partition(" custom-call(")
    operands = call.partition("), custom_call_target")[0]
    return (f"{name} pallas:{operands.count('%')}->"
            f"{'+'.join(_DTYPE.findall(outs))}")


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: Path) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    out: dict = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = out["devices"].setdefault(
                m.group(1), {"programs": [], "ops": []})
            for line in plane.lines:
                key = {PROGRAM_LINE: "programs", OPS_LINE: "ops"}.get(
                    line.name)
                if key is None:
                    continue
                label = op_label if key == "ops" else (
                    lambda name: _ID.sub("", name))
                dev[key].extend(
                    [label(e.name), float(e.start_ns),
                     float(e.duration_ns)] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name[len(PREFIX):], float(e.start_ns),
                     float(e.duration_ns)]
                    for e in line.events if e.name.startswith(PREFIX))
    for dev in out["devices"].values():
        dev["programs"].sort(key=lambda r: r[1])
        dev["ops"].sort(key=lambda r: r[1])
    out["host"].sort(key=lambda r: r[1])
    return out


# ---- interval arithmetic ------------------------------------------------


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Disjoint sorted intervals covering the same points."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: list[tuple[float, float]], lo: float, hi: float):
    """The complement of disjoint sorted ``busy`` inside ``[lo, hi]``."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def window_ns(trace: dict) -> tuple[float, float]:
    """The traced window on the trace's clock: from the benchmark's
    ``window_open`` marker to its ``window_close`` marker."""
    opened = [r for r in trace["host"] if r[0] == "window_open"]
    closed = [r for r in trace["host"] if r[0] == "window_close"]
    if not opened or not closed:
        raise ValueError("the trace lacks the window_open/window_close spans")
    return opened[0][1], closed[-1][1] + closed[-1][2]


def device_rows(trace: dict, key: str, device: str):
    return trace["devices"][device][key]


def busy_intervals(trace: dict, device: str, lo: float, hi: float):
    """When an operation ran on the device (programs where the ops line is
    empty), clipped to the window."""
    rows = device_rows(trace, "ops", device) or device_rows(
        trace, "programs", device)
    return union(clip([(s, s + d) for _, s, d in rows], lo, hi))


def busy_and_window_s(trace: dict) -> tuple[float, float]:
    """Seconds an operation ran, averaged over the devices in the trace,
    and the window's length."""
    lo, hi = window_ns(trace)
    devices = sorted(trace["devices"])
    if not devices:
        raise ValueError("no device plane in the trace: nothing ran on a TPU")
    busy = [total(busy_intervals(trace, d, lo, hi)) for d in devices]
    return sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9


def program_events(trace: dict, device: str, program: str):
    """Runs of the jitted program ``program`` (``jit_<program>``)."""
    want = {program, f"jit_{program}"}
    return [r for r in device_rows(trace, "programs", device)
            if r[0] in want]


def ops_within(trace: dict, device: str, spans_ns):
    """Operation events that start inside any of the sorted disjoint
    ``spans_ns`` (a program's runs)."""
    out, i = [], 0
    spans_ns = sorted(spans_ns)
    for row in device_rows(trace, "ops", device):
        while i < len(spans_ns) and spans_ns[i][1] <= row[1]:
            i += 1
        if i < len(spans_ns) and spans_ns[i][0] <= row[1]:
            out.append(row)
    return out


def family(op_label: str) -> str:
    """``fusion.123`` and ``fusion.7`` are one family, ``fusion``, and
    ``fusion.84.remat`` another, ``fusion.remat``; a kernel keeps its
    signature: ``attn pallas:3->bf16+f32``."""
    name, sep, kernel = op_label.partition(" ")
    return (_SUFFIX.sub("", name) or name) + sep + kernel


def top_device_ops(trace: dict, n: int = 10) -> list[list]:
    lo, hi = window_ns(trace)
    devices = sorted(trace["devices"])
    sums: dict[str, float] = {}
    for d in devices:
        for name, s, dur in device_rows(trace, "ops", d):
            if lo <= s < hi:
                sums[family(name)] = sums.get(family(name), 0.0) + dur
    rows = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / len(devices)] for k, v in rows]


def idle_gaps_by_span(trace: dict, n: int = 10) -> list[list]:
    """Idle time of the first device by what the host was doing: each gap
    goes to the benchmark's span that covers most of it."""
    lo, hi = window_ns(trace)
    device = sorted(trace["devices"])[0]
    spans = [(name, s, s + d) for name, s, d in trace["host"]
             if name not in ("window_open", "window_close")]
    sums: dict[str, float] = {}
    first = 0  # spans before it ended before the gap at hand began
    for a, b in gaps(busy_intervals(trace, device, lo, hi), lo, hi):
        while first < len(spans) and spans[first][2] <= a:
            first += 1
        best, cover = "no_span", 0.0
        for name, s, e in spans[first:]:
            if s >= b:
                break
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = name, c
        sums[best] = sums.get(best, 0.0) + (b - a)
    rows = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in rows]


# ---- the per-layer metrics of a cell ------------------------------------


def read_metric(name: str, facts: dict):
    """One per-layer metric through its own file and reader; ``None``
    where the reader finds nothing to read."""
    spec = harness.load_json(harness.HERE / "layer_metrics" / f"{name}.json")
    reader = importlib.import_module(f"yardstick.readers.{spec['reader']}")
    return reader.read(facts, **spec.get("args", {}))


def per_layer_metrics(cell, trace_dir, spans, window, driver_facts,
                      devices, peak_bytes):
    trace = load_xplane(find_xplane(trace_dir))
    busy_s, window_s = busy_and_window_s(trace)
    facts = {
        "trace": trace, "spans": spans.rows, "t_open": window.t_open,
        "t_close": window.t_close, "busy_s": busy_s, "window_s": window_s,
        "peaks": harness.peaks_for(devices[0].device_kind),
        "memory_peak_bytes": peak_bytes, "config": cell.config,
        "traffic": cell.traffic, **driver_facts,
    }
    metrics = {}
    for m in cell.per_layer:
        value = read_metric(m["name"], facts)
        if value is not None:
            metrics[m["name"]] = value
    breakdown = {"device_ops": top_device_ops(trace),
                 "idle_gaps": idle_gaps_by_span(trace)}
    return metrics, {"busy_s": busy_s, "window_s": window_s}, breakdown
