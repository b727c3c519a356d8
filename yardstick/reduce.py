"""From a profiler trace and the benchmark's spans to per-layer metrics.

``load_xplane`` is the one parse of the profiler's ``.xplane.pb``; it turns
it into a small plain structure (what ``yardstick/testdata`` keeps a
recorded copy of, without ``op_text``, ``program`` and ``xplane``):

    {"devices": {"0": {"programs": [[name, start_ns, dur_ns], ...],
                       "ops": [[name, start_ns, dur_ns], ...],
                       "op_text": [hlo_text, ...]}},   # beside "ops"
     "host": [[name, start_ns, dur_ns], ...],        # the benchmark's spans
     "program": [[name, start_ns, dur_ns, attrs, line], ...],
     "xplane": path}

On a TPU each chip is a plane ``/device:TPU:<n>``; its line ``XLA Modules``
has one event for each run of a jitted program (``jit_<name>(<id>)``) and
its line ``XLA Ops`` one for each operation inside, named by its whole HLO
text (``%fusion.12 = bf16[...] fusion(...)``). :func:`op_label` keeps the
instruction's name and, for a Pallas kernel (a ``tpu_custom_call``), how
many operands it takes and the types it returns, since the program gives
its kernels no names of their own: ``attn.72 pallas:3->bf16+f32``. The
whole text stays in ``op_text``, where ``scoped_ops`` looks up an
operation's scope. On the host planes' thread lines, on the same clock
(looked at by hand, PR 25), the benchmark's ``TraceAnnotation`` spans are
events named ``ys.<name>``, and the program's own spans
(``obs/tracing.span``) events named ``dtg.<name>`` whose stats are the
span's attributes (``tick``, ``step``, ``rows``, ...). Both are kept
without the prefix; a program row is in order of start with a parent
before its children, and ``line`` tells one host thread's events from
another's. A program that writes no ``dtg.`` span gives ``[]``, and a
structure without the key (the recorded one) reads as ``[]`` too.
Everything below works on that structure alone, and keeps what more than
one reader derives from it beside it (``busy``, ``scoped``).
"""

from __future__ import annotations

import bisect
import importlib
import re
from pathlib import Path

from yardstick import harness
from yardstick.spans import PREFIX

PROGRAM_PREFIX = "dtg."  # the program's own spans (``obs/tracing.span``)

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
    out: dict = {"devices": {}, "host": [], "program": [],
                 "xplane": str(path)}
    known: dict[str, tuple[str, str]] = {}  # HLO text -> (label, text)
    line_no = 0  # the program's rows number the host lines as they come
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = out["devices"].setdefault(
                m.group(1), {"programs": [], "ops": []})
            for line in plane.lines:
                if line.name == PROGRAM_LINE:
                    dev["programs"].extend(
                        [_ID.sub("", e.name), float(e.start_ns),
                         float(e.duration_ns)] for e in line.events)
                elif line.name == OPS_LINE:
                    for e in line.events:
                        name = e.name
                        label = known.get(name)
                        if label is None:  # one label and text a name
                            label = known[name] = (op_label(name), name)
                        dev["ops"].append([label[0], float(e.start_ns),
                                           float(e.duration_ns), label[1]])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                line_no += 1
                for e in line.events:
                    name = e.name
                    if name.startswith(PREFIX):
                        out["host"].append(
                            [name[len(PREFIX):], float(e.start_ns),
                             float(e.duration_ns)])
                    elif name.startswith(PROGRAM_PREFIX):
                        out["program"].append(
                            [name[len(PROGRAM_PREFIX):], float(e.start_ns),
                             float(e.duration_ns), dict(e.stats), line_no])
    for dev in out["devices"].values():
        dev["programs"].sort(key=lambda r: r[1])
        dev["ops"].sort(key=lambda r: r[1])
        dev["op_text"] = [r.pop() for r in dev["ops"]]
    out["host"].sort(key=lambda r: r[1])
    out["program"].sort(key=lambda r: (r[1], -r[2]))
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


def events_held(trace: dict) -> dict | None:
    """How much of the window's work the first device's lines hold:
    ``launches``, the program's ``engine.dispatch`` spans that start in the
    window; ``programs_held``, the share of them with a run on the ``XLA
    Modules`` line; ``ops_held``, the device time under an ``XLA Ops``
    event over that under a run; ``held_s``, the seconds from the window's
    opening to the end of the last run held. ``None`` where the program
    wrote no dispatch span (a training cell)."""
    lo, hi = window_ns(trace)
    launched = [r[3].get("program") for r in trace.get("program", [])
                if r[0] == "engine.dispatch" and lo <= r[1] < hi]
    if not launched:
        return None
    device, programs = sorted(trace["devices"])[0], set(launched)
    runs = [(s, s + d) for name, s, d in device_rows(
        trace, "programs", device)
        if name.removeprefix("jit_") in programs and lo <= s < hi]
    ran = total(union(clip([(s, s + d) for _, s, d in device_rows(
        trace, "programs", device)], lo, hi)))
    return {"launches": len(launched),
            "programs_held": len(runs) / len(launched),
            "ops_held": total(busy_intervals(trace, device, lo, hi)) / ran
            if ran else 0.0,
            "held_s": (max(b for _, b in runs) - lo) / 1e9 if runs else 0.0}


def busy_intervals(trace: dict, device: str, lo: float, hi: float):
    """When an operation ran on the device (programs where the ops line is
    empty), clipped to the window; kept in ``trace["busy"]``, since the
    idle share, the breakdown and the readers all ask for it."""
    kept = trace.setdefault("busy", {})
    if (device, lo, hi) not in kept:
        rows = device_rows(trace, "ops", device) or device_rows(
            trace, "programs", device)
        kept[device, lo, hi] = union(clip([(s, s + d) for _, s, d in rows],
                                          lo, hi))
    return kept[device, lo, hi]


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


def starting_within(rows: list[list], spans_ns) -> list[list]:
    """The rows (in order of start) that start inside any of the sorted
    disjoint ``spans_ns`` (a program's runs)."""
    out = []
    for a, b in sorted(spans_ns):
        out.extend(rows[bisect.bisect_left(rows, a, key=_start):
                        bisect.bisect_left(rows, b, key=_start)])
    return out


def _start(row: list) -> float:
    return row[1]


def ops_within(trace: dict, device: str, spans_ns):
    """Operation events that start inside any of the sorted disjoint
    ``spans_ns``."""
    return starting_within(device_rows(trace, "ops", device), spans_ns)


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
    families: dict[str, str] = {}
    for d in devices:
        for name, s, dur in device_rows(trace, "ops", d):
            if lo <= s < hi:
                kind = families.get(name)
                if kind is None:
                    kind = families[name] = family(name)
                sums[kind] = sums.get(kind, 0.0) + dur
    rows = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / len(devices)] for k, v in rows]


def leaves(rows: list[list]) -> list[list]:
    """The program's spans with no span inside them, thread by thread
    (``rows`` in ``trace["program"]``'s order), in order of start."""
    out = []
    last: dict[int, list] = {}  # the newest span of each line
    for row in rows:
        before = last.get(row[4])
        if before is not None and row[1] >= before[1] + before[2]:
            out.append(before)  # the next one starts after it: a leaf
        last[row[4]] = row
    out.extend(last.values())
    out.sort(key=lambda r: r[1])
    return out


def idle_gaps(trace: dict) -> list[tuple[float, float]]:
    """The first device's idle intervals in the window, sorted."""
    lo, hi = window_ns(trace)
    device = sorted(trace["devices"])[0]
    return gaps(busy_intervals(trace, device, lo, hi), lo, hi)


def under_spans(idle, spans):
    """``(gap index, name, start, end)`` of each part of the sorted
    disjoint ``idle`` that lies under a span ``(name, start, end)``, span
    by span in the order given."""
    starts, ends = [a for a, _ in idle], [b for _, b in idle]
    for name, s, e in spans:
        for i in range(bisect.bisect_right(ends, s),
                       bisect.bisect_left(starts, e)):
            a, b = max(idle[i][0], s), min(idle[i][1], e)
            if b > a:
                yield i, name, a, b


def leaf_spans(trace: dict) -> list[tuple[str, float, float]]:
    return [(r[0], r[1], r[1] + r[2])
            for r in leaves(trace.get("program", []))]


def idle_gaps_by_span(trace: dict, n: int = 10) -> list[list]:
    """Idle time of the first device by what the host was doing: each gap
    goes to the innermost span that covers most of it, a leaf of the
    program's own spans (``engine.fetch``, ``loop.data_wait``) where one
    covers any of it, else the benchmark's span, else ``no_span``."""
    idle = idle_gaps(trace)
    best: list[tuple[str, float]] = [("no_span", 0.0)] * len(idle)
    benchmark = [(name, s, s + d) for name, s, d in trace["host"]
                 if name not in ("window_open", "window_close")]
    for i, name, a, b in under_spans(idle, leaf_spans(trace)):
        if b - a > best[i][1]:
            best[i] = (name, b - a)
    rest = [i for i, (_, cover) in enumerate(best) if not cover]
    for j, name, a, b in under_spans([idle[i] for i in rest], benchmark):
        if b - a > best[rest[j]][1]:
            best[rest[j]] = (name, b - a)
    sums: dict[str, float] = {}
    for (name, _), (a, b) in zip(best, idle):
        sums[name] = sums.get(name, 0.0) + (b - a)
    rows = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in rows]


# ---- the per-layer metrics of a cell ------------------------------------


def read_metric(name: str, facts: dict):
    """One per-layer metric through its own file and reader; ``None``
    where the reader finds nothing to read."""
    spec = harness.load_json(harness.HERE / "layer_metrics" / f"{name}.json")
    reader = importlib.import_module(f"yardstick.readers.{spec['reader']}")
    return reader.read(facts, **spec.get("args", {}))


def per_layer_metrics(cell, trace_dir, driver_facts, devices, peak_bytes):
    trace = load_xplane(find_xplane(trace_dir))
    held = events_held(trace)
    if held:
        harness.say(events_held=held)
    busy_s, window_s = busy_and_window_s(trace)
    facts = {
        "trace": trace, "busy_s": busy_s, "window_s": window_s,
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
