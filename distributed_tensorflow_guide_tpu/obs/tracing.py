"""Span API + Chrome/Perfetto trace-event JSON exporter.

:func:`span` is the one way the program marks a span, and it writes to two
sinks. Always: an annotation of ``jax.profiler`` named ``dtg.<name>``, so
that whenever a profiler session runs (``jax.profiler.start_trace``,
``utils.profiling.ProfilerHook``, the benchmark's ``--trace 1``) the span
lies on the host plane of the same ``.xplane.pb`` as the device's ``XLA
Modules`` and ``XLA Ops`` lines, on their clock. The session is the switch:
with none running no annotation is made. And, when the recorder
is enabled, paired events (``span.begin`` / ``span.end``) in the same
flight-recorder stream as everything else, with no second bookkeeping
path. The exporter maps the serve engine's event vocabulary onto the
Chrome trace-event format
(`chrome://tracing` / https://ui.perfetto.dev, "Open trace file"):

* ``span.begin`` / ``span.end``   -> ``B``/``E`` duration events on the
  track named in the payload (the train loop's data-wait / dispatch /
  hooks timeline, the engine's tick phases).
* ``prefill.launch`` / ``decode.launch`` -> ``X`` complete events on one
  track per engine slot (``slot0``, ``slot1``, ...), so a request reads
  as queued -> admitted -> prefill chunk(s) -> decode on its slot lane.
* ``req.admit``                   -> an ``X`` on the ``queue`` track
  spanning arrival -> admission (the queue-wait bar).
* everything else                 -> ``i`` instant events (lifecycle
  terminals, prefix hits/evictions, chaos faults, snapshots, ...).

:func:`watch_stalls` spans what stalls the host without being the
program's own work: a generation-2 garbage collection (``dtg.gc``) and
each phase of a JAX compile (``dtg.compile``), through the interpreter's
``gc.callbacks`` and ``jax.monitoring``'s listeners, and counts both.

Timestamps: the exporter prefers the semantic clock ``t`` (the engine's
virtual ``now``) and falls back to ``mono`` when ``t`` is None (every
span: a span measures the host, so it carries the wall clock). Events
whose resolved timestamp is non-finite are skipped —
``ServeEngine.run()`` drains with ``now=inf``, which is meaningful to
the scheduler but not to a timeline. ``pid`` is the event category,
``tid`` the track; both are stable small integers with ``M`` metadata
records carrying the human names.
"""

from __future__ import annotations

import gc
import json
import math
import threading
from typing import Iterable

from distributed_tensorflow_guide_tpu.obs import events as obs_events
from distributed_tensorflow_guide_tpu.obs.events import ObsEvent


_annotation = None  # bound by the first span(): obs/ imports without jax
_profiling = None  # ``TraceAnnotation.is_enabled``: a session is running


def _bind() -> None:
    global _annotation, _profiling
    import jax

    _profiling = jax.profiler.TraceAnnotation.is_enabled
    _annotation = jax.profiler.TraceAnnotation  # the guard: bound last


class span:
    """Mark a ``with`` block as the span ``name`` (dotted: ``engine.build``).

    The profiler sees ``dtg.<name>`` with ``attrs`` as the event's stats
    whenever a session runs; the recorder, when enabled, gets
    ``span.begin`` (payload: name, track, ``attrs``) and ``span.end``.
    Track and actor are the name's first part, so one component's spans
    share a lane. ``attrs`` are small values already at hand: they are
    evaluated whether or not anything listens. Spans nest; the ones of a
    tick or a step share its ``tick`` / ``step``. A class and not a
    generator under ``contextmanager``, which alone costs more than both
    sinks do when nothing listens. The annotation is made only while a
    session runs (one made outside a session records nothing, even if a
    session starts before it ends), which halves a span's cost when
    nothing listens."""

    __slots__ = ("_profiled", "_rec", "_cat", "_name", "_attrs")

    def __init__(self, rec, name: str, *, cat: str = "train", **attrs):
        if _annotation is None:
            _bind()
        self._profiled = (_annotation("dtg." + name, **attrs)
                          if _profiling() else None)
        self._rec, self._cat, self._name, self._attrs = rec, cat, name, attrs

    def _emit(self, kind: str, attrs: dict) -> None:
        track = self._name.partition(".")[0]
        self._rec.emit(kind, cat=self._cat, actor=track,
                       payload={"name": self._name, "track": track, **attrs})

    def __enter__(self) -> None:
        if self._profiled is not None:
            self._profiled.__enter__()
        if self._rec.enabled:
            self._emit("span.begin", self._attrs)

    def __exit__(self, *exc) -> None:
        if self._rec.enabled:
            self._emit("span.end", {})
        if self._profiled is not None:
            self._profiled.__exit__(*exc)


#: the phases of a JAX compile, by the event ``jax._src.dispatch.
#: log_elapsed_time`` records around each: ``record_scalar(event, start)``
#: on entry and ``record_event_duration_secs(event, elapsed)`` on exit,
#: whether or not the phase raised
COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}


class StallWatch:
    """Spans and counts of what stalls the host outside the program's own
    work. ``gc_full``: generation-2 collections since the watch started;
    ``compiles``: backend compiles (a persistent-cache hit among them);
    ``faults``: callbacks that failed, caught here and never passed on to
    the collector or the compiler.

    A generation-2 collection is the span ``dtg.gc``; generations 0 and 1
    return after one comparison (hundreds a second, each well under a
    millisecond, and a span there would take the leaf from every engine
    phase it struck). Each compile phase is ``dtg.compile`` with ``phase``
    (``trace``, ``lower``, ``backend``) and ``fun``. Both spans go to the
    profiler alone, the one sink their metrics read: a collection can
    strike inside the flight recorder's own locked ``emit``, where a
    ``span.begin`` would wait on a lock its own thread holds, and a
    compile's phases depend on the cache, not on the run. Collections
    never overlap (the interpreter makes one at a time), so one open gc
    span is all there is; compile phases nest, and are kept per thread,
    since a compile starts and ends on the thread that made it."""

    def __init__(self):
        self.gc_full = 0
        self.compiles = 0
        self.faults = 0
        self._gc_open = None
        self._open = threading.local()
        self._count = threading.Lock()  # compiles may run on two threads

    def _compiling(self) -> list:
        """This thread's open compile spans, innermost last."""
        return self._open.__dict__.setdefault("compile", [])

    def on_gc(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` entry. ``gc_full`` needs no lock, and must not
        take one: the collection may have struck while it was held."""
        if info["generation"] != 2:
            return
        try:
            if phase == "start":
                self.gc_full += 1
                self._gc_open = span(obs_events.NULL_RECORDER, "gc",
                                     cat="runtime")
                self._gc_open.__enter__()
            elif self._gc_open is not None:
                opened, self._gc_open = self._gc_open, None
                opened.__exit__(None, None, None)
        except Exception:  # noqa: BLE001 - never into the collector
            self.faults += 1

    def on_compile_start(self, event: str, value, **kwargs) -> None:
        """``jax.monitoring`` scalar listener: a compile phase begins."""
        phase = COMPILE_PHASES.get(event)
        if phase is None:
            return
        try:
            if phase == "backend":
                with self._count:
                    self.compiles += 1
            # the profiler's stats end at a comma
            fun = str(kwargs.get("fun_name", "")).replace(",", " ")
            opened = span(obs_events.NULL_RECORDER, "compile", cat="runtime",
                          phase=phase, fun=fun)
            opened.__enter__()
            self._compiling().append((phase, opened))
        except Exception:  # noqa: BLE001 - never into the compiler
            self.faults += 1

    def on_compile_end(self, event: str, duration_secs, **kwargs) -> None:
        """``jax.monitoring`` duration listener: the innermost open span of
        the phase closes, with any left open inside it; a phase with none
        open is ignored."""
        phase = COMPILE_PHASES.get(event)
        if phase is None:
            return
        try:
            stack = self._compiling()
            at = next((i for i in range(len(stack) - 1, -1, -1)
                       if stack[i][0] == phase), None)
            while at is not None and len(stack) > at:
                stack.pop()[1].__exit__(None, None, None)
        except Exception:  # noqa: BLE001 - never into the compiler
            self.faults += 1


_watch: StallWatch | None = None
_watch_lock = threading.Lock()


def watch_stalls() -> StallWatch:
    """The process's one :class:`StallWatch`, registered with the
    interpreter and with JAX on the first call; later calls register
    nothing more. With no profiler session running a collection or a
    compile costs the callback and a span that makes no annotation: the
    session is the switch, as for every span."""
    global _watch
    with _watch_lock:
        if _watch is None:
            import jax

            if _annotation is None:  # bound before a callback needs it
                _bind()
            watch = StallWatch()
            gc.callbacks.append(watch.on_gc)
            jax.monitoring.register_scalar_listener(watch.on_compile_start)
            jax.monitoring.register_event_duration_secs_listener(
                watch.on_compile_end)
            _watch = watch
    return _watch


def _fields(e) -> tuple[str, str, str, float | None, float, dict]:
    """(kind, cat, actor, t, mono, payload) from an ObsEvent or a dict
    (the shape ``events_from_dump`` round-trips)."""
    if isinstance(e, dict):
        return (e["kind"], e["cat"], e["actor"], e.get("t"),
                e.get("mono", 0.0), e.get("payload", {}))
    return e.kind, e.cat, e.actor, e.t, e.mono, e.payload


def _ts(t: float | None, mono: float) -> float | None:
    """Microsecond timestamp: semantic clock first, wall fallback;
    None = skip this event (non-finite virtual time)."""
    base = t if t is not None else mono
    if base is None or not math.isfinite(base):
        return None
    return base * 1e6


class _Ids:
    """Stable first-seen-order pid/tid assignment + metadata records."""

    def __init__(self):
        self.pids: dict[str, int] = {}
        self.tids: dict[tuple[int, str], int] = {}
        self.meta: list[dict] = []

    def pid(self, cat: str) -> int:
        if cat not in self.pids:
            self.pids[cat] = len(self.pids) + 1
            self.meta.append({"ph": "M", "name": "process_name",
                              "pid": self.pids[cat], "tid": 0,
                              "args": {"name": cat}})
        return self.pids[cat]

    def tid(self, pid: int, track: str) -> int:
        key = (pid, track)
        if key not in self.tids:
            self.tids[key] = len(self.tids) + 1
            self.meta.append({"ph": "M", "name": "thread_name",
                              "pid": pid, "tid": self.tids[key],
                              "args": {"name": track}})
        return self.tids[key]


def to_chrome_trace(events: Iterable) -> dict:
    """Events (ObsEvent objects or dump dicts) -> Chrome trace JSON."""
    ids = _Ids()
    out: list[dict] = []
    for e in events:
        kind, cat, actor, t, mono, payload = _fields(e)
        ts = _ts(t, mono)
        if ts is None:
            continue
        pid = ids.pid(cat)
        if kind in ("span.begin", "span.end"):
            tid = ids.tid(pid, str(payload.get("track", "main")))
            out.append({"ph": "B" if kind == "span.begin" else "E",
                        "name": str(payload.get("name", kind)),
                        "pid": pid, "tid": tid, "ts": ts})
        elif kind in ("prefill.launch", "decode.launch"):
            # a launch carries several slots' rows: one box a slot's lane
            dur = max(payload.get("dur_s", 0.0), 0.0) * 1e6
            slots = payload.get("slots", [])
            rids = payload.get("rids", [])
            chunks = payload.get("chunks", [None] * len(slots))
            for slot, rid, chunk in zip(slots, rids, chunks):
                tid = ids.tid(pid, f"slot{slot}")
                args = {"tick": payload.get("tick")}
                if chunk is not None:
                    args["chunk"] = chunk
                out.append({"ph": "X",
                            "name": f"{kind.partition('.')[0]} rid{rid}",
                            "pid": pid, "tid": tid, "ts": ts, "dur": dur,
                            "args": args})
        elif kind == "req.admit":
            wait = payload.get("queue_wait_s")
            tid = ids.tid(pid, "queue")
            if wait is not None and math.isfinite(wait) and wait >= 0:
                out.append({"ph": "X",
                            "name": f"rid{payload.get('rid')} queued",
                            "pid": pid, "tid": tid, "ts": ts - wait * 1e6,
                            "dur": wait * 1e6, "args": dict(payload)})
            else:
                out.append({"ph": "i", "s": "t", "name": kind, "pid": pid,
                            "tid": tid, "ts": ts,
                            "args": dict(payload)})
        else:
            tid = ids.tid(pid, "events")
            out.append({"ph": "i", "s": "t", "name": kind, "pid": pid,
                        "tid": tid, "ts": ts,
                        "args": {"actor": actor, **payload}})
    return {"traceEvents": ids.meta + out,
            "displayTimeUnit": "ms"}


def ttft_breakdown(events: Iterable) -> dict[int, dict[str, float]]:
    """Per-request TTFT split from the serve event stream.

    For every rid that reached a first token:
    ``queue_wait_s`` (arrival -> admission, from ``req.admit``),
    ``prefill_s`` (sum of its prefill launch durations), and
    ``first_decode_s`` (duration of the first decode launch carrying the
    rid; 0.0 when the final prefill chunk itself produced the first
    token). Durations are measured launch wall times — real numbers
    under the bench's virtual clock."""
    queue_wait: dict[int, float] = {}
    prefill: dict[int, float] = {}
    first_decode: dict[int, float] = {}
    first_token: set[int] = set()
    for e in events:
        kind, _cat, _actor, _t, _mono, payload = _fields(e)
        rid = payload.get("rid")
        if kind == "req.admit" and rid is not None:
            w = payload.get("queue_wait_s")
            if w is not None and math.isfinite(w):
                queue_wait.setdefault(rid, w)
        elif kind == "prefill.launch":
            for r in payload.get("rids", []):
                prefill[r] = prefill.get(r, 0.0) + payload.get("dur_s", 0.0)
        elif kind == "decode.launch":
            for r in payload.get("rids", []):
                if r not in first_token:
                    first_decode.setdefault(r, payload.get("dur_s", 0.0))
        elif kind == "req.first_token" and rid is not None:
            first_token.add(rid)
    return {rid: {"queue_wait_s": queue_wait.get(rid, 0.0),
                  "prefill_s": prefill.get(rid, 0.0),
                  "first_decode_s": first_decode.get(rid, 0.0)}
            for rid in sorted(first_token)}


def events_from_dump(path: str) -> list[ObsEvent]:
    """Load a :meth:`FlightRecorder.dump` file back into events."""
    with open(path) as f:
        data = json.load(f)
    return [ObsEvent(seq=d.get("seq", i), t=d.get("t"),
                     mono=d.get("mono", 0.0), kind=d["kind"],
                     cat=d.get("cat", "misc"), actor=d.get("actor", ""),
                     payload=d.get("payload", {}))
            for i, d in enumerate(data.get("events", []))]
