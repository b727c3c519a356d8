"""Which part of the program a device operation belongs to.

The program wraps its parts in ``jax.named_scope`` (``dtg.routed``,
``dtg.short_conv``, ``dtg.attn``), and the compiler keeps the scope path of
every operation, a fusion's too, as its ``op_name``. The profiler writes
that into the trace as the ``tf_op`` stat of the operation's *event
metadata*, which ``jax.profiler.ProfileData`` does not hand out (an event's
own stats are its offset and duration; looked at by hand on a v5e trace, PR
28). So this file reads the ``.xplane.pb`` a third time, as bytes: a
protocol-buffer walk of just the fields it needs (``xplane.proto``: XSpace
1 planes; XPlane 2 name, 4 event_metadata, 5 stat_metadata; a map entry 1
key, 2 value; XEventMetadata 2 name, 5 stats; XStat 1 metadata_id, 5
str_value, 7 ref_value; XStatMetadata 2 name), skipping the lines, which
are nearly all of the file.

A row is ``[hlo_text, start_ns, dur_ns, scope_path]`` on the clock of
``facts["trace"]`` (the same file, the same ``ProfileData`` arithmetic). A
program without such scopes gives rows whose paths lack them, and a share
read from them is 0: the metric's file decides whether that is left out.
"""

from __future__ import annotations

import functools
from pathlib import Path

from yardstick import harness
from yardstick import reduce as reduction


def _varint(buf: bytes, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, at
        shift += 7


def fields(buf: bytes):
    """``(field number, wire type, value)`` of one message; a
    length-delimited value is a ``memoryview`` slice, not a copy."""
    at, end = 0, len(buf)
    while at < end:
        tag, at = _varint(buf, at)
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 1:
            value, at = buf[at:at + 8], at + 8
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire == 5:
            value, at = buf[at:at + 4], at + 4
        else:
            raise ValueError(f"wire type {wire} at byte {at}")
        yield number, wire, value


def _entry(buf) -> tuple[int, bytes]:
    key, value = 0, b""
    for number, _, v in fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def scope_paths(path: Path, stat: str = "tf_op") -> dict[str, str]:
    """Event-metadata name (an operation's whole HLO text) -> its scope
    path, over the device planes of the trace at ``path``."""
    out: dict[str, str] = {}
    space = memoryview(Path(path).read_bytes())
    for number, _, plane in fields(space):
        if number != 1:
            continue
        name, events, stats = "", [], {}
        for n, _, v in fields(plane):
            if n == 2:
                name = bytes(v).decode()
            elif n == 4:
                events.append(_entry(v)[1])
            elif n == 5:
                key, meta = _entry(v)
                stats[key] = next((bytes(x).decode() for f, _, x
                                   in fields(meta) if f == 2), "")
        if not reduction.DEVICE_PLANE.match(name):
            continue
        wanted = {k for k, v in stats.items() if v == stat}
        for meta in events:
            text, scope = "", None
            for n, _, v in fields(meta):
                if n == 2:
                    text = bytes(v).decode()
                elif n == 5:
                    st = {f: x for f, _, x in fields(v)}
                    if st.get(1) in wanted:
                        scope = (bytes(st[5]).decode() if 5 in st
                                 else stats.get(st.get(7), ""))
            if scope is not None:
                out[text] = scope
    return out


@functools.cache
def load(cell: str) -> dict[str, list[list]]:
    """Device -> the scoped rows of the traced run of ``cell`` that this
    process made, in order of start."""
    import jax

    path = reduction.find_xplane(harness.HERE / ".traces" / cell)
    scopes = scope_paths(path)
    out: dict[str, list[list]] = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        m = reduction.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        rows = out.setdefault(m.group(1), [])
        for line in plane.lines:
            if line.name == reduction.OPS_LINE:
                rows.extend([e.name, float(e.start_ns),
                             float(e.duration_ns), scopes.get(e.name, "")]
                            for e in line.events)
        rows.sort(key=lambda r: r[1])
    return out


def program_runs(facts: dict, program: str) -> list[tuple[float, float]]:
    """The first device's runs of ``program`` that start in the window."""
    trace = facts["trace"]
    device = sorted(trace["devices"])[0]
    lo, hi = reduction.window_ns(trace)
    return [(s, s + d) for _, s, d in reduction.program_events(
        trace, device, program) if lo <= s < hi]


def rows_within(facts: dict, cell: str, runs) -> list[list]:
    """The first device's scoped rows that start inside ``runs`` (sorted,
    disjoint)."""
    device = sorted(facts["trace"]["devices"])[0]
    out, i = [], 0
    for row in load(cell).get(device, []):
        while i < len(runs) and runs[i][1] <= row[1]:
            i += 1
        if i < len(runs) and runs[i][0] <= row[1]:
            out.append(row)
    return out


def under(row: list, scope: str, also_named=()) -> bool:
    """Whether a scoped row lies under ``scope``: by its path, or by its
    instruction's name where the compiler's own calls carry no path."""
    text, _, _, path = row
    return scope in path.split("/") or (
        bool(also_named) and text.lstrip("%").startswith(tuple(also_named)))
