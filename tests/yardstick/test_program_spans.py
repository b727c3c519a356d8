"""The readers of the program's own spans (``dtg.*``) on a structure placed
by hand, each metric by hand arithmetic; the one parse of a profile the CPU
wrote; and the metric files through ``reduce.read_metric``."""

from __future__ import annotations

import jax
import pytest

from yardstick import harness, program_spans
from yardstick import reduce as reduction
from yardstick.readers import (
    idle_explained,
    program_span_ms,
    scope_share,
    span_device_lag_ms,
)

MS = 1e6  # ns
SERVE, TRAIN = "gpt2-xl.serve.backlog", "gpt2-medium.train.seq1024"


def row(name, start_ms, end_ms, line=1, **attrs):
    return [name, start_ms * MS, (end_ms - start_ms) * MS, attrs, line]


def serve_rows():
    """A window of 100 ms. Tick 7 decodes: its program runs 14-34 with a
    hole at 20-22. Tick 8 prefills request 5: its program runs 60-85. Tick
    9 finds nothing to launch. Tick 6 began before the window."""
    t7, t8 = {"tick": 7}, {"tick": 8, "rid": 5}
    return [
        row("engine.tick", -5, -1, tick=6),
        row("engine.schedule", -5, -4, tick=6),
        row("engine.tick", 10, 40, tick=7),
        row("engine.schedule", 10, 11, **t7),
        row("engine.build", 11, 11.5, kind="decode", rows=2, **t7),
        row("engine.dispatch", 12, 13, program="decode_step", **t7),
        row("engine.fetch", 13, 35, **t7),
        row("engine.apply", 35, 37, **t7),
        row("engine.tick", 50, 90, tick=8),
        row("engine.schedule", 50, 53, **t8),
        row("engine.build", 53, 54.5, kind="prefill", rows=1, **t8),
        row("engine.dispatch", 55, 56, program="prefill_chunk_step",
            chunks=2, width=2, **t8),
        row("engine.fetch", 56, 88, **t8),
        row("engine.apply", 88, 89, **t8),
        row("engine.tick", 92, 93, tick=9),
        row("engine.schedule", 92, 92.8, tick=9),
    ]


def serve_facts(rows=()):
    trace = {
        "devices": {"0": {
            "programs": [["jit_decode_step", 14 * MS, 20 * MS],
                         ["jit_prefill_chunk_step", 60 * MS, 25 * MS]],
            "ops": [["fusion.1", 14 * MS, 6 * MS],
                    ["fusion.2", 22 * MS, 12 * MS],
                    ["fusion.3", 60 * MS, 25 * MS]]}},
        "host": [["window_open", 0.0, 0.0], ["window_close", 100 * MS, 0.0]],
        "program": list(rows),
    }
    return {"trace": trace}


def test_phase_means_by_hand():
    facts = serve_facts(serve_rows())

    def ms(span, **kw):
        return program_span_ms.read(facts, span=span, **kw)

    # ticks 7 and 8 launched; tick 9's 0.8 ms only counts without `beside`
    assert ms("engine.schedule", beside="engine.dispatch") == pytest.approx(
        (1 + 3) / 2)
    assert ms("engine.schedule") == pytest.approx((1 + 3 + 0.8) / 3)
    assert ms("engine.build") == pytest.approx((0.5 + 1.5) / 2)
    assert ms("engine.apply") == pytest.approx((2 + 1) / 2)
    # the whole tick less its program: 30 - 20, 40 - 25, and the idle 1
    assert ms("engine.tick", stat="max", less_device=True) == pytest.approx(
        15.0)
    assert ms("engine.tick", less_device=True) == pytest.approx(
        (10 + 15 + 1) / 3)
    assert ms("engine.tick", stat="max") == pytest.approx(40.0)
    assert ms("no.such.span") is None
    with pytest.raises(ValueError, match="mean or max"):
        ms("engine.build", stat="median")


def test_fetch_lag_between_host_span_and_device_program_by_hand():
    facts = serve_facts(serve_rows())
    # fetch end less program end: 35 - 34 and 88 - 85
    assert span_device_lag_ms.read(facts) == pytest.approx((1 + 3) / 2)
    # a tick whose program the trace lacks is left out, not guessed
    facts["trace"]["devices"]["0"]["programs"].pop()
    assert span_device_lag_ms.read(facts) == pytest.approx(1.0)


def test_idle_explained_by_hand(capsys):
    # idle: 0-14, 20-22, 34-60, 85-100 = 57 ms. Under a leaf span:
    #   0-14:   schedule 1, build 0.5, dispatch 1, fetch 13-14      = 3.5
    #   20-22:  fetch                                               = 2
    #   34-60:  fetch 1, apply 2, schedule 3, build 1.5, dispatch 1,
    #           fetch 56-60                                         = 12.5
    #   85-100: fetch 3, apply 1, tick 9's schedule 0.8             = 4.8
    got = idle_explained.read(serve_facts(serve_rows()))
    assert got == pytest.approx(100 * 22.8 / 57)
    said = capsys.readouterr().err
    assert '"engine.fetch": 0.011' in said and '"idle_s": 0.057' in said
    # engine.tick covers 71 of the 100 ms and is no leaf: it explains none
    assert '"engine.tick"' not in said


def test_the_breakdown_gives_each_gap_to_the_leaf_under_most_of_it():
    """The same parts as ``idle_explained``'s, each whole gap to the one
    leaf under most of it (the first of equals): 0-14 to tick 7's schedule
    (1 ms, as its dispatch and the fetch), 20-22, 34-60 (4 ms of fetch of
    12.5 under leaves) and 85-100 (3 of 4.8) to a fetch."""
    trace = serve_facts(serve_rows())["trace"]
    trace["host"] += [["engine_step", 9 * MS, 90 * MS]]
    trace["host"].sort(key=lambda r: r[1])
    gaps = dict(reduction.idle_gaps_by_span(trace))
    assert gaps == {"engine.fetch": pytest.approx(0.043),
                    "engine.schedule": pytest.approx(0.014)}
    # without the program's spans (the parent of PR 26) the benchmark's
    # span around ``step()`` takes every gap it touches, as it did
    trace["program"] = []
    assert reduction.idle_gaps_by_span(trace) == [
        ["engine_step", pytest.approx(0.057)]]


def test_leaves_thread_by_thread():
    rows = sorted(
        [row("a", 0, 10), row("a.b", 1, 4), row("a.c", 4, 9),
         row("a.c.d", 5, 6), row("e", 10, 12),
         row("other", 2, 8, line=2)],  # overlaps a.b, on another thread
        key=lambda r: (r[1], -r[2]))
    assert [r[0] for r in reduction.leaves(rows)] == [
        "a.b", "other", "a.c.d", "e"]


def test_train_spans_per_step_by_hand():
    facts = {**serve_facts([
        row("loop.data_wait", 3, 4, step=3),
        row("prefetch.host_fetch", 3.1, 3.2),
        row("prefetch.put", 3.2, 3.7),
        row("loop.dispatch", 4, 6, step=3),
        row("loop.data_wait", 45, 48, step=4),
        row("prefetch.put", 46, 47),
        row("loop.dispatch", 48, 52, step=4),
        row("loop.data_wait", 101, 102, step=5),  # after the window
    ]), "steps": 2}

    def ms(span):
        return program_span_ms.read(facts, span=span, per="steps")

    assert ms("loop.data_wait") == pytest.approx((1 + 3) / 2)
    assert ms("prefetch.put") == pytest.approx((0.5 + 1) / 2)
    assert ms("loop.dispatch") == pytest.approx((2 + 4) / 2)


#: each metric file of the program's spans, and what it reads from them
SPAN_METRICS = {
    "schedule_ms_per_tick.backlog": 2.0, "build_ms_per_tick.backlog": 1.0,
    "apply_ms_per_tick.backlog": 1.5, "fetch_lag_ms_per_tick.backlog": 2.0,
    "tick_host_max_ms.backlog": 15.0, "idle_explained.backlog": 40.0,
    "data_wait_ms.train": 2.0, "prefetch_put_ms.train": 0.75,
    "dispatch_ms.train": 3.0,
    # tick 7's launch overlapped the one before it, tick 8's did not
    "overlapped_launches.backlog": 0.5, "overlapped_launches.nemotron": 0.5,
    # tick 8's prefill launch: 2 chunks in 25 ms of device time
    "chunks_per_prefill.backlog": 2.0, "chunks_per_prefill.sharegpt": 2.0,
    "chunks_per_prefill.nemotron": 2.0, "chunks_per_prefill.phi4flash": 2.0,
    "prefill_ms_per_chunk.backlog": 12.5,
    "prefill_ms_per_chunk.sharegpt": 12.5,
    "prefill_ms_per_chunk.nemotron": 12.5,
    "prefill_ms_per_chunk.phi4flash": 12.5,
}
SOURCES = {"chunks_per_prefill": "program_counter",
           "overlapped_launches": "program_counter",
           "prefill_ms_per_chunk": "device_trace"}


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_metric_file_reads_its_span_and_is_silent_without(name):
    """Each metric through its own file, as the command reads it; on a
    trace with no ``dtg.`` rows (the parent's) it returns ``None``."""
    facts = {**serve_facts(), "steps": 2}
    assert reduction.read_metric(name, facts) is None
    rows = serve_rows()  # a cell's trace holds its own program's spans
    rows[5][3]["overlapped"], rows[11][3]["overlapped"] = 1, 0
    if name.endswith(".train"):
        rows = [
            row("loop.data_wait", 3, 4, step=3),
            row("prefetch.put", 3.2, 3.7), row("loop.dispatch", 4, 6, step=3),
            row("loop.data_wait", 45, 48, step=4),
            row("prefetch.put", 46, 47), row("loop.dispatch", 48, 52, step=4)]
    facts["trace"]["program"] = rows
    assert reduction.read_metric(name, facts) == pytest.approx(
        SPAN_METRICS[name])
    entry = {m["name"]: m for m in harness.load_json(harness.MANIFEST)[
        "per_layer"]}[name]
    assert entry["source"] == SOURCES.get(name.partition(".")[0],
                                          "program_span")
    cell = {"train": TRAIN, "backlog": SERVE}.get(name.rpartition(".")[2])
    assert cell is None or entry["workloads"] == [cell]


def profile(path):
    """The program's spans and one of the benchmark's, as a profiler
    session on the CPU writes them."""
    from distributed_tensorflow_guide_tpu.obs import events, tracing

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=options)
    try:
        rec = events.NULL_RECORDER
        with tracing.span(rec, "engine.tick", cat="serve", tick=4):
            with tracing.span(rec, "engine.build", cat="serve", tick=4,
                              kind="decode", rows=3):
                pass
            with tracing.span(rec, "engine.fetch", cat="serve", tick=4):
                pass
        with jax.profiler.TraceAnnotation("ys.not_the_programs"):
            pass
    finally:
        jax.profiler.stop_trace()
    return reduction.find_xplane(path)


def test_loader_reads_names_attrs_and_order_from_a_real_profile(tmp_path):
    trace = reduction.load_xplane(profile(tmp_path))
    rows = trace["program"]
    assert [r[0] for r in rows] == ["engine.tick", "engine.build",
                                    "engine.fetch"]
    assert rows[0][3] == {"tick": 4}
    assert rows[1][3] == {"tick": 4, "kind": "decode", "rows": 3}
    assert [program_spans.ident(r) for r in rows] == [4, 4, 4]
    tick, build, fetch = rows
    assert tick[1] <= build[1] and build[1] + build[2] <= fetch[1]
    assert fetch[1] + fetch[2] <= tick[1] + tick[2]
    assert len({r[4] for r in rows}) == 1  # one thread, one line
    assert [r[0] for r in reduction.leaves(rows)] == [
        "engine.build", "engine.fetch"]
    # the benchmark's span from the same parse, on the same clock
    (mine,) = trace["host"]
    assert mine[0] == "not_the_programs" and mine[1] >= fetch[1]
    assert program_spans.host_spans(trace["xplane"]) == rows


def test_one_parse_of_the_profile_serves_every_reader(tmp_path,
                                                      monkeypatch):
    """``ProfileData`` opens the file once: the program's spans come from
    the reduction's parse, and the scopes from a walk of its bytes."""
    path = profile(tmp_path)
    opened = []
    real = jax.profiler.ProfileData.from_file
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        lambda p: opened.append(p) or real(p))
    trace = reduction.load_xplane(path)
    trace["devices"]["0"] = {"programs": [["jit_decode_step", 0.0, 1.0]],
                             "ops": [], "op_text": []}
    first = trace["program"][0][1]
    trace["host"] += [["window_open", first - 1, 0.0],
                      ["window_close", first + 1e9, 0.0]]
    trace["host"].sort(key=lambda r: r[1])
    facts = {"trace": trace}
    assert program_spans.in_window(facts) == trace["program"]
    assert program_span_ms.read(facts, span="engine.build") > 0
    assert scope_share.read(facts, program="decode_step",
                            scope="dtg.routed") is None
    assert trace["scoped"] == {"0": []}
    assert opened == [str(path)]
