"""The program's stall spans (``dtg.gc``, ``dtg.compile``) as a profiler
session on the CPU writes them and the reduction keeps them, and the twelve
``gc_ms_per_s.*`` / ``compile_ms_per_s.*`` metric files through
``reduce.read_metric``, on structures placed by hand."""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import pytest

from tests.yardstick.test_program_spans import row, serve_facts, serve_rows
from yardstick import harness
from yardstick import reduce as reduction
from yardstick.readers import span_ms_per_s

#: each cell's suffix, its cell and its layer
CELLS = {
    "train": ("gpt2-medium.train.seq1024", "train_loop"),
    "dp4": ("gpt2-medium.train.dp4", "train_loop"),
    "backlog": ("gpt2-xl.serve.backlog", "serve_engine"),
    "sharegpt": ("lfm2-24b-a2b.serve.sharegpt-backlog", "serve_engine"),
    "nemotron": ("nemotron-3-nano-30b-a3b.serve.sharegpt-backlog",
                 "serve_engine"),
    "phi4flash": ("phi-4-mini-flash-reasoning.serve.reasoning-backlog",
                  "serve_engine"),
}
#: the span and the counter each quantity reads
QUANTITIES = {"gc_ms_per_s": ("gc", "gc_full"),
              "compile_ms_per_s": ("compile", "compiles")}
STALL_METRICS = sorted(f"{q}.{s}" for q in QUANTITIES for s in CELLS)


def stalled_facts(counters: bool = True):
    """The 100 ms window of ``serve_rows`` with its ticks' counters, a
    collection of 4 ms inside tick 7's fetch, one of 3 ms across the
    window's end, and a compile whose trace (2 ms) holds an inner trace
    and whose lowering follows it: 30-33 ms under ``compile``, 3 ms."""
    rows = serve_rows()
    for r in rows:
        if counters and r[0] == "engine.tick":
            r[3].update(gc_full=0, compiles=0)
    rows += [row("gc", 20, 24), row("gc", 98, 101),
             row("compile", 30, 32, phase="trace", fun="f"),
             row("compile", 30.5, 31, phase="trace", fun="g"),
             row("compile", 32, 33, phase="lower", fun="jit(f)")]
    rows.sort(key=lambda r: (r[1], -r[2]))
    return {**serve_facts(rows), "window_s": 0.1, "steps": 2}


def test_reader_by_hand():
    facts = stalled_facts()
    # 4 ms and the 2 ms of the second collection inside the window
    assert span_ms_per_s.read(facts, span="gc", counter="gc_full") == \
        pytest.approx(6 / 0.1)
    # the union: the inner trace adds nothing, the lowering 1 ms
    assert span_ms_per_s.read(facts, span="compile",
                              counter="compiles") == pytest.approx(3 / 0.1)
    # watched, and nothing happened
    facts["trace"]["program"] = [r for r in facts["trace"]["program"]
                                 if r[0] not in ("gc", "compile")]
    assert span_ms_per_s.read(facts, span="gc", counter="gc_full") == 0.0
    # a program that does not watch says nothing, spans or none
    assert span_ms_per_s.read(stalled_facts(counters=False), span="gc",
                              counter="gc_full") is None


@pytest.mark.parametrize("name", STALL_METRICS)
def test_stall_metric_file_reads_its_span_and_is_silent_without(name):
    quantity, _, suffix = name.partition(".")
    span, counter = QUANTITIES[quantity]
    cell, layer = CELLS[suffix]
    facts = stalled_facts()
    if suffix in ("train", "dp4"):  # the counters ride the dispatch spans
        facts["trace"]["program"] = [
            r for r in facts["trace"]["program"] if r[0] == span] + [
            row("loop.dispatch", 4, 6, step=3, gc_full=0, compiles=0),
            row("loop.dispatch", 48, 52, step=4, gc_full=1, compiles=0)]
    assert reduction.read_metric(name, facts) == pytest.approx(
        {"gc": 60.0, "compile": 30.0}[span])
    assert reduction.read_metric(name, stalled_facts(counters=False)) is None
    assert reduction.read_metric(name, {**serve_facts(), "window_s": 0.1}) \
        is None  # the parent's trace: no ``dtg.`` rows at all
    entry = {m["name"]: m for m in harness.load_json(harness.MANIFEST)[
        "per_layer"]}[name]
    assert entry == {
        "name": name, "unit": "ms/s", "better": "lower",
        "source": "program_span", "layer": layer,
        "moves": ("train" if layer == "train_loop" else "serve")
        + "_tokens_per_s", "workloads": [cell]}
    assert harness.load_json(harness.HERE / "layer_metrics"
                             / f"{name}.json")["args"] == {
        "span": span, "counter": counter}


def test_a_gap_under_a_collection_goes_to_it():
    """``breakdown.idle_gaps``: the collection inside tick 7's fetch is a
    leaf and the fetch no longer one, so the gap 20-22 goes to ``gc``
    where it went to ``engine.fetch`` (``test_program_spans``: 0.043 s of
    fetch); the compile ends before the next gap opens and takes none."""
    gaps = dict(reduction.idle_gaps_by_span(stalled_facts()["trace"]))
    assert gaps == {"engine.fetch": pytest.approx(0.041),
                    "engine.schedule": pytest.approx(0.014),
                    "gc": pytest.approx(0.002)}


def profile(path, body):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return reduction.load_xplane(reduction.find_xplane(path))["program"]


def test_a_full_collection_is_a_leaf_inside_the_phase_it_struck(tmp_path):
    from distributed_tensorflow_guide_tpu.obs import events, tracing

    tracing.watch_stalls()

    def body():
        with tracing.span(events.NULL_RECORDER, "engine.fetch", tick=3):
            gc.collect(0)  # a young generation: no span
            gc.collect()

    rows = profile(tmp_path, body)
    names = [r[0] for r in rows]
    assert names.count("gc") == 1 and names.count("engine.fetch") == 1
    fetch, = (r for r in rows if r[0] == "engine.fetch")
    full, = (r for r in rows if r[0] == "gc")
    assert full[4] == fetch[4]  # the same thread's line
    assert fetch[1] <= full[1] and full[1] + full[2] <= fetch[1] + fetch[2]
    assert [r[0] for r in reduction.leaves(rows)] == ["gc"]


def test_a_fresh_jit_is_traced_lowered_and_compiled_under_spans(tmp_path):
    from distributed_tensorflow_guide_tpu.obs import events, tracing

    watch = tracing.watch_stalls()

    def body():
        x = jnp.arange(3.0)

        @jax.jit
        def fresh(v):
            return jnp.cos(v) + 7.0

        with tracing.span(events.NULL_RECORDER, "engine.dispatch", tick=1):
            fresh(x).block_until_ready()

        @jax.jit
        def broken(v):
            return v[5, 5]  # raises while it is traced

        with pytest.raises(IndexError):
            broken(x)

    compiles = watch.compiles
    rows = profile(tmp_path, body)
    assert watch.compiles > compiles and watch._compiling() == []
    dispatch, = (r for r in rows if r[0] == "engine.dispatch")
    mine = [r for r in rows if r[0] == "compile"
            and r[3]["fun"] in ("fresh", "jit(fresh)")]
    assert [r[3]["phase"] for r in mine] == ["trace", "lower", "backend"]
    for before, after in zip(mine, mine[1:]):
        assert before[1] + before[2] <= after[1]
    for r in mine:  # inside the phase that called it, on its line
        assert r[4] == dispatch[4]
        assert dispatch[1] <= r[1]
        assert r[1] + r[2] <= dispatch[1] + dispatch[2]
    inner = [r for r in rows if r[0] == "compile" and r[3]["fun"] == "cos"]
    assert inner and mine[0][1] <= inner[0][1]  # traced inside ``fresh``
    assert inner[0][1] + inner[0][2] <= mine[0][1] + mine[0][2]
    failed = [r for r in rows if r[0] == "compile"
              and r[3]["fun"] == "broken"]
    assert [r[3]["phase"] for r in failed] == ["trace"]
