"""Observability plane (obs/): the acceptance pin is INERTNESS —
recorder ON vs OFF must be bitwise-invisible to every compiled path
(engine completions across the decode levers with zero new compiles, a
50-step train loop's final state), while the recorder itself must be
exactly reproducible under seeded chaos, dump a usable black box on
watchdog/give-up trips, export schema-valid Chrome traces, and join
static cost vectors against measured time with pinned closed forms."""

import dataclasses
import gc
import json
import math
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_guide_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
)
from distributed_tensorflow_guide_tpu.obs import events as obs_events
from distributed_tensorflow_guide_tpu.obs import metrics as obs_metrics
from distributed_tensorflow_guide_tpu.obs import recon as obs_recon
from distributed_tensorflow_guide_tpu.obs import tracing as obs_trace
from distributed_tensorflow_guide_tpu.serve import Request, ServeEngine
from distributed_tensorflow_guide_tpu.serve import engine as serve_engine
from distributed_tensorflow_guide_tpu.testing.chaos import FaultSchedule
from distributed_tensorflow_guide_tpu.train.hooks import (
    MetricsHook,
    StopAtStepHook,
)
from distributed_tensorflow_guide_tpu.train.loop import TrainLoop

# same geometry as tests/test_serving.py: the engine step-fn memo is
# keyed by (cfg, geometry, sampling), so these runs share its compiles —
# recorder tests must never pay (or cause) a new compile.
CFG = TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                        d_model=16, d_ff=32, max_len=64, causal=True,
                        dtype=jnp.float32)
PROMPTS = [np.array([3, 5, 7, 9, 11], np.int32),
           np.array([2, 4, 6, 8, 10, 12, 14, 16, 18], np.int32),
           np.array([1] * 17, np.int32)]
MAX_NEW = [8, 6, 10]


@pytest.fixture(scope="module")
def params():
    return Transformer(CFG).init(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))["params"]


def _engine(cfg, params, *, recorder=None, prompts=PROMPTS,
            max_new=MAX_NEW, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("num_blocks", 33)
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_chunk", 8)
    eng = ServeEngine(cfg, params, temperature=0.8, top_k=10,
                      recorder=recorder, **kw)
    for i, (p, mn) in enumerate(zip(prompts, max_new)):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=mn,
                           rng=jax.random.PRNGKey(100 + i)))
    return eng


def _drive(eng):
    """Step with a finite virtual clock (like bench_serving's driver) so
    every event carries a real semantic timestamp."""
    now = 0.0
    while (eng.sched.has_queued or eng.sched.has_resident
           or eng._pressure_holds):
        eng.step(now)
        now += 0.01


# ---- ring semantics ---------------------------------------------------------


def test_ring_drops_oldest_and_counts():
    rec = obs_events.FlightRecorder(capacity=4)
    for i in range(10):
        rec.emit("k", payload={"i": i})
    assert len(rec) == 4 and rec.total == 10 and rec.dropped == 6
    assert [e.payload["i"] for e in rec.events()] == [6, 7, 8, 9]
    assert [e.seq for e in rec.events()] == [6, 7, 8, 9]
    rec.clear()
    assert len(rec) == 0 and rec.total == 10  # history count survives
    with pytest.raises(ValueError, match="capacity"):
        obs_events.FlightRecorder(capacity=0)


def test_dump_roundtrip_signature_and_volatile_keys(tmp_path):
    def mk(dur):
        rec = obs_events.FlightRecorder(clock=lambda: 2.5)
        rec.emit("req.admit", cat="serve", actor="sched",
                 payload={"rid": 1, "queue_wait_s": dur})
        rec.emit("decode.launch", cat="serve", actor="engine",
                 payload={"slots": [0], "rids": [1], "dur_s": dur})
        return rec

    a, b = mk(0.111), mk(0.999)
    # wall-measured durations are VOLATILE: they differ run to run and
    # must not break the reproducibility signature
    assert obs_events.signature(a.events()) == \
        obs_events.signature(b.events())
    sig_t = obs_events.signature(a.events(), include_t=True)
    assert all(row[3] == 2.5 for row in sig_t)  # injected clock stamped

    path = a.dump(str(tmp_path / "d.json"))
    data = json.loads(open(path).read())
    assert data["schema"] == obs_events.SCHEMA
    assert data["total"] == 2 and data["dropped"] == 0
    back = obs_trace.events_from_dump(path)
    assert obs_events.signature(back) == obs_events.signature(a.events())
    # non-finite floats become null in strict JSON
    a.emit("x", payload={"v": float("inf")})
    data = json.loads(open(a.dump(str(tmp_path / "e.json"))).read())
    assert data["events"][-1]["payload"]["v"] is None


def test_crash_dump_black_box(tmp_path):
    bb = tmp_path / "bb.json"
    rec = obs_events.FlightRecorder(crash_dump_path=str(bb))
    rec.emit("before", payload={})
    out = rec.crash_dump("watchdog.trip", cat="watchdog",
                         payload={"tag": "step"})
    assert out == str(bb)
    dumped = json.loads(bb.read_text())
    assert [e["kind"] for e in dumped["events"]] == \
        ["before", "watchdog.trip"]
    # no path configured: the event still lands, nothing is written
    rec2 = obs_events.FlightRecorder()
    assert rec2.crash_dump("x") is None and rec2.total == 1


def test_null_recorder_and_install():
    null = obs_events.NULL_RECORDER
    assert not null.enabled and null.emit("k") is None
    assert null.events() == [] and len(null) == 0
    assert null.crash_dump("k") is None
    rec = obs_events.FlightRecorder()
    prev = obs_events.install(rec)
    try:
        assert obs_events.current() is rec
    finally:
        obs_events.install(prev)
    assert obs_events.current() is prev


# ---- metrics registry -------------------------------------------------------


def test_registry_counters_gauges_histograms():
    reg = obs_metrics.Registry()
    reg.counter("dtg_c", "help").inc(3)
    reg.counter("dtg_c").inc()  # get-or-create returns the same metric
    with pytest.raises(ValueError, match="decrease"):
        reg.counter("dtg_c").inc(-1)
    reg.gauge("dtg_g", labels={"tenant": "0"}).set(2.5)
    h = reg.histogram("dtg_h", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    snap = reg.snapshot()
    assert snap["dtg_c"] == 4.0
    assert snap['dtg_g{tenant="0"}'] == 2.5
    assert snap["dtg_h"]["count"] == 3 and snap["dtg_h"]["sum"] == 5.55
    assert snap["dtg_h"]["buckets"] == {0.1: 1, 1.0: 2}
    with pytest.raises(TypeError, match="registered as counter"):
        reg.gauge("dtg_c")
    text = reg.to_prometheus()
    assert "# TYPE dtg_c counter" in text
    assert 'dtg_g{tenant="0"} 2.5' in text
    assert 'dtg_h_bucket{le="+Inf"} 3' in text
    assert "dtg_h_sum 5.55" in text


def test_absorbers_map_existing_stats():
    reg = obs_metrics.Registry()
    obs_metrics.absorb_dispatch(reg, SimpleNamespace(
        dispatches=4, steps=2, host_gap_s=0.2, dispatch_s=0.05))
    obs_metrics.absorb_prefetch(reg, SimpleNamespace(
        batches=3, host_wait_s=0.1, max_host_wait_s=0.08, put_s=0.01,
        peak_ahead=2))
    snap = reg.snapshot()
    assert snap["dtg_train_dispatches_total"] == 4
    assert snap["dtg_train_host_gap_ms_per_dispatch"] == \
        pytest.approx(50.0)
    assert snap["dtg_data_prefetch_batches_total"] == 3
    assert snap["dtg_data_prefetch_peak_ahead"] == 2


def test_pool_and_prefix_stats_shapes():
    from distributed_tensorflow_guide_tpu.serve import BlockPool
    from distributed_tensorflow_guide_tpu.serve.prefix_index import (
        PrefixIndex,
    )

    pool = BlockPool(num_blocks=5, block_size=8)
    blocks = pool.alloc(1, 1)
    pool.share(2, blocks)  # refcount 2 -> one live block, shared
    s = pool.stats()
    assert s == {"capacity": 4, "free": 3, "live": 1, "shared": 1,
                 "holds": 2}
    pool.free(1, blocks)
    pool.free(2, blocks)
    pool.check_leaks()
    assert pool.stats()["free"] == 4 and pool.stats()["shared"] == 0

    idx = PrefixIndex(block_size=4)
    assert idx.stats() == {"nodes": 0, "leaves": 0, "max_depth": 0,
                           "adapters": 0, "spilled": 0}
    reg = obs_metrics.Registry()
    obs_metrics.absorb_pool(reg, s)
    obs_metrics.absorb_prefix(reg, idx.stats())
    snap = reg.snapshot()
    assert snap["dtg_serve_pool_live"] == 1
    assert snap["dtg_serve_prefix_nodes"] == 0
    assert snap["dtg_serve_prefix_spilled"] == 0


# ---- chrome trace exporter --------------------------------------------------


def test_chrome_exporter_schema():
    rec = obs_events.FlightRecorder()
    rec.emit("span.begin", cat="train",
             payload={"name": "s", "track": "loop", "step": 0}, t=1.0)
    rec.emit("span.end", cat="train",
             payload={"name": "s", "track": "loop"}, t=2.0)
    rec.emit("prefill.launch", cat="serve",
             payload={"slots": [0, 2], "rids": [1, 4], "chunks": [8, 0],
                      "width": 2, "tick": 0, "dur_s": 0.5}, t=3.0)
    rec.emit("decode.launch", cat="serve",
             payload={"slots": [0, 1], "rids": [1, 2], "tick": 1,
                      "dur_s": 0.25}, t=4.0)
    rec.emit("req.admit", cat="serve",
             payload={"rid": 3, "slot": 1, "queue_wait_s": 0.5}, t=5.0)
    rec.emit("req.done", cat="serve", payload={"rid": 1, "tick": 2},
             t=6.0)
    rec.emit("req.admit", cat="serve", payload={"rid": 9},
             t=float("inf"))  # engine.run() drains at now=inf: skipped

    trace = obs_trace.to_chrome_trace(rec.events())
    json.dumps(trace)  # strict-JSON serializable
    evs = trace["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    real = [e for e in evs if e["ph"] != "M"]
    # the non-finite-clock event is dropped, everything else lands
    assert not any(e.get("args", {}).get("rid") == 9 for e in real)
    # B/E pair up per (pid, tid, name)
    b = [(e["pid"], e["tid"], e["name"]) for e in real if e["ph"] == "B"]
    e_ = [(e["pid"], e["tid"], e["name"]) for e in real
          if e["ph"] == "E"]
    assert b and sorted(b) == sorted(e_)
    # decode.launch fans out to one X per (slot, rid)
    xs = [e for e in real if e["ph"] == "X"]
    assert {e["name"] for e in xs} >= {"decode rid1", "decode rid2",
                                       "prefill rid1", "prefill rid4"}
    assert [e["args"]["chunk"] for e in xs
            if e["name"].startswith("prefill")] == [8, 0]
    # the queue-wait bar is backdated by exactly the admit's wait
    bar = next(e for e in xs if e["name"] == "rid3 queued")
    assert bar["ts"] == pytest.approx(5.0e6 - 0.5e6)
    assert bar["dur"] == pytest.approx(0.5e6)
    # every (pid, tid) in use carries exactly one thread_name M record
    used = {(e["pid"], e["tid"]) for e in real}
    named = [(e["pid"], e["tid"]) for e in meta
             if e["name"] == "thread_name"]
    assert len(named) == len(set(named)) and used <= set(named)
    assert {e["pid"] for e in real} == \
        {e["pid"] for e in meta if e["name"] == "process_name"}
    # instants carry scope + ts
    inst = [e for e in real if e["ph"] == "i"]
    assert inst and all(e["s"] == "t" and math.isfinite(e["ts"])
                        for e in inst)


# ---- inertness: recorder on/off is bitwise-invisible ------------------------


def test_engine_bitwise_parity_and_zero_new_compiles(params):
    eng_off = _engine(CFG, params)
    eng_off.run()
    compiled = len(serve_engine._STEP_FNS)

    rec = obs_events.FlightRecorder()
    eng_on = _engine(CFG, params, recorder=rec)
    eng_on.run()
    assert eng_on.completions() == eng_off.completions()
    # the recorder caused no new program: same memoized geometry
    assert len(serve_engine._STEP_FNS) == compiled
    kinds = {e.kind for e in rec.events()}
    assert {"req.submit", "req.admit", "prefill.launch", "decode.launch",
            "req.first_token", "req.done"} <= kinds
    done = [e.payload["rid"] for e in rec.events()
            if e.kind == "req.done"]
    assert sorted(done) == [0, 1, 2]
    # determinism: an identical run produces the identical sequence
    rec2 = obs_events.FlightRecorder()
    eng2 = _engine(CFG, params, recorder=rec2)
    eng2.run()
    assert obs_events.signature(rec2.events()) == \
        obs_events.signature(rec.events())


@pytest.mark.parametrize("kv,impl", [("int8", "dense"), (None, "pallas"),
                                     ("int8", "pallas")])
def test_engine_parity_across_decode_levers(params, kv, impl):
    """The PR-10 lever geometries (identical to test_serving's, so the
    step-fn memo is shared): recording must be invisible under each."""
    cfg = dataclasses.replace(CFG, kv_dtype=kv, decode_impl=impl)
    kw = dict(prompts=PROMPTS[:2], max_new=MAX_NEW[:2], num_blocks=17)
    eng_off = _engine(cfg, params, **kw)
    eng_off.run()
    rec = obs_events.FlightRecorder()
    eng_on = _engine(cfg, params, recorder=rec, **kw)
    eng_on.run()
    assert eng_on.completions() == eng_off.completions(), \
        f"kv={kv} impl={impl}"
    assert {e.kind for e in rec.events()} >= {"req.done"}


def test_train_loop_bitwise_parity_and_spans():
    @jax.jit
    def step(state, batch):
        new = state - 0.01 * (2 * state + batch)
        return new, {"loss": jnp.sum(state ** 2)}

    def data():
        return (jnp.full((4,), float(s)) for s in range(10_000))

    hooks = lambda: [StopAtStepHook(50)]  # noqa: E731
    off = TrainLoop(step, jnp.ones((4,)), data(), hooks=hooks()).run()
    rec = obs_events.FlightRecorder(capacity=1 << 12)
    on = TrainLoop(step, jnp.ones((4,)), data(), hooks=hooks(),
                   recorder=rec).run()
    np.testing.assert_array_equal(np.asarray(off), np.asarray(on))
    begun = [e.payload for e in rec.events() if e.kind == "span.begin"]
    ended = [e.payload["name"] for e in rec.events()
             if e.kind == "span.end"]
    # one of each a step, and the iterator's last, empty, next() never
    # happens: StopAtStepHook stops the loop before it
    for name in ("loop.data_wait", "loop.dispatch", "loop.hooks"):
        assert [p["step"] for p in begun if p["name"] == name] == list(
            range(50)), name
        assert ended.count(name) == 50
    assert len(begun) == len(ended) == 150
    assert {p["track"] for p in begun} == {"loop"}


def test_metrics_hook_and_tb_roundtrip(tmp_path):
    from distributed_tensorflow_guide_tpu.utils.tb_writer import (
        SummaryWriter,
        read_scalars,
    )

    def step(state, batch):
        return state + batch, {"loss": jnp.asarray(state)}

    reg = obs_metrics.Registry()
    with SummaryWriter(tmp_path) as w:
        hook = MetricsHook(reg, every_steps=5, writer=w)
        TrainLoop(step, 0.0, (1.0 for _ in range(10_000)),
                  hooks=[StopAtStepHook(20), hook]).run()
    snap = reg.snapshot()
    assert snap["dtg_train_steps_total"] == 20
    assert snap["dtg_train_metric_loss"] == 19.0
    assert snap["dtg_train_dispatches_total"] == 20
    (event_file,) = tmp_path.glob("events.out.tfevents.*")
    rows = read_scalars(event_file)
    assert rows and rows[-1][1]["dtg_train_steps_total"] == 20.0
    assert any("dtg_train_metric_loss" in scalars
               for _, scalars in rows)


# ---- the program's spans on the profiler's clock ----------------------------

ENGINE_PHASES = ["engine.schedule", "engine.build", "engine.dispatch",
                 "engine.fetch", "engine.apply"]
PROGRAM_SPANS = ["engine.tick", *ENGINE_PHASES, "loop.data_wait",
                 "loop.dispatch", "loop.hooks", "prefetch.host_fetch",
                 "prefetch.put"]


@jax.jit
def _toy_step(state, batch):
    new = state - 0.01 * (2 * state + batch)
    return new, {"loss": jnp.sum(state ** 2)}


def _toy_loop():
    from distributed_tensorflow_guide_tpu.data.prefetch import (
        DevicePrefetchIterator,
    )

    feed = DevicePrefetchIterator(
        (np.full((4,), float(s), np.float32) for s in range(10_000)))
    return TrainLoop(_toy_step, jnp.ones((4,)), feed,
                     hooks=[StopAtStepHook(5)])


@pytest.fixture(scope="module")
def profiled(params, tmp_path_factory):
    """A tiny engine driven to its end and a 5-step ``TrainLoop`` once with
    no profiler session and once inside one; the session's ``dtg.`` host
    events as (name, start_ns, end_ns, stats), in order of start."""
    plain = _engine(CFG, params)
    _drive(plain)
    plain_state = np.asarray(_toy_loop().run())
    engine_programs = len(serve_engine._STEP_FNS)
    train_programs = _toy_step._cache_size()

    logdir = tmp_path_factory.mktemp("profile")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the program's own spans, not Python's
    jax.profiler.start_trace(str(logdir), profiler_options=options)
    try:
        eng = _engine(CFG, params)
        _drive(eng)
        state = np.asarray(_toy_loop().run())
    finally:
        jax.profiler.stop_trace()
    (path,) = logdir.glob("plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    spans = sorted(
        ((e.name[len("dtg."):], e.start_ns, e.start_ns + e.duration_ns,
          dict(e.stats))
         for plane in data.planes for line in plane.lines
         for e in line.events if e.name.startswith("dtg.")),
        key=lambda r: (r[1], -r[2]))
    return SimpleNamespace(
        spans=spans, same_tokens=eng.completions() == plain.completions(),
        same_state=np.array_equal(state, plain_state),
        ticks=eng._tick, launches=eng.steps["prefill"] + eng.steps["decode"],
        new_programs=(len(serve_engine._STEP_FNS) - engine_programs,
                      _toy_step._cache_size() - train_programs))


@pytest.mark.parametrize("name", PROGRAM_SPANS)
def test_span_lands_in_the_profilers_trace(profiled, name):
    found = [r for r in profiled.spans if r[0] == name]
    want = {"engine.tick": profiled.ticks, "engine.schedule": profiled.ticks,
            "loop.data_wait": 5, "loop.dispatch": 5, "loop.hooks": 5,
            # depth 2: two batches before the first step, one after each
            "prefetch.host_fetch": 7, "prefetch.put": 7}
    assert len(found) == want.get(name, profiled.launches)


def test_tick_phases_nest_in_their_tick_and_do_not_overlap(profiled):
    """One launch in flight: a tick schedules, builds and dispatches its
    own launch and then fetches and applies the launch BEFORE it, so its
    last two phases carry that launch's tick. The first tick after idle
    has nothing to settle, the last nothing to launch."""
    ticks = {r[3]["tick"]: r for r in profiled.spans if r[0] == "engine.tick"}
    assert sorted(ticks) == list(range(profiled.ticks))
    phases = [r for r in profiled.spans if r[0] in ENGINE_PHASES]
    launched, settled, unsettled = [], [], None
    for tick, (_, t_start, t_end, stats) in sorted(ticks.items()):
        inside = [r for r in phases if t_start <= r[1] and r[2] <= t_end]
        names = [r[0] for r in inside]
        assert names in (ENGINE_PHASES, ENGINE_PHASES[:3],
                         ENGINE_PHASES[:1] + ENGINE_PHASES[3:],
                         ENGINE_PHASES[:1]), (tick, names)
        for before, after in zip(inside, inside[1:]):
            assert before[2] <= after[1], (tick, before[0], after[0])
        assert stats == {"tick": tick, "gc_full": stats["gc_full"],
                         "compiles": stats["compiles"]}
        for r in inside:
            own = r[0] in ENGINE_PHASES[:3]
            assert r[3]["tick"] == (tick if own else unsettled), (tick, r[0])
        if "engine.fetch" in names:
            settled.append(unsettled)
        if "engine.dispatch" in names:
            (dispatch,) = (r for r in inside if r[0] == "engine.dispatch")
            # overlapped: the launch before was still unsettled
            assert dispatch[3]["overlapped"] == int(unsettled is not None)
            launched.append(tick)
            unsettled = tick
        elif "engine.fetch" in names:
            unsettled = None
    assert len(phases) == sum(
        t_start <= r[1] and r[2] <= t_end for r in phases
        for _, t_start, t_end, _ in ticks.values())  # none outside a tick
    # every launch is settled once, in order, by the tick after its own
    assert launched == settled and len(launched) == profiled.launches
    assert all(b == a + 1 for a, b in zip(launched, launched[1:]))


def test_the_recorder_sees_a_launch_settled_under_its_own_tick(params):
    """The same in the recorder: ``engine.fetch`` / ``engine.apply`` begin
    with the tick (and rids) of the launch they settle, ``engine.dispatch``
    says whether it overlapped (and a prefill launch its chunks and its
    width), and ``health()`` counts both."""
    rec = obs_events.FlightRecorder(capacity=1 << 14)
    eng = _engine(CFG, params, recorder=rec)
    _drive(eng)
    begun = [e.payload for e in rec.events() if e.kind == "span.begin"]
    by_name = {n: [p for p in begun if p["name"] == n]
               for n in ENGINE_PHASES + ["engine.tick"]}
    dispatched = by_name["engine.dispatch"]
    health = eng.health()
    assert health["launches"] == len(dispatched) == (
        eng.steps["prefill"] + eng.steps["decode"])
    assert [p["overlapped"] for p in dispatched] == [0] + [1] * (
        len(dispatched) - 1)
    assert health["overlapped_launches"] == len(dispatched) - 1
    for name in ("engine.fetch", "engine.apply"):
        got = [(p["tick"], p.get("rids")) for p in by_name[name]]
        assert got == [(p["tick"], p.get("rids")) for p in dispatched], name
    # a launch's recorder event keeps its own tick too
    launches = [e.payload["tick"] for e in rec.events()
                if e.kind in ("prefill.launch", "decode.launch")]
    assert launches == [p["tick"] for p in dispatched]
    firsts = {e.payload["rid"]: e.payload["tick"] for e in rec.events()
              if e.kind == "req.first_token"}
    last_chunk = {}
    prefills = [p for p in dispatched if p["program"] == "prefill_chunk_step"]
    for p in prefills:
        rids = [int(r) for r in str(p["rids"]).split()]
        assert p["chunks"] == len(rids) <= p["width"] <= 2  # two slots
        last_chunk.update(dict.fromkeys(rids, p["tick"]))
    assert firsts == last_chunk  # the tick that launched the last chunk
    assert (health["prefill_launches"], health["prefill_chunks"]) == (
        len(prefills), sum(p["chunks"] for p in prefills))


def test_span_attrs_read_back_from_the_events_stats(profiled):
    builds = [r[3] for r in profiled.spans if r[0] == "engine.build"]
    prefills = [b for b in builds if b["kind"] == "prefill"]
    decodes = [b for b in builds if b["kind"] == "decode"]
    assert len(prefills) + len(decodes) == len(builds)
    # the profiler reads a lone rid back as a number, several as "0 1"
    rids = [str(b["rids"]).split() for b in prefills]
    assert {int(r) for launch in rids for r in launch} == {0, 1, 2}
    assert [b["rows"] for b in prefills] == [len(launch) for launch in rids]
    assert decodes and all(
        1 <= b["rows"] <= 2 and "rids" not in b for b in decodes)
    widths = [(r[3]["chunks"], r[3]["width"]) for r in profiled.spans
              if r[0] == "engine.dispatch" and "chunks" in r[3]]
    assert len(widths) == len(prefills) and set(widths) <= {(1, 1), (2, 2)}
    programs = {r[3]["program"] for r in profiled.spans
                if r[0] == "engine.dispatch"}
    assert programs == {"prefill_chunk_step", "decode_step"}
    for name in ("loop.data_wait", "loop.dispatch", "loop.hooks"):
        assert [r[3]["step"] for r in profiled.spans
                if r[0] == name] == list(range(5)), name
    # a put lies inside the data wait that asked for it
    waits = [r for r in profiled.spans if r[0] == "loop.data_wait"]
    for r in (r for r in profiled.spans if r[0] == "prefetch.put"):
        assert any(w[1] <= r[1] and r[2] <= w[2] for w in waits)


def test_a_profiler_session_is_invisible_to_the_program(profiled):
    assert profiled.same_tokens and profiled.same_state
    assert profiled.new_programs == (0, 0)


@pytest.mark.parametrize("name", ["engine.tick", "loop.dispatch"])
def test_tick_and_dispatch_carry_the_stall_counters(profiled, name):
    """The watch's two counters as each span opened: whole numbers that
    never fall and never pass what the watch has counted since, so a
    reader tells "watched, nothing happened" (a 0 that stays) from a
    program that does not watch (no attribute)."""
    watch = obs_trace.watch_stalls()
    rows = [r[3] for r in profiled.spans if r[0] == name]
    assert rows
    for counter, now in (("gc_full", watch.gc_full),
                         ("compiles", watch.compiles)):
        seen = [s[counter] for s in rows]
        assert all(isinstance(v, int) and 0 <= v <= now for v in seen)
        assert seen == sorted(seen), counter


def test_watch_stalls_registers_once():
    """Process-wide: an engine, a loop and a second call share one watch
    and one entry in each of the three hooks."""
    from jax._src import monitoring  # the public module lists no getters

    watch = obs_trace.watch_stalls()
    TrainLoop(_toy_step, jnp.ones((4,)), iter(()))
    assert obs_trace.watch_stalls() is watch
    assert gc.callbacks.count(watch.on_gc) == 1
    assert monitoring.get_scalar_listeners().count(
        watch.on_compile_start) == 1
    assert monitoring.get_event_duration_listeners().count(
        watch.on_compile_end) == 1


def test_stall_callbacks_never_raise_and_count_what_failed(monkeypatch):
    """A fresh watch (registered nowhere) driven by hand: a young
    collection is ignored, a full one counted, a failure inside a callback
    counted in ``faults`` and not raised, a phase's end with nothing open
    ignored, and a stale inner phase closed with the one around it."""
    watch = obs_trace.StallWatch()
    watch.on_gc("start", {"generation": 0})
    watch.on_gc("stop", {"generation": 1})
    assert (watch.gc_full, watch._gc_open) == (0, None)
    watch.on_gc("start", {"generation": 2})
    assert watch.gc_full == 1 and watch._gc_open is not None
    watch.on_gc("stop", {"generation": 2})
    assert watch._gc_open is None
    watch.on_gc("stop", {"generation": 2})  # nothing open: ignored
    lower, trace = "/jax/core/compile/jaxpr_to_mlir_module_duration", \
        "/jax/core/compile/jaxpr_trace_duration"
    watch.on_compile_end(lower, 0.1, fun_name="f")  # nothing open
    watch.on_compile_start("/jax/other", 1.0)  # not a compile phase
    watch.on_compile_start(trace, 1.0, fun_name="f, g")
    watch.on_compile_start(lower, 1.0, fun_name="f")
    assert [p for p, _ in watch._compiling()] == ["trace", "lower"]
    watch.on_compile_end(trace, 0.1, fun_name="f")  # closes both
    assert (watch._compiling(), watch.faults) == ([], 0)

    def broken(*a, **k):
        raise RuntimeError("annotation")

    # the stall spans go to the profiler alone, whatever recorder is set
    rec = obs_events.FlightRecorder()
    prev = obs_events.install(rec)
    try:
        watch.on_compile_start(trace, 1.0, fun_name="f")
        watch.on_compile_end(trace, 0.1, fun_name="f")
        watch.on_gc("start", {"generation": 2})
        watch.on_gc("stop", {"generation": 2})
    finally:
        obs_events.install(prev)
    assert (rec.events(), watch.gc_full) == ([], 2)

    monkeypatch.setattr(obs_trace, "_annotation", broken)
    monkeypatch.setattr(obs_trace, "_profiling", lambda: True)
    watch.on_gc("start", {"generation": 2})
    watch.on_compile_start("/jax/core/compile/backend_compile_duration", 1.0)
    assert (watch.gc_full, watch.compiles, watch.faults) == (3, 1, 2)


def test_span_feeds_recorder_and_profiler_alike():
    """Recorder on: the pair of events with the attrs in ``span.begin``;
    recorder off: nothing, and the block still runs; an exception leaves
    through both sinks."""
    rec = obs_events.FlightRecorder()
    with pytest.raises(KeyError):
        with obs_trace.span(rec, "engine.build", cat="serve", tick=3,
                            kind="decode"):
            raise KeyError("x")
    begin, end = rec.events()
    assert (begin.kind, end.kind) == ("span.begin", "span.end")
    assert begin.cat == end.cat == "serve"
    assert begin.actor == end.actor == "engine"
    assert begin.payload == {"name": "engine.build", "track": "engine",
                             "tick": 3, "kind": "decode"}
    assert end.payload == {"name": "engine.build", "track": "engine"}
    assert begin.t is None and end.mono >= begin.mono
    ran = []
    quiet = obs_trace.span(obs_events.NULL_RECORDER, "loop.hooks", step=1)
    with quiet:
        ran.append(1)
    assert ran == [1] and len(rec.events()) == 2
    assert quiet._profiled is None  # no session: no annotation is made


# ---- black boxes: watchdog trip + seeded chaos storm ------------------------


def test_watchdog_trip_dumps_flight_recorder(tmp_path):
    from distributed_tensorflow_guide_tpu.utils.watchdog import Watchdog

    diag = tmp_path / "stacks.txt"
    rec = obs_events.FlightRecorder()
    rec.emit("step.before", payload={"step": 7})
    with Watchdog(action=lambda info: None, diag_path=diag,
                  poll_s=0.005, recorder=rec) as wd:
        wd.arm("stuck section", 0.02)
        deadline = time.time() + 5
        while wd.tripped is None and time.time() < deadline:
            time.sleep(0.01)
    bb = tmp_path / "stacks.txt.flightrec.json"
    assert bb.exists()
    dumped = json.loads(bb.read_text())
    trip = dumped["events"][-1]
    assert trip["kind"] == "watchdog.trip"
    assert trip["payload"]["tag"] == "stuck section"
    assert trip["payload"]["deadline_s"] == 0.02
    assert trip["payload"]["waited_s"] >= 0.02
    # the context that led up to the trip is in the same tail
    assert dumped["events"][0]["kind"] == "step.before"


def test_seeded_chaos_storm_exactly_reproducible(params):
    kinds = ("serve_step_exception", "client_abandon", "pool_pressure")

    def run_once():
        sched = FaultSchedule.random_serve(
            11, max_position=12, kinds=kinds, n_faults=3)
        rec = obs_events.FlightRecorder()
        eng = _engine(CFG, params, recorder=rec, chaos=sched,
                      retry_base_delay_s=0.001)
        eng.run()
        return sched, rec, eng.completions()

    s1, r1, c1 = run_once()
    s2, r2, c2 = run_once()
    assert c1 == c2  # chaos absorbed identically
    assert obs_events.signature(r1.events()) == \
        obs_events.signature(r2.events())
    recorded = {(e.payload["kind"], e.payload["position"])
                for e in r1.events() if e.kind == "chaos.fault"}
    assert recorded == {(f.kind, f.position) for f in s1.fired}
    assert len(s1.fired) == len(s2.fired)


def test_ttft_breakdown_from_driven_engine(params):
    rec = obs_events.FlightRecorder()
    eng = _engine(CFG, params, recorder=rec)
    _drive(eng)
    trace = obs_trace.to_chrome_trace(rec.events())
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(xs) > 0  # finite virtual clock -> real complete spans
    bk = obs_trace.ttft_breakdown(rec.events())
    assert sorted(bk) == [0, 1, 2]
    for rid, parts in bk.items():
        assert set(parts) == {"queue_wait_s", "prefill_s",
                              "first_decode_s"}
        assert parts["prefill_s"] > 0
        assert all(v >= 0 for v in parts.values())
    # the absorber must accept a REAL health() dict, not a hand-built one
    reg = obs_metrics.Registry()
    obs_metrics.absorb_engine(reg, eng.health())
    snap = reg.snapshot()
    assert snap["dtg_serve_completed_total"] == 3
    assert snap["dtg_serve_ticks_total"] > 0
    assert snap["dtg_serve_resident"] == 0


def test_spill_tier_absorbers_from_driven_engine(params):
    """The host-tier gauges flow from REAL shapes — a driven hierarchy-on
    engine's health() and its BlockStore's stats(), not hand-built dicts
    — so the absorbers break loudly if either producer drifts."""
    eng = _engine(CFG, params, host_blocks=8, prefix_cache=True)
    _drive(eng)
    sd = eng.sched
    freed = sd.prefix.demote_many(sd.pool, sd._cache_demote_batch)
    assert freed  # the driven prompts cached demotable full blocks
    reg = obs_metrics.Registry()
    obs_metrics.absorb_engine(reg, eng.health())
    obs_metrics.absorb_spill_store(reg, eng.store.stats())
    obs_metrics.absorb_prefix(reg, sd.prefix.stats())
    snap = reg.snapshot()
    assert snap["dtg_serve_spill_host_blocks"] == len(freed)
    assert snap["dtg_serve_spill_out_blocks_total"] == len(freed)
    assert snap["dtg_serve_spill_d2h_bytes_total"] > 0
    assert snap["dtg_serve_spill_host_bytes"] == eng.store.bytes_stored()
    assert snap["dtg_serve_spill_store_live"] == len(freed)
    assert snap["dtg_serve_spill_store_holds"] == len(freed)
    assert snap["dtg_serve_prefix_spilled"] == len(freed)
    eng.close()
    sd.check_leaks()


# ---- checkpoint / elastic events --------------------------------------------


def test_checkpointer_save_restore_events(tmp_path):
    from distributed_tensorflow_guide_tpu.train.checkpoint import (
        Checkpointer,
    )

    state = {"w": jnp.arange(4, dtype=jnp.float32)}
    rec = obs_events.FlightRecorder()
    ckpt = Checkpointer(tmp_path / "ckpt", recorder=rec)
    try:
        ckpt.save(3, state, force=True)
        ckpt.wait()
        restored = ckpt.restore_latest_valid(state)
        assert restored is not None and restored[1] == 3
    finally:
        ckpt.close()
    kinds = [e.kind for e in rec.events()]
    assert kinds == ["ckpt.save", "ckpt.restore"]
    save = rec.events()[0].payload
    assert save == {"step": 3, "async": False, "force": True}
    assert rec.events()[1].payload == {"step": 3, "skipped": []}

    rec2 = obs_events.FlightRecorder()
    empty = Checkpointer(tmp_path / "none", recorder=rec2)
    try:
        assert empty.restore_latest_valid(state) is None
    finally:
        empty.close()
    assert [e.kind for e in rec2.events()] == ["ckpt.restore_miss"]


def test_elastic_recovery_events_and_give_up_black_box(tmp_path):
    from distributed_tensorflow_guide_tpu.train.checkpoint import (
        Checkpointer,
    )
    from distributed_tensorflow_guide_tpu.train.elastic import (
        TooManyRestarts,
        run_with_recovery,
    )

    def step_fn(state, batch):
        return {"params": state["params"] - 0.01 * batch}, {}

    def make_data(start):
        return (jnp.full((4,), float(s)) for s in range(start, 10_000))

    crashed = []

    def crashing(state, batch):
        if int(batch[0]) == 7 and not crashed:
            crashed.append(True)
            raise RuntimeError("injected crash")
        return step_fn(state, batch)

    rec = obs_events.FlightRecorder()
    prev = obs_events.install(rec)
    ckpt = Checkpointer(tmp_path / "el", max_to_keep=2)
    try:
        run_with_recovery(crashing, {"params": jnp.ones((4,))},
                          make_data, ckpt,
                          hooks=[StopAtStepHook(10)],
                          checkpoint_every=5, max_restarts=3)
    finally:
        obs_events.install(prev)
        ckpt.close()
    el = [e for e in rec.events() if e.kind.startswith("elastic.")]
    assert [e.kind for e in el] == \
        ["elastic.restore", "elastic.restart", "elastic.restore"]
    assert el[0].payload == {"start": 0, "restarts": 0, "fresh": True}
    assert el[1].payload == {"step": 7, "restarts": 1,
                             "error": "RuntimeError"}
    assert el[2].payload == {"start": 5, "restarts": 1, "fresh": False}
    # the restore ladder's choices landed too (save at 5, 10 + end save)
    assert "ckpt.restore" in {e.kind for e in rec.events()}

    # restart budget exhausted -> elastic.give_up crash-dumps the tail
    bb = tmp_path / "giveup.json"
    rec2 = obs_events.FlightRecorder(crash_dump_path=str(bb))
    prev = obs_events.install(rec2)
    ckpt2 = Checkpointer(tmp_path / "fail", max_to_keep=1)
    try:
        with pytest.raises(TooManyRestarts):
            run_with_recovery(
                lambda s, b: (_ for _ in ()).throw(RuntimeError("perm")),
                {"params": jnp.ones((4,))}, make_data, ckpt2,
                hooks=[StopAtStepHook(10)], checkpoint_every=5,
                max_restarts=1)
    finally:
        obs_events.install(prev)
        ckpt2.close()
    dumped = json.loads(bb.read_text())
    last = dumped["events"][-1]
    assert last["kind"] == "elastic.give_up"
    # the counter has moved past the budget when the supervisor quits
    assert last["payload"]["restarts"] == 2
    assert last["payload"]["error"] == "RuntimeError"


def test_anomaly_trip_events():
    from distributed_tensorflow_guide_tpu.train.anomaly import (
        AnomalyDetected,
        AnomalySentinelHook,
    )

    rec = obs_events.FlightRecorder()
    data = iter([jnp.ones((4,)), jnp.full((4,), jnp.nan)])

    def step(state, batch):
        return state, {"loss": jnp.sum(batch)}

    loop = TrainLoop(step, {"w": jnp.zeros(2)}, data,
                     hooks=[AnomalySentinelHook(budget=3, recorder=rec)])
    with pytest.raises(AnomalyDetected):
        loop.run()
    trips = [e for e in rec.events() if e.kind == "anomaly.trip"]
    assert len(trips) == 1
    assert trips[0].payload["step"] == 1
    assert trips[0].payload["trips"] == 1
    assert trips[0].payload["budget"] == 3


# ---- cost reconciliation: pinned closed form --------------------------------


def test_reconcile_closed_form():
    roof = obs_recon.Roofline(peak_flops_s=100.0, peak_hbm_bytes_s=50.0,
                              peak_ici_bytes_s=10.0)
    cost = {"flops": 200.0, "hbm_bytes_read": 70.0,
            "hbm_bytes_written": 50.0, "collective_bytes": {"data": 5.0}}
    r = obs_recon.reconcile(cost, 4.0, roof)
    assert r["achieved_gflops_s"] == pytest.approx(200 / 4 / 1e9)
    assert r["achieved_hbm_gb_s"] == pytest.approx(120 / 4 / 1e9)
    assert r["achieved_ici_gb_s"] == pytest.approx(5 / 4 / 1e9)
    assert r["flops_frac"] == pytest.approx(0.5)      # 200/4/100
    assert r["hbm_frac"] == pytest.approx(0.6)        # 120/4/50
    assert r["ici_frac"] == pytest.approx(0.125)      # 5/4/10
    # model time = max(200/100, 120/50, 5/10) = 2.4s -> memory-bound
    assert r["model_time_s"] == pytest.approx(2.4)
    assert r["efficiency"] == pytest.approx(0.6)
    assert r["bound"] == "memory"
    # no ICI peak: comm drops out of the roofline entirely
    r2 = obs_recon.reconcile(cost, 4.0, obs_recon.Roofline(100.0, 50.0))
    assert r2["ici_frac"] is None and r2["bound"] == "memory"
    with pytest.raises(ValueError, match="measured_s"):
        obs_recon.reconcile(cost, 0.0, roof)


def test_roofline_from_env(monkeypatch):
    monkeypatch.setenv("DTG_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("DTG_PEAK_HBM_BPS", "1e11")
    monkeypatch.setenv("DTG_PEAK_ICI_BPS", "1e10")
    roof = obs_recon.Roofline.from_env()
    assert roof.peak_flops_s == 1e12
    assert roof.peak_hbm_bytes_s == 1e11
    assert roof.peak_ici_bytes_s == 1e10
    monkeypatch.delenv("DTG_PEAK_ICI_BPS")
    assert obs_recon.Roofline.from_env().peak_ici_bytes_s is None

# ---- fleet reliability plane (PR 20) ----------------------------------------


def test_absorb_fleet_shapes_from_real_health(params):
    """The ``dtg_fleet_*`` reliability series, shape-tested against a
    REAL ``FleetScheduler.health()`` driven through a crash + stall
    storm — not a hand-built dict — so the absorber and the health
    schema cannot drift apart.  The storm's recovery lifecycle must
    also land in the flight recorder as ``fleet.*`` events."""
    from distributed_tensorflow_guide_tpu.serve import FleetScheduler
    from distributed_tensorflow_guide_tpu.testing.chaos import Fault

    rec = obs_events.FlightRecorder()
    fc = FaultSchedule([Fault("replica_crash", 3, 0.0),
                        Fault("migration_torn", 3),
                        Fault("replica_stall", 6, 1.0)])
    fl = FleetScheduler(CFG, params, replicas=2, slots=2, num_blocks=33,
                        block_size=8, prefill_chunk=8, temperature=0.8,
                        top_k=10, fleet_chaos=fc, recorder=rec)
    for i, (p, mn) in enumerate(zip(PROMPTS, MAX_NEW)):
        fl.submit(Request(rid=i, prompt=p, max_new_tokens=mn,
                          rng=jax.random.PRNGKey(100 + i), tenant=i % 2))
    fl.run()
    h = fl.health()
    kinds = {str(e.kind) for e in rec.events()}
    assert {"fleet.replica_crash", "fleet.replica_stall",
            "fleet.migration_torn", "fleet.migrate_dup",
            "fleet.replica_probe",
            "fleet.replica_recovered"} <= kinds

    reg = obs_metrics.Registry()
    obs_metrics.absorb_fleet(reg, h)
    snap = reg.snapshot()
    assert snap["dtg_fleet_replica_crashes_total"] == 1
    assert snap["dtg_fleet_replica_stalls_total"] == 1
    assert snap["dtg_fleet_migration_dups_dropped_total"] == 1
    assert snap["dtg_fleet_breaker_probes_total"] >= 1
    assert snap["dtg_fleet_breaker_recoveries_total"] >= 1
    assert snap["dtg_fleet_completed_total"] == 3
    assert snap["dtg_fleet_stalled_replicas"] == 0
    assert snap["dtg_fleet_draining_replicas"] == 0
    assert snap["dtg_fleet_autoscale_target"] == 2
    # per-replica reliability gauges under {replica, role} labels
    assert snap['dtg_fleet_replica_breaker_open'
                '{replica="0",role="colocated"}'] == 0.0
    assert snap['dtg_fleet_replica_breaker_open'
                '{replica="1",role="colocated"}'] == 0.0
    assert 'dtg_fleet_replica_launch_failures_total' \
        '{replica="0",role="colocated"}' in snap
    # the engine-level attempt counter rolls up separately from the
    # fleet-level step-boundary fault counter
    assert "dtg_fleet_launch_failures_total" in snap
    assert "dtg_fleet_replica_faults_total" in snap
    fl.check_leaks()
    fl.close()
