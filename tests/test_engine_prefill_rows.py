"""A prefill launch carries every waiting prompt's next chunk, a row each,
up to the widest of ``engine.PREFILL_WIDTHS`` (on the CPU one prompt a
launch unless a test says otherwise: every test here does). Nothing that is
computed may change by that, so every case here holds an engine as the chip
runs it to a twin held to width 1 (the plan of before), for each family
of step programs ``build_step_fns`` makes: GPT-2's block, multi-LoRA,
``MoEMLP``, a patterned convolution + attention + routed model (state
leaves), a Mamba-2 + routed one (a share of the experts), and a Mamba-1 +
window + cross-attention one (rings).
"""

from __future__ import annotations

import dataclasses
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
from distributed_tensorflow_guide_tpu.serve import engine as E
from distributed_tensorflow_guide_tpu.serve.engine import Request, ServeEngine
from tests import test_nemotron_h, test_patterned, test_phi4flash

GPT2 = TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                         d_model=16, d_ff=32, max_len=64, causal=True,
                         dtype=jnp.float32)
#: prompts of one to four chunks of 8 (of 4 for the window model, whose
#: sequences are as long): three or more wait together at the start, one or
#: two later, so a run meets every width and a padded row
LENGTHS = [13, 21, 9, 5, 17, 30, 8, 3, 11]
MAX_NEW = 7


def _gpt2(**kw):
    cfg = dataclasses.replace(GPT2, **kw)
    tree = Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    return cfg, tree["params"], tree.get("adapters")


def _lora():
    cfg, tree, bank = _gpt2(lora_rank=2, lora_adapters=2)
    # ids 1 and 2 differ from the base model and from each other
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 64))
    bank = jax.tree.map(
        lambda x: 0.3 * jax.random.normal(next(keys), x.shape, x.dtype), bank)
    return cfg, tree, bank


def _widened(module, weights):
    """A test module's tiny configuration with its seeded tree as float32."""
    return module.config(), jax.tree.map(
        lambda x: x.astype(jnp.float32),
        weights.flax_tree(module.SEED, module.Z)), None


FAMILIES = {
    "gpt2": _gpt2,
    "lora": _lora,
    "moe_mlp": lambda: _gpt2(moe_experts=4, moe_capacity=2),
    "conv_attention_routed": lambda: _widened(
        test_patterned, test_patterned.weights_lfm2),
    "mamba2_routed": lambda: _widened(
        test_nemotron_h, test_nemotron_h.weights_nemotron),
    "mamba1_window_cross": lambda: _widened(
        test_phi4flash, test_phi4flash.weights_phi4flash)}


@pytest.fixture(autouse=True)
def as_wide_as_on_the_chip(monkeypatch):
    monkeypatch.setattr(E, "CPU_PREFILL_WIDTHS", E.PREFILL_WIDTHS)


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def family(request):
    cfg, tree, bank = FAMILIES[request.param]()
    window = cfg.window is not None
    chunk = 4 if window else 8
    geometry = dict(slots=5, num_blocks=81 if window else 41,
                    block_size=chunk, prefill_chunk=chunk)
    rng = np.random.default_rng(36)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in ([n // 2 + 1 for n in LENGTHS] if window
                         else LENGTHS)]
    return SimpleNamespace(name=request.param, cfg=cfg, tree=tree,
                           bank=bank, prompts=prompts, geometry=geometry,
                           chunk=chunk, runs={})


def engine(family, *, submit=True, sampled=False, **kw) -> ServeEngine:
    if family.bank is not None:
        kw["adapters"] = family.bank
    eng = ServeEngine(family.cfg, family.tree,
                      temperature=0.8 if sampled else 0.0,
                      top_k=10 if sampled else None,
                      **{**family.geometry, **kw})
    if submit:
        for i, p in enumerate(family.prompts):
            eng.submit(request(family, i, p))
    return eng


def request(family, rid, prompt, max_new=MAX_NEW) -> Request:
    return Request(rid=rid, prompt=prompt, max_new_tokens=max_new,
                   rng=np.asarray(jax.random.PRNGKey(100 + rid)),
                   adapter=rid % 3 if family.bank is not None else 0)


def drain(eng) -> list:
    """Step ``eng`` to its end on a clock of a second a tick; every call's
    (events, kind)."""
    out, now = [], 0.0
    while eng.sched.has_queued or eng.sched.has_resident:
        out.append(eng.step(now))
        now += 1.0
        assert len(out) < 2000
    out.append((eng.settle(), "settled"))
    return out


def held_to_width_1(monkeypatch, family, **kw) -> ServeEngine:
    with monkeypatch.context() as m:
        m.setattr(E, "CPU_PREFILL_WIDTHS", (1,))
        eng = engine(family, **kw)
    assert eng._widths == (1,)
    return eng


def a_run(family, monkeypatch, sampled: bool):
    """The family's requests served by the engine as it comes (a recorder
    on it) and by one held to width 1: once a module."""
    if sampled not in family.runs:
        rec = obs_events.FlightRecorder(capacity=1 << 15)
        wide = engine(family, sampled=sampled, recorder=rec)
        narrow = held_to_width_1(monkeypatch, family, sampled=sampled)
        drain(wide), drain(narrow)
        family.runs[sampled] = SimpleNamespace(wide=wide, narrow=narrow,
                                               rec=rec)
    return family.runs[sampled]


def launches_of(rec) -> list[dict]:
    return [e.payload for e in rec.events() if e.kind == "prefill.launch"]


# ---- the same tokens --------------------------------------------------------


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_every_request_gets_the_tokens_of_an_engine_held_to_width_1(
        family, monkeypatch, sampled):
    run = a_run(family, monkeypatch, sampled)
    got, want = run.wide.completions(), run.narrow.completions()
    assert sorted(got) == list(range(len(family.prompts)))
    assert got == want
    assert all(len(toks) == MAX_NEW for toks in got.values())
    # and it did take several prompts a launch, at every width, padded too
    launches = launches_of(run.rec)
    assert {p["width"] for p in launches} == {1, 2, 4}
    if not run.wide.fns.moe:  # (its stalled decode rows shift who waits)
        assert any(len(p["slots"]) == 3 for p in launches)
    assert all(len(p["slots"]) <= p["width"] for p in launches)
    for eng in (run.wide, run.narrow):
        eng.sched.check_leaks()
        assert eng.live_blocks() == 0


def test_health_counts_prefill_launches_and_their_chunks(family,
                                                         monkeypatch):
    run = a_run(family, monkeypatch, False)
    wide, narrow = run.wide.health(), run.narrow.health()
    chunks = sum(-(-len(p) // family.chunk) for p in family.prompts)
    assert wide["prefill_chunks"] == narrow["prefill_chunks"] == chunks
    assert narrow["prefill_launches"] == chunks  # one prompt a launch
    assert wide["prefill_launches"] == run.wide.steps["prefill"] == len(
        launches_of(run.rec)) < chunks
    assert chunks == sum(len(p["slots"]) for p in launches_of(run.rec))
    assert wide["launches"] == (wide["prefill_launches"]
                                + run.wide.steps["decode"])
    # a routed model's census sums over the same assignments either way
    assert wide["routed"] == narrow["routed"]


def test_several_prompts_finish_in_one_launch(family, monkeypatch):
    """Four prompts of one chunk each, all waiting: one launch of four
    rows finishes them all, and the call after it hands out four first
    tokens, oldest admission first, each what the request gets alone."""
    prompts = [p[:family.chunk - i] for i, p in enumerate(family.prompts[:4])]
    eng = engine(family, submit=False)
    alone = held_to_width_1(monkeypatch, family, submit=False)
    for i, p in enumerate(prompts):
        eng.submit(request(family, i, p, max_new=3))
        alone.submit(request(family, i, p, max_new=3))
    events, kind = eng.step(0.0)
    assert kind == "prefill" and eng.health()["prefill_chunks"] == 4
    events = events + eng.step(1.0)[0] + eng.settle()
    firsts = [e for e in events if e.first]
    assert [e.rid for e in firsts] == [0, 1, 2, 3]
    assert all(s is not None and s.phase == "decode"
               for s in eng.sched.slots[:4])
    drain(eng), drain(alone)
    assert eng.completions() == alone.completions()
    assert [e.token for e in firsts] == [
        alone.completions()[i][0] for i in range(4)]
    eng.sched.check_leaks()


# ---- a padding row ----------------------------------------------------------


def test_a_padding_row_leaves_state_pool_and_census_alone(family):
    """The program of two rows over one real chunk and a padding row gives
    the real row's token, pool blocks, state row and census as the program
    of one row does, and hands the padding row's state row (another
    slot's, and not empty) back to the last bit."""
    eng = engine(family, submit=False)
    fns, slots = eng.fns, family.geometry["slots"]
    eng.submit(request(family, 0, family.prompts[1]))
    eng.sched.admit(0.0)
    (i,) = [j for j, s in enumerate(eng.sched.slots) if s is not None]

    def marked(tree):  # nothing starts from zeros: a copy-back would show
        return jax.tree.map(lambda x: x + jnp.asarray(0.25, x.dtype), tree)

    outs = []
    for width in (1, 2):
        args = list(eng._prefill_operands([i], width))
        args[1] = marked(eng.pool)
        if fns.patterned:
            args[2] = marked(eng.state)
        outs.append(jax.device_get(fns.prefill(*args)))
    one, two = outs
    assert int(one[0][0]) == int(two[0][0])  # the real row's sample
    # a product of 16 rows may round as one of 8 does not: the real row's
    # numbers to rounding, everything it does not own to the last bit
    near = dict(rtol=1e-5, atol=1e-5)
    trash = eng.sched.pool.trash_block
    for a, b in zip(jax.tree.leaves(one[1]), jax.tree.leaves(two[1])):
        keep = np.arange(a.shape[0]) != trash  # the padding row's writes
        np.testing.assert_allclose(a[keep], b[keep], **near)
    if fns.patterned:
        spare = int(eng._prefill_operands([i], 2)[-1][1])
        assert spare != i
        before = jax.device_get(marked(eng.state))
        for (path, a), b, was in zip(
                jax.tree_util.tree_leaves_with_path(one[2]),
                jax.tree.leaves(two[2]), jax.tree.leaves(before)):
            ring = path[-1].key in E.WINDOW_LEAVES
            rows = (a.shape[0] - 1) // slots if ring else 1  # its blocks
            own = slice(i * rows, (i + 1) * rows)
            np.testing.assert_allclose(a[own], b[own], **near)
            assert np.any(b[own] != was[own])
            others = np.ones((a.shape[0],), bool)
            others[own] = False
            if ring:
                others[-1] = False  # the rings' own trash block
            np.testing.assert_array_equal(b[others], was[others])
            np.testing.assert_array_equal(a[others], was[others])
        np.testing.assert_array_equal(one[3], two[3])  # the routed census
    elif fns.moe:
        np.testing.assert_array_equal(one[2], two[2])  # expert load
        assert not two[3].any()  # dropless: nothing overflowed


# ---- preempt, cancel, snapshot with several rows in flight -------------------


def _in_flight(family, **kw):
    """An engine whose launch in flight carries several prompts' chunks."""
    eng = engine(family, **kw)
    eng.step(0.0)
    assert eng.prefill_chunks == 4 and eng.steps["prefill"] == 1
    if not eng.fns.moe:  # (a ``MoEMLP`` engine settles at once)
        assert eng._inflight is not None and len(eng._inflight.arg) == 4
    return eng


def test_a_request_cancelled_between_launches_is_not_in_the_next_one(
        family, monkeypatch):
    want = a_run(family, monkeypatch, False).narrow.completions()
    eng = _in_flight(family)
    rid = eng.sched.slots[1].rid  # mid-prefill, in the launch in flight
    assert len(family.prompts[rid]) > family.chunk and eng.cancel(rid)
    calls = [eng.step(1.0)] + drain(eng)
    ended = [e for events, _ in calls for e in events if e.status != "ok"]
    assert [(e.rid, e.status) for e in ended] == [(rid, "cancelled")]
    got = eng.completions()
    assert got.pop(rid) == [] and got == {
        r: toks for r, toks in want.items() if r != rid}
    eng.sched.check_leaks()
    assert eng.live_blocks() == 0


def test_a_slot_preempted_with_its_chunk_in_flight_resumes_to_the_same(
        family, monkeypatch):
    want = a_run(family, monkeypatch, False).narrow.completions()
    eng = _in_flight(family)
    eng.sched._preempt(2)  # settles first: the continuation reads values
    assert eng._inflight is None and eng.health()["preemptions"] == 1
    drain(eng)
    assert eng.completions() == want
    eng.sched.check_leaks()


def test_a_snapshot_taken_with_several_rows_in_flight_restores_to_the_same(
        family, monkeypatch, tmp_path):
    want = a_run(family, monkeypatch, False).narrow.completions()
    eng = _in_flight(family, snapshot_dir=tmp_path)
    eng.step(1.0), eng.step(2.0)
    label = eng.save_snapshot()
    chunks = eng.health()["prefill_chunks"]
    eng.close()
    fresh = engine(family, submit=False, snapshot_dir=tmp_path)
    assert fresh.restore_latest_snapshot() == label
    assert fresh.health()["prefill_chunks"] == chunks  # rides the snapshot
    drain(fresh)
    assert fresh.completions() == want
    fresh.close()
    fresh.sched.check_leaks()


# ---- nothing compiles once the engine serves --------------------------------


def test_nothing_compiles_after_the_first_request_is_served(family):
    """One request alone first, as the benchmark's warm request: it meets
    width 1 only, and every width is compiled by then. The run after it
    meets them all and compiles nothing."""
    # a geometry of this test's own: programs no other test has compiled
    eng = engine(family, submit=False,
                 num_blocks=family.geometry["num_blocks"] + 1)
    assert not eng.fns.compiled_widths
    eng.submit(request(family, 99, family.prompts[0]))
    drain(eng)
    jitted = (eng.fns.prefill, eng.fns.decode, E._place_tokens,
              E._merge_tokens)
    assert eng.fns.prefill._cache_size() == len(E.PREFILL_WIDTHS) == 3
    compiled = [f._cache_size() for f in jitted]
    seen = []
    plan = eng.sched.plan
    eng.sched.plan = lambda: seen.append(plan()) or seen[-1]
    for i, p in enumerate(family.prompts):
        eng.submit(request(family, i, p))
    drain(eng)
    rows = {min(len(arg), 4) for kind, arg in seen if kind == "prefill"}
    assert rows >= {1, 2} and max(rows) >= 3  # the programs of 1, 2 and 4
    assert [f._cache_size() for f in jitted] == compiled
    # a second engine of the same shapes compiles, and runs, nothing first
    twin = engine(family, submit=False,
                  num_blocks=family.geometry["num_blocks"] + 1)
    assert twin.fns is eng.fns
    twin.submit(request(family, 0, family.prompts[0]))
    drain(twin)
    assert [f._cache_size() for f in jitted] == compiled
    eng.sched.check_leaks()


def test_an_engine_of_few_slots_launches_no_wider_than_its_slots(family):
    """A padding row's state row is a slot's, so the widths stop at the
    slots: two slots, the programs of one and two rows."""
    eng, narrow = engine(family, slots=2), engine(family, slots=1)
    assert (eng._widths, narrow._widths) == ((1, 2), (1,))
    drain(eng), drain(narrow)
    assert eng.completions() == narrow.completions()
    assert eng.health()["prefill_launches"] < eng.health()["prefill_chunks"]
    eng.sched.check_leaks()


def test_on_the_cpu_a_launch_carries_one_prompt_unless_a_test_says_so(
        monkeypatch):
    """XLA's CPU products round by the extent of their row axis, and the
    CPU's tests pin tokens to references computed for a request alone."""
    monkeypatch.undo()  # this module's own override
    assert E.CPU_PREFILL_WIDTHS == (1,) and jax.default_backend() == "cpu"
    cfg, tree, _ = _gpt2()
    eng = ServeEngine(cfg, tree, slots=6, num_blocks=49, block_size=8,
                      prefill_chunk=8)
    assert eng._widths == (1,)


def test_the_plan_alternates_and_hands_over_every_waiting_prompt():
    """One prefill launch between two decode launches however many prompts
    wait, and the plan names them all, oldest admission first; the engine
    takes the first four."""
    cfg, tree, _ = _gpt2()
    eng = ServeEngine(cfg, tree, slots=6, num_blocks=49, block_size=8,
                      prefill_chunk=8)
    rng = np.random.default_rng(3)

    def submit(rid, n):
        eng.submit(Request(rid=rid, max_new_tokens=30,
                           prompt=rng.integers(0, 64, n).astype(np.int32),
                           rng=np.zeros((2,), np.uint32)))

    submit(0, 5)
    eng.step(0.0), eng.step(0.0)  # rid 0 decodes
    for rid in range(1, 6):
        submit(rid, 24)  # three chunks each
    eng.sched.admit(0.0)
    seen = []
    plan = eng.sched.plan
    eng.sched.plan = lambda: seen.append(plan()) or seen[-1]
    for _ in range(8):
        eng.step(0.0)
    kinds = [kind for kind, _ in seen]
    assert kinds == ["prefill", "decode"] * 4 or kinds == [
        "decode", "prefill"] * 4
    waiting = [arg for kind, arg in seen if kind == "prefill"]
    by_age = [[eng.sched.slots[i].rid for i in arg] for arg in waiting]
    # five wait, the launch takes four: the fifth's first chunk rides with
    # the others' second, and so on until the oldest four have finished
    assert by_age == [[1, 2, 3, 4, 5]] * 3 + [[5]]
    eng.run()
    health = eng.health()  # rid 0's chunk, 3 launches of 4 rows, 3 of rid 5
    assert (health["prefill_launches"], health["prefill_chunks"]) == (7, 16)
    eng.sched.check_leaks()


# ---- the executables kept beside the compile cache ---------------------------


def test_a_later_process_loads_its_programs_and_traces_nothing(tmp_path,
                                                               monkeypatch):
    """With a compile cache configured the engine keeps its four
    executables (decode, prefill at three widths) there, and an engine that
    finds them (a later process: the memo of traced programs is empty)
    serves the same tokens having traced nothing; a file cut short is
    compiled again; another sampling temperature is another program."""
    from distributed_tensorflow_guide_tpu.serve import program_cache

    cfg, tree, _ = _gpt2()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 64, n).astype(np.int32) for n in LENGTHS]

    def serve(**kw):
        monkeypatch.setattr(E, "_STEP_FNS", {})  # as a new process has it
        eng = ServeEngine(cfg, tree, slots=5, num_blocks=43, block_size=8,
                          prefill_chunk=8, **kw)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=MAX_NEW,
                               rng=np.asarray([0, i], np.uint32)))
        drain(eng)
        eng.sched.check_leaks()
        return eng

    assert program_cache.directory() is None
    plain = serve()
    assert not plain.fns.programs and plain.fns.prefill._cache_size() == 3
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:
        assert program_cache.directory() is None  # not on the CPU
        monkeypatch.setattr(program_cache, "directory",
                            lambda: str(tmp_path))  # as on the chip
        first = serve()
        kept = sorted(tmp_path.glob("dtg-*.executable"))
        assert [f.name.split("-")[1] for f in kept] == [
            "decode", "prefill", "prefill", "prefill"]
        assert len(first.fns.programs) == 4
        later = serve()
        assert later.fns is not first.fns and len(later.fns.programs) == 4
        assert later.fns.prefill._cache_size() == 0  # nothing traced
        assert later.fns.decode._cache_size() == 0
        assert (later.completions() == first.completions()
                == plain.completions())
        assert later.health()["prefill_chunks"] == plain.health()[
            "prefill_chunks"]
        # a file that does not load is compiled again and written over
        whole = kept[0].stat().st_size
        kept[0].write_bytes(kept[0].read_bytes()[:whole // 2])
        again = serve()
        assert again.completions() == plain.completions()
        assert kept[0].stat().st_size > 0.9 * whole
        assert again.fns.decode._cache_size() == 0  # lowered, not called
        # what reaches the trace beside the arguments is in the name
        serve(temperature=0.7)
        assert len(list(tmp_path.glob("dtg-*.executable"))) == 8
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
