"""One launch in flight: ``ServeEngine.step()`` dispatches a tick's launch
before it fetches the tokens of the launch before it. Nothing that is
computed may change by that, so every case here drives one engine as it
comes and a twin that is settled after every tick (``step(); settle()``:
the serial tick of before, through the same code) and holds the two to the
same tokens, for the three shapes the benchmark's cells run: GPT-2's block,
a patterned convolution + attention + routed model, a Mamba-2 + routed one.
"""

from __future__ import annotations

import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_guide_tpu.models.generation import (
    make_generate_fn,
)
from distributed_tensorflow_guide_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
)
from distributed_tensorflow_guide_tpu.serve.engine import Request, ServeEngine
from tests import test_nemotron_h, test_patterned

GEOMETRY = dict(slots=3, num_blocks=25, block_size=8, prefill_chunk=8)
GPT2 = TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                         d_model=16, d_ff=32, max_len=64, causal=True,
                         dtype=jnp.float32)
LENGTHS, MAX_NEW = [13, 21, 9, 5, 17], 12


def _gpt2():
    tree = Transformer(GPT2).init(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))["params"]
    return GPT2, tree


def _widened(module, weights):
    """A test module's tiny configuration with its seeded tree as float32."""
    return module.config(), jax.tree.map(
        lambda x: x.astype(jnp.float32),
        weights.flax_tree(module.SEED, module.Z))


SHAPES = {
    "gpt2": _gpt2,
    "conv_attention_routed": lambda: _widened(
        test_patterned, test_patterned.weights_lfm2),
    "mamba2_routed": lambda: _widened(
        test_nemotron_h, test_nemotron_h.weights_nemotron)}


@pytest.fixture(scope="module", params=sorted(SHAPES))
def shape(request):
    cfg, tree = SHAPES[request.param]()
    rng = np.random.default_rng(34)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in LENGTHS]
    return SimpleNamespace(name=request.param, cfg=cfg, tree=tree,
                           prompts=prompts)


def engine(shape, *, submit=True, max_new=MAX_NEW, **kw) -> ServeEngine:
    eng = ServeEngine(shape.cfg, shape.tree, temperature=0.0,
                      **{**GEOMETRY, **kw})
    if submit:
        for i, p in enumerate(shape.prompts):
            eng.submit(request(i, p, max_new))
    return eng


def request(rid, prompt, max_new=MAX_NEW, **kw) -> Request:
    return Request(rid=rid, prompt=prompt, max_new_tokens=max_new,
                   rng=np.asarray([0, rid], np.uint32), **kw)


def drive(eng, *, settled=False, ticks=None, between=None):
    """Step ``eng`` until it has nothing left (or ``ticks`` times), on a
    clock of one second a tick. ``settled`` settles after every tick: no
    launch is ever in flight when the next is planned. Returns each call's
    (events, kind)."""
    out, now = [], 0.0
    while eng.sched.has_queued or eng.sched.has_resident:
        events, kind = eng.step(now)
        if settled:
            events = events + eng.settle()
        out.append((events, kind))
        if between is not None:
            between(eng, now)
        now += 1.0
        if ticks is not None and len(out) >= ticks:
            break
    return out


def assert_nothing_owed(eng):
    assert eng._inflight is None and eng.sched._open == 0
    assert not eng.sched.owed and not eng._settled
    assert all(t is not None and t >= 0
               for toks in eng.sched.emitted.values() for t in toks)


def assert_streams_well_formed(calls, eng):
    """Each request's events in order: its tokens as ``completions()`` has
    them, ``first`` once and on the first, ``done`` on the last, a terminal
    status last of all and nothing after it; a call's kind is the kind of
    the launch its events came from."""
    by_rid: dict[int, list] = {}
    for events, kind in calls:
        ok = [e for e in events if e.status == "ok"]
        assert all(isinstance(e.token, int) and e.token >= 0 for e in ok)
        if kind == "prefill":  # at most a token a row
            assert len(ok) <= eng._widths[-1]
            assert len({e.rid for e in ok}) == len(ok)
        if kind == "decode":
            assert not any(e.first for e in ok)
        if kind == "idle":
            assert not ok
        for e in events:
            by_rid.setdefault(e.rid, []).append(e)
    done = eng.completions()
    for rid, events in by_rid.items():
        ok = [e for e in events if e.status == "ok"]
        assert [e.token for e in ok] == done[rid], rid
        assert [e.first for e in ok] == [True] + [False] * (len(ok) - 1)
        assert sum(e.done for e in events) == 1 and events[-1].done, rid
        assert all(e.status == "ok" for e in events[:-1]), rid


# ---- (a) the same tokens -----------------------------------------------------


@pytest.fixture(scope="module")
def settled_tokens(shape):
    eng = engine(shape)
    calls = drive(eng, settled=True)
    assert eng.health()["overlapped_launches"] == 0
    assert_streams_well_formed(calls, eng)
    return eng.completions()


def test_pipelined_ticks_serve_the_tokens_of_settled_ticks(shape,
                                                           settled_tokens):
    eng = engine(shape)
    calls = drive(eng)
    assert_nothing_owed(eng)
    assert eng.completions() == settled_tokens
    assert sorted(settled_tokens) == list(range(len(LENGTHS)))
    assert all(len(t) == MAX_NEW for t in settled_tokens.values())
    assert_streams_well_formed(calls, eng)
    eng.sched.check_leaks()
    if shape.name == "gpt2":  # the one-shot oracle, bitwise
        gen = make_generate_fn(shape.cfg, max_new_tokens=MAX_NEW,
                               temperature=0.0, top_k=None)
        for i, p in enumerate(shape.prompts):
            one = np.asarray(gen(shape.tree, p[None],
                                 np.asarray([0, i], np.uint32)))
            assert settled_tokens[i] == one[0, len(p):].tolist(), i


def test_a_launchs_events_come_with_the_call_after_its_dispatch(shape):
    eng = engine(shape)
    calls = drive(eng)
    kinds = [kind for _, kind in calls]
    # the first call after idle launches and has nothing to settle; the
    # last has nothing to launch and settles what is owed
    assert calls[0][0] == [] and kinds[0] == "prefill"
    assert calls[-1][0] and kinds[-1] != "idle"
    launched = eng.steps["prefill"] + eng.steps["decode"]
    assert len(calls) == launched + 1 and eng.steps["idle"] == 0
    assert eng.step(99.0) == ([], "idle")


# ---- (b) the order of a request's events -------------------------------------


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_no_token_follows_a_terminal_applied_while_it_was_owed(
        shape, settled_tokens, how):
    eng = engine(shape, submit=False)
    for i, p in enumerate(shape.prompts):
        eng.submit(request(i, p, deadline_s=14.5 if how == "deadline"
                           and i == 1 else None))
    struck = []

    def between(eng, now):
        # request 1 decodes and a launch that holds its next token is in
        # flight: the cancellation (the deadline, 14.5 s after a clock of
        # a second a tick) is applied by the next call's sweep, before
        # that token is fetched
        flight = eng._inflight
        if not struck and now >= 14.0 and flight.kind == "decode":
            assert eng.sched.emitted[1][-1] is None
            assert how == "deadline" or eng.cancel(1)
            struck.append(now)

    calls = drive(eng, between=between)
    assert struck == [14.0]
    assert_nothing_owed(eng)
    assert_streams_well_formed(calls, eng)
    last = [e for events, _ in calls for e in events if e.rid == 1][-1]
    assert last.status == ("cancelled" if how == "cancel" else "expired")
    served = eng.completions()
    assert 0 < len(served[1]) < MAX_NEW
    assert served[1] == settled_tokens[1][:len(served[1])]
    assert {r: t for r, t in served.items() if r != 1} == {
        r: t for r, t in settled_tokens.items() if r != 1}
    eng.sched.check_leaks()


def test_a_request_cancelled_before_its_dispatch_is_never_launched(shape):
    eng = engine(shape)
    eng.step(0.0)
    assert eng.cancel(4)  # queued behind three slots
    its = [e for events, _ in drive(eng) for e in events if e.rid == 4]
    assert [(e.status, e.done) for e in its] == [("cancelled", True)]
    assert eng.completions()[4] == []


# ---- (c) a pool too small for its residents ----------------------------------


def test_preemption_of_a_slot_whose_token_is_owed(shape):
    tight = dict(num_blocks=7, max_new=20)  # three residents outgrow it
    prompts = shape.prompts[:3]
    ref = SimpleNamespace(**{**vars(shape), "prompts": prompts})
    roomy = engine(ref, max_new=20)
    roomy.run()
    pipelined, settled = engine(ref, **tight), engine(ref, **tight)
    calls = drive(pipelined)
    drive(settled, settled=True)
    assert pipelined.health()["preemptions"] > 0
    assert (pipelined.health()["preemptions"]
            == settled.health()["preemptions"])
    assert (pipelined.completions() == settled.completions()
            == roomy.completions())
    assert_streams_well_formed(calls, pipelined)
    # a preemption settles first: fewer launches overlap than were made
    h = pipelined.health()
    assert 0 < h["overlapped_launches"] < h["launches"] - 1
    pipelined.sched.check_leaks()


def test_a_swapped_in_continuation_sets_its_row_from_the_host():
    """The host tier resumes a preempted request in decode phase with no
    prefill: the device's pending row is not its token, the host's is."""
    cfg, tree = _gpt2()
    rng = np.random.default_rng(5)
    ref = SimpleNamespace(cfg=cfg, tree=tree, prompts=[
        rng.integers(0, 64, n).astype(np.int32) for n in (13, 21, 9)])
    roomy = engine(ref, max_new=20)
    roomy.run()
    tight = engine(ref, max_new=20, num_blocks=7, host_blocks=16)
    calls = drive(tight)
    h = tight.health()
    assert h["preemptions"] > 0 and h["spill_resumes"] > 0
    assert tight.completions() == roomy.completions()
    assert_streams_well_formed(calls, tight)
    tight.close()
    tight.sched.check_leaks()


# ---- (d) state taken with a launch unsettled ---------------------------------


def test_export_and_adopt_with_a_launch_unsettled(shape, settled_tokens):
    eng = engine(shape)
    calls = drive(eng, ticks=7)
    assert eng._inflight is not None and eng.sched.emitted[0][-1] is None
    record = eng.export_stream(0, with_kv=False)
    assert eng._inflight is None  # the export settled first
    assert record["emitted"] == settled_tokens[0][:len(record["emitted"])]
    assert 0 < len(record["emitted"]) < MAX_NEW
    other = engine(shape, submit=False)
    other.adopt_stream(record)
    other.run()
    assert other.completions()[0] == settled_tokens[0]
    calls += drive(eng)
    assert_nothing_owed(eng)
    assert eng.completions() == {
        r: t for r, t in settled_tokens.items() if r != 0}
    # the token the export settled left with the record AND as an event
    handed = [e.token for events, _ in calls for e in events if e.rid == 0]
    assert handed == record["emitted"]


def test_snapshot_and_restore_with_a_launch_unsettled(shape, settled_tokens,
                                                      tmp_path):
    eng = engine(shape, snapshot_dir=tmp_path)
    drive(eng, ticks=9)
    assert eng._inflight is not None
    label = eng.save_snapshot()
    assert eng._inflight is None and label is not None
    eng.close()
    fresh = engine(shape, submit=False, snapshot_dir=tmp_path)
    assert fresh.restore_latest_snapshot() == label
    fresh.run()
    assert fresh.completions() == settled_tokens
    fresh.close()
    fresh.sched.check_leaks()


# ---- (e) the counter ---------------------------------------------------------


def test_every_launch_of_a_backlog_but_the_first_overlaps(shape):
    eng = engine(shape)
    forced = []

    def between(eng, now):
        if now in (5.0, 11.0) and eng._inflight is not None:
            forced.append(eng.settle())  # the events leave with the caller

    drive(eng, between=between)
    h = eng.health()
    assert len(forced) == 2
    assert h["launches"] == eng.steps["prefill"] + eng.steps["decode"]
    assert h["overlapped_launches"] == h["launches"] - 1 - len(forced)
    plain = engine(shape)
    plain.run()
    h = plain.health()
    assert h["overlapped_launches"] == h["launches"] - 1 > 10


def test_a_model_whose_bookkeeping_reads_the_launch_never_overlaps():
    """``MoEMLP``'s programs hand back the slots whose token overflowed an
    expert: those rows keep their token, which no one can know before the
    launch is fetched. Every launch is settled at once."""
    cfg = dataclasses.replace(GPT2, moe_experts=4, moe_capacity=1)
    tree = Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))["params"]
    rng = np.random.default_rng(3)
    ref = SimpleNamespace(cfg=cfg, tree=tree, prompts=[
        rng.integers(0, 64, n).astype(np.int32) for n in (13, 21, 9)])
    eng = engine(ref)
    calls = []
    while eng.sched.has_queued or eng.sched.has_resident:
        calls.append(eng.step(0.0))
        assert eng._inflight is None  # settled in the call that launched
    h = eng.health()
    assert h["launches"] > 10 and h["overlapped_launches"] == 0
    assert h["moe"]["stall_slot_ticks"] > 0  # rows that kept their token
    assert_streams_well_formed(calls, eng)
    assert all(len(t) == MAX_NEW for t in eng.completions().values())
    twin = engine(ref)
    drive(twin, settled=True)  # settling again after each call: a no-op
    assert twin.completions() == eng.completions()


# ---- (f) nothing is left owed ------------------------------------------------


def test_run_and_an_idle_return_leave_no_token_owed(shape, settled_tokens):
    eng = engine(shape)
    events = eng.run()
    assert_nothing_owed(eng)
    assert eng.completions() == settled_tokens
    assert sum(e.status == "ok" for e in events) == MAX_NEW * len(LENGTHS)
    assert eng.step(0.0) == ([], "idle")
    bounded = engine(shape)
    some = bounded.run(max_ticks=6)
    assert_nothing_owed(bounded)  # a bounded run settles at its end
    more = bounded.run()
    assert bounded.completions() == settled_tokens
    assert (sum(e.status == "ok" for e in some + more)
            == MAX_NEW * len(LENGTHS))
    stepped = engine(shape)
    while True:
        _, kind = stepped.step(0.0)
        if kind == "idle":
            break
    assert_nothing_owed(stepped)
    assert stepped.completions() == settled_tokens


def test_what_reads_a_tokens_value_settles_first(shape, settled_tokens):
    for read in ("completions", "health"):
        eng = engine(shape)
        calls = drive(eng, ticks=8)
        assert eng._inflight is not None
        getattr(eng, read)()
        assert eng._inflight is None and eng.sched._open == 0
        assert eng._settled  # its events wait for the next hand-out
        calls += drive(eng)
        assert eng.completions() == settled_tokens
        assert_streams_well_formed(calls, eng)  # and none was lost
