"""Serving subsystem (serve/): the acceptance pin is BITWISE parity —
every per-request stream the continuous-batching engine emits must be
identical to a one-shot ``make_generate_fn`` run of that request alone,
greedy and sampled, across the decode levers, through chunked prefill,
and across eviction/re-admission. Plus the host-side invariants the
device programs rest on: block accounting (no leak, no aliasing),
deterministic scheduling under a fixed trace, and the paged byte model.
"""

import dataclasses
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_guide_tpu.models.generation import (
    decode_cache_bytes_per_step,
    make_generate_fn,
    paged_decode_cache_bytes_per_step,
)
from distributed_tensorflow_guide_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
)
from distributed_tensorflow_guide_tpu.ops.decode_attention import (
    cache_slot_bytes,
)
from benchmarks.common import spill_bytes_per_swap
from distributed_tensorflow_guide_tpu.serve import (
    BlockPool,
    BlockStore,
    EngineOverloaded,
    Request,
    ServeEngine,
    blocks_for,
    build_step_fns,
    gather_view,
    table_row,
    write_chunk,
)
from distributed_tensorflow_guide_tpu.serve.scheduler import Scheduler, _Slot
from distributed_tensorflow_guide_tpu.testing.chaos import (
    Fault,
    FaultSchedule,
)

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


_ORACLE_CACHE: dict = {}  # every make_generate_fn call is a fresh compile


def _oracle(cfg, params, i, temp, top_k, *, prompts=PROMPTS,
            max_new=MAX_NEW, **gen_kw):
    """The one-shot stream request ``i`` must reproduce bitwise.

    Memoized: many tests pin against the same (cfg, request, sampling)
    oracle, and each uncached call compiles a whole one-shot program —
    the cache is most of this file's tier-1 wall-clock budget. Safe
    because every caller passes the module-scoped ``params`` fixture.
    """
    p, mn = prompts[i], max_new[i]
    key = (repr(cfg), i, temp, top_k, tuple(p.tolist()), mn,
           tuple(sorted(gen_kw.items())))
    if key not in _ORACLE_CACHE:
        gen = make_generate_fn(cfg, max_new_tokens=mn, temperature=temp,
                               top_k=top_k, **gen_kw)
        out = gen(params, p[None], jax.random.PRNGKey(100 + i))
        _ORACLE_CACHE[key] = np.asarray(out)[0, len(p):].tolist()
    return list(_ORACLE_CACHE[key])


def _serve(cfg, params, *, temp, top_k, prompts=PROMPTS, max_new=MAX_NEW,
           **kw):
    eng = ServeEngine(cfg, params, temperature=temp, top_k=top_k, **kw)
    for i, (p, mn) in enumerate(zip(prompts, max_new)):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=mn,
                           rng=jax.random.PRNGKey(100 + i)))
    events = eng.run()
    return eng, events


# ---- the acceptance pin: engine == one-shot, bitwise ------------------------


@pytest.mark.parametrize("temp,top_k", [(0.0, None), (0.8, 10)],
                         ids=["greedy", "sampled"])
def test_engine_matches_one_shot_bitwise(params, temp, top_k):
    """Three mixed-length requests on two slots: every completed stream
    equals that request's solo one-shot run exactly — positions-derived
    sampling keys make the engine's interleaving invisible."""
    eng, events = _serve(CFG, params, temp=temp, top_k=top_k, slots=2,
                         num_blocks=33, block_size=8, prefill_chunk=8)
    got = eng.completions()
    for i in range(len(PROMPTS)):
        assert got[i] == _oracle(CFG, params, i, temp, top_k), f"req {i}"
    assert eng.sched.done == {0, 1, 2}
    # every rid emits exactly one first and one done event
    assert sorted(e.rid for e in events if e.first) == [0, 1, 2]
    assert sorted(e.rid for e in events if e.done) == [0, 1, 2]
    eng.sched.pool.check_leaks()
    assert eng.live_blocks() == 0


def test_chunked_prefill_equals_whole_prompt(params):
    """prefill_chunk=8 (longest prompt streams in 3 chunks, interleaved
    with decode) vs prefill_chunk=32 (every prompt is one chunk): the
    completions must be identical token for token — the chunk schedule
    only changes WHEN cache rows get written, never what is sampled."""
    chunked, _ = _serve(CFG, params, temp=0.8, top_k=10, slots=2,
                        num_blocks=33, block_size=8, prefill_chunk=8)
    whole, _ = _serve(CFG, params, temp=0.8, top_k=10, slots=2,
                      num_blocks=33, block_size=8, prefill_chunk=32)
    assert chunked.completions() == whole.completions()
    # chunked really did split: more prefill launches than requests
    assert chunked.steps["prefill"] > len(PROMPTS)
    assert whole.steps["prefill"] == len(PROMPTS)


@pytest.mark.parametrize("kv,impl", [("int8", "dense"), (None, "pallas"),
                                     ("int8", "pallas")])
def test_engine_parity_across_decode_levers(params, kv, impl):
    """The serving path reuses the one-shot decode levers (int8 KV pool,
    length-aware paged Pallas kernel) — parity must hold bitwise under
    each, because engine and oracle run the SAME lever code."""
    cfg = dataclasses.replace(CFG, kv_dtype=kv, decode_impl=impl)
    prompts, max_new = PROMPTS[:2], MAX_NEW[:2]
    eng, _ = _serve(cfg, params, temp=0.8, top_k=10, prompts=prompts,
                    max_new=max_new, slots=2, num_blocks=17,
                    block_size=8, prefill_chunk=8)
    got = eng.completions()
    for i in range(len(prompts)):
        assert got[i] == _oracle(cfg, params, i, 0.8, 10,
                                 prompts=prompts, max_new=max_new), \
            f"req {i} kv={kv} impl={impl}"
    eng.sched.pool.check_leaks()


def test_speculative_one_shot_equals_engine_stream(params):
    """The engine never drafts; the speculative lever is covered through
    the spec==vanilla guarantee: a one-shot run WITH self-speculation
    emits the vanilla stream bitwise, and the engine emits the vanilla
    stream bitwise, so the two agree (docs/serving.md rationale)."""
    spec_oracle = _oracle(CFG, params, 0, 0.7, 12, spec_draft_layers=1)
    eng, _ = _serve(CFG, params, temp=0.7, top_k=12,
                    prompts=PROMPTS[:1], max_new=MAX_NEW[:1], slots=2,
                    num_blocks=17, block_size=8, prefill_chunk=8)
    assert eng.completions()[0] == spec_oracle


def test_eviction_preemption_preserves_parity(params):
    """A pool too small for both residents forces preemption mid-decode;
    the evicted request's continuation (prompt + emitted tail, remaining
    budget, same rng) re-prefills and must land on the SAME stream —
    eviction can never fork a request."""
    prompts = [np.array([3, 5, 7, 9, 11], np.int32),
               np.array([2, 4, 6, 8, 10, 12, 14], np.int32)]
    max_new = [40, 40]
    # capacity 8 blocks x 8 slots = 64 positions < the ~92 both need
    eng, _ = _serve(CFG, params, temp=0.7, top_k=12, prompts=prompts,
                    max_new=max_new, slots=2, num_blocks=9,
                    block_size=8, prefill_chunk=8)
    assert eng.sched.preemptions >= 1
    got = eng.completions()
    for i in range(2):
        assert got[i] == _oracle(CFG, params, i, 0.7, 12,
                                 prompts=prompts, max_new=max_new), \
            f"req {i} diverged across eviction"
    eng.sched.pool.check_leaks()
    assert eng.live_blocks() == 0


def test_mid_flight_admission_interleaves_streams(params):
    """Three requests, two slots: the third is admitted the moment a slot
    frees, WHILE the other resident keeps decoding — its tokens appear
    between the survivor's tokens with nothing recompiled."""
    eng, events = _serve(CFG, params, temp=0.0, top_k=None,
                         max_new=[16, 4, 6], slots=2, num_blocks=33,
                         block_size=8, prefill_chunk=8)
    first2 = next(k for k, e in enumerate(events)
                  if e.rid == 2 and e.first)
    first_done = next(k for k, e in enumerate(events) if e.done)
    assert first2 > first_done  # admitted into a freed slot...
    # ...while an earlier request was still streaming
    assert any(e.rid != 2 for e in events[first2 + 1:])
    assert eng.sched.done == {0, 1, 2}


def test_scheduler_determinism_replays_identical_event_log(params):
    """Identical submitted trace -> identical event log, tick for tick,
    including through preemption (the tight pool from the eviction test).
    Everything downstream (bench numbers, battery rows) rests on this."""
    prompts = [np.array([3, 5, 7, 9, 11], np.int32),
               np.array([2, 4, 6, 8, 10, 12, 14], np.int32)]
    max_new = [40, 40]

    def once():
        eng, events = _serve(CFG, params, temp=0.7, top_k=12,
                             prompts=prompts, max_new=max_new, slots=2,
                             num_blocks=9, block_size=8, prefill_chunk=8)
        return ([(e.rid, e.token, e.first, e.done) for e in events],
                dict(eng.steps), eng.sched.preemptions)

    log1, steps1, pre1 = once()
    log2, steps2, pre2 = once()
    assert log1 == log2
    assert steps1 == steps2 and pre1 == pre2


# ---- intake validation ------------------------------------------------------


def test_submit_validation(params):
    # capacity 4 blocks = 32 positions (the trash block is never granted)
    eng = ServeEngine(CFG, params, slots=2, num_blocks=5, block_size=8,
                      prefill_chunk=8)
    with pytest.raises(ValueError, match="out of vocabulary"):
        eng.submit(Request(rid=0, prompt=np.array([99], np.int32),
                           max_new_tokens=4, rng=jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(rid=1, prompt=np.array([], np.int32),
                           max_new_tokens=4, rng=jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(Request(rid=2, prompt=np.array([1] * 60, np.int32),
                           max_new_tokens=8, rng=jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="never fit"):
        # fits max_len (38 <= 64) but needs 5 blocks, capacity 4
        eng.submit(Request(rid=3, prompt=np.array([1] * 30, np.int32),
                           max_new_tokens=8, rng=jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="must divide"):
        ServeEngine(CFG, params, slots=2, num_blocks=9, block_size=8,
                    prefill_chunk=7)


# ---- host-side block accounting ---------------------------------------------


def test_block_pool_accounting():
    pool = BlockPool(5, 8)
    assert pool.trash_block == 4 and pool.capacity == 4
    # lowest ids first, deterministically
    assert pool.alloc(1, 2) == [0, 1]
    # an unsatisfiable alloc changes nothing
    assert pool.alloc(2, 3) is None and pool.free_blocks == 2
    assert pool.alloc(2, 2) == [2, 3]  # trash block never handed out
    assert pool.live_blocks() == 4 and pool.owned_by(1) == [0, 1]
    pool.check_leaks()
    # ownership is enforced on free: no cross-request free, no double free
    with pytest.raises(ValueError, match="does not own"):
        pool.free(2, [0])
    pool.free(1, [0, 1])
    with pytest.raises(ValueError, match="does not own"):
        pool.free(1, [0, 1])
    assert pool.alloc(3, 1) == [0]  # freed blocks recycle lowest-first
    pool.check_leaks()
    # a leaked block is caught
    del pool._holders[0]
    with pytest.raises(AssertionError, match="leak"):
        pool.check_leaks()
    with pytest.raises(ValueError, match=">= 2 blocks"):
        BlockPool(1, 8)


def test_blocks_for_and_table_row():
    assert blocks_for(1, 8) == 1
    assert blocks_for(8, 8) == 1
    assert blocks_for(9, 8) == 2
    row = table_row([3, 1], 4, trash=9)
    np.testing.assert_array_equal(row, [3, 1, 9, 9])
    np.testing.assert_array_equal(table_row([], 3, trash=5), [5, 5, 5])


# ---- device-side gather / write ---------------------------------------------


def test_gather_scatter_roundtrip_and_trash_isolation():
    """write_chunk through a table then gather_view back must equal the
    dense view, and a trash-pointing table row must leave every owned
    block untouched (the inactive-slot write path)."""
    r = np.random.RandomState(0)
    N, bs, H, hd = 5, 4, 2, 3  # the pool layout: a block's slots last
    pool = jnp.asarray(r.randn(N, H, hd, bs), jnp.float32)
    tables = jnp.asarray([[2, 0, 3], [4, 4, 4]], jnp.int32)  # trash id 4
    view = gather_view(pool, tables)
    assert view.shape == (2, H, hd, 3 * bs)
    np.testing.assert_array_equal(
        np.asarray(view[0, :, :, :bs]), np.asarray(pool[2]))
    np.testing.assert_array_equal(
        np.asarray(view[1, :, :, bs:2 * bs]), np.asarray(pool[4]))
    # write a 4-token chunk for request 0 at logical position 2 (straddles
    # physical blocks 2 and 0) while request 1's row points at trash
    chunk = jnp.asarray(r.randn(2, H, hd, 4), jnp.float32)
    idx = jnp.asarray([2, 0], jnp.int32)
    out = write_chunk(pool, chunk, tables, idx, block_size=bs)
    got = gather_view(out, tables)
    np.testing.assert_array_equal(np.asarray(got[0, :, :, 2:6]),
                                  np.asarray(chunk[0]))
    # request 0's untouched positions survive
    np.testing.assert_array_equal(np.asarray(got[0, :, :, :2]),
                                  np.asarray(view[0, :, :, :2]))
    np.testing.assert_array_equal(np.asarray(got[0, :, :, 6:]),
                                  np.asarray(view[0, :, :, 6:]))
    # request 1's trash-routed write left every unwritten block intact
    # (request 0 touched only physical blocks 2 and 0)
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(pool[1]))
    np.testing.assert_array_equal(np.asarray(out[3]), np.asarray(pool[3]))


# request 0 owns blocks 2, 0, 3; request 1 owns 1 and then nothing (the
# trash id is 4); request 2 is an inactive slot, all trash
_WRITE_TABLES = [[2, 0, 3], [1, 4, 4], [4, 4, 4]]


@pytest.mark.parametrize("kernel", [False, True], ids=["loop", "pallas"])
@pytest.mark.parametrize("chunk,starts", [
    (1, [5, 3, 0]),    # a decode step: one slot a row
    (4, [2, 0, 0]),    # chunk == block size, straddling blocks 2 and 0
    (6, [3, 0, 6]),    # chunk > block size: three blocks touched
    (3, [8, 1, 2]),    # chunk < block size, inside one block
    (5, [7, 2, 6]),    # a tail that runs off request 1's blocks: trash
])
def test_write_chunk_puts_each_position_in_its_blocks_slot(kernel, chunk,
                                                           starts):
    """The pool write (its loop and its Pallas form) against the sentence
    that defines it, position by position in numpy: position ``p`` of row
    ``b`` lands in slot ``p % bs`` of block ``table[b][p // bs]``. Bitwise
    on every owned block: straddling chunks, chunk != block size,
    trash-routed rows (whose landing place nothing reads: skipped)."""
    r = np.random.RandomState(chunk)
    N, bs, H, d = 5, 4, 2, 3
    trash = N - 1
    pool = jnp.asarray(r.randn(N, H, d, bs), jnp.float32)
    rows = jnp.asarray(r.randn(3, H, d, chunk), jnp.float32)
    tables = jnp.asarray(_WRITE_TABLES, jnp.int32)
    idx = jnp.asarray(starts, jnp.int32)
    want = np.array(pool)
    for b, start in enumerate(starts):
        for c in range(chunk):
            block, slot = divmod(start + c, bs)
            if (block < len(_WRITE_TABLES[b])
                    and _WRITE_TABLES[b][block] != trash):
                want[_WRITE_TABLES[b][block], :, :, slot] = np.asarray(
                    rows[b, :, :, c])
    got = jax.jit(lambda *a: write_chunk(*a, block_size=bs, kernel=kernel))(
        pool, rows, tables, idx)
    # every owned block: the written slots, and all the rest as it was
    np.testing.assert_array_equal(np.asarray(got)[:trash], want[:trash])


@pytest.mark.parametrize("decode_impl", ["dense", "pallas"])
@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["native", "int8"])
@pytest.mark.parametrize("residency", ["paged", "one_shot"])
def test_cache_leaves_have_one_shape_whatever_the_lever(residency, kv_dtype,
                                                        decode_impl):
    """One cache layout a residency: the pool an engine holds is ``(N, H,
    hd, block_size)`` and the one-shot cache ``(B, H, max_len, hd)`` under
    every ``decode_impl`` and ``kv_dtype`` (which decides the dtype and
    adds the scale rows, nothing else), so what tier-1 runs on the CPU by
    default is the layout the chip runs."""
    from distributed_tensorflow_guide_tpu.models.generation import (
        init_cache,
    )
    from distributed_tensorflow_guide_tpu.serve.engine import (
        paged_cache_shapes,
        paged_config,
    )

    cfg = dataclasses.replace(CFG, kv_dtype=kv_dtype,
                              decode_impl=decode_impl)
    H, hd = CFG.num_heads, CFG.head_dim
    if residency == "paged":
        leaves = paged_cache_shapes(
            paged_config(cfg, num_blocks=9, block_size=8), 2)
        want, want_scale = (9, H, hd, 8), (9, H, 1, 8)
    else:
        leaves = init_cache(cfg, None, 3)
        want, want_scale = (3, H, CFG.max_len, hd), (3, H, 1, CFG.max_len)
    payload = jnp.int8 if kv_dtype == "int8" else CFG.dtype
    for i in range(CFG.num_layers):
        layer = dict(leaves[f"block_{i}"]["attn"])
        for name in ("cached_key", "cached_value"):
            leaf = layer.pop(name)
            assert (leaf.shape, leaf.dtype) == (want, payload), name
        if kv_dtype == "int8":
            for name in ("key_scale", "value_scale"):
                leaf = layer.pop(name)
                assert (leaf.shape, leaf.dtype) == (want_scale,
                                                    jnp.float32), name
        assert not layer, f"unexpected cache leaves {sorted(layer)}"


@pytest.mark.parametrize("kernel", [False, True], ids=["loop", "pallas"])
def test_write_chunk_past_the_table_lands_in_trash(kernel):
    """Positions past a row's last table entry touch no owned block."""
    r = np.random.RandomState(1)
    N, bs, H, d = 5, 4, 2, 1  # d == 1: the int8 cache's scale rows
    pool = jnp.asarray(r.randn(N, H, d, bs), jnp.float32)
    rows = jnp.asarray(r.randn(1, H, d, 6), jnp.float32)
    tables = jnp.asarray([[2, 0, 3]], jnp.int32)
    got = write_chunk(pool, rows, tables, jnp.asarray([9], jnp.int32),
                      block_size=bs, kernel=kernel)
    np.testing.assert_array_equal(np.asarray(got[3, :, :, 1:]),
                                  np.asarray(rows[0, :, :, :3]))
    np.testing.assert_array_equal(np.asarray(got[3, :, :, 0]),
                                  np.asarray(pool[3, :, :, 0]))
    np.testing.assert_array_equal(np.asarray(got[:3]), np.asarray(pool[:3]))


# ---- paged byte model -------------------------------------------------------


def test_paged_byte_model_charges_live_blocks_not_max_len():
    per_slot = CFG.num_heads * cache_slot_bytes(CFG.head_dim, CFG.dtype)
    got = paged_decode_cache_bytes_per_step(
        CFG, block_size=8, live_blocks=3, active_slots=2)
    assert got == CFG.num_layers * (3 * 8 + 2) * per_slot
    # strictly below the dense model's batch * max_len charge
    assert got < decode_cache_bytes_per_step(CFG, 2)
    # int8 pool: 1-byte slots + f32 scales through the shared definition
    i8 = paged_decode_cache_bytes_per_step(
        dataclasses.replace(CFG, kv_dtype="int8"), block_size=8,
        live_blocks=3, active_slots=2)
    assert i8 < got


# ---- program plumbing -------------------------------------------------------


def test_step_fns_donation_declared_and_gated():
    """The pool donation INTENT is always (1,) — the lint contract audits
    it in alias mode — but actual donation is gated off on the CPU test
    backend (no input-output aliasing there, same as make_generate_fn)."""
    fns = build_step_fns(CFG, slots=2, num_blocks=9, block_size=8,
                        prefill_chunk=8)
    assert fns.declared_donate_argnums == (1,)
    assert fns.donates_pool == (jax.default_backend() != "cpu")
    assert fns.cfg.paged_num_blocks == 9
    assert fns.n_blk == CFG.max_len // 8
    # memoized on everything that reaches the trace: a second engine at
    # the same geometry reuses the SAME jitted pair (slots / chunk width
    # shape-specialize inside jit and deliberately don't key the memo),
    # while a different pool geometry or sampling knob builds fresh
    assert build_step_fns(CFG, slots=4, num_blocks=9, block_size=8,
                          prefill_chunk=16) is fns
    assert build_step_fns(CFG, slots=2, num_blocks=17, block_size=8,
                          prefill_chunk=8) is not fns
    assert build_step_fns(CFG, slots=2, num_blocks=9, block_size=8,
                          prefill_chunk=8, temperature=0.5) is not fns


# ---- serving under fire (PR 11) ---------------------------------------------
# Request lifecycle (cancel / deadlines / shedding), chaos absorption, and
# engine snapshot/restore. Everything here reuses the geometries the tests
# above already compiled (the build_step_fns memo), so this whole section
# adds no new program compiles to tier-1.


def _submit_all(eng, *, prompts=PROMPTS, max_new=MAX_NEW):
    for i, (p, mn) in enumerate(zip(prompts, max_new)):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=mn,
                           rng=jax.random.PRNGKey(100 + i)))


def test_pick_victim_is_youngest_admission_deterministically(params):
    """The documented tie-break: the victim is the YOUNGEST resident by
    admission order (highest admitted_seq — unique per admission, so the
    max is total and replay can never diverge), excluding the growing
    slot and blockless residents."""
    sch = Scheduler(slots=3, num_blocks=9, block_size=8, prefill_chunk=8,
                    max_len=64)
    key = np.asarray(jax.random.PRNGKey(0))

    def mk(rid, seq, blocks):
        return _Slot(rid=rid, prompt=np.array([1], np.int32), budget=4,
                     rng=key, blocks=blocks, admitted_seq=seq)

    sch.slots = [mk(0, 5, [0]), mk(1, 9, [1]), mk(2, 7, [2])]
    assert sch._pick_victim(exclude=0) == 1  # seq 9 is youngest
    assert sch._pick_victim(exclude=1) == 2  # excluding it: seq 7
    sch.slots[1] = None
    assert sch._pick_victim(exclude=0) == 2
    sch.slots[2].blocks = []  # blockless: evicting frees nothing
    assert sch._pick_victim(exclude=0) is None

    # end to end: under forced eviction the victim SEQUENCE is a pure
    # function of the submitted trace — two runs preempt identical rids
    # in identical order
    prompts = [np.array([3, 5, 7, 9, 11], np.int32),
               np.array([2, 4, 6, 8, 10, 12, 14], np.int32)]

    def victims_once():
        eng = ServeEngine(CFG, params, slots=2, num_blocks=9,
                          block_size=8, prefill_chunk=8, temperature=0.7,
                          top_k=12)
        victims = []
        orig = eng.sched._preempt

        def spy(i):
            victims.append(eng.sched.slots[i].rid)
            return orig(i)

        eng.sched._preempt = spy
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=40,
                               rng=jax.random.PRNGKey(100 + i)))
        eng.run()
        return victims

    v1, v2 = victims_once(), victims_once()
    assert v1 and v1 == v2


def test_cancel_frees_resources_and_preserves_prefix(params):
    """Client cancellation mid-decode: one terminal event at the next
    step boundary, slot+blocks freed (check_leaks clean), survivors
    bitwise, and the cancelled stream is a bitwise PREFIX of its
    uninterrupted one-shot run — cancellation never corrupts what was
    already delivered."""
    eng = ServeEngine(CFG, params, slots=2, num_blocks=33, block_size=8,
                      prefill_chunk=8, temperature=0.8, top_k=10)
    _submit_all(eng)
    events = []
    for _ in range(6):  # rid 0 is mid-decide: >=1 token, budget unspent
        evs, _ = eng.step()
        events.extend(evs)
    assert eng.cancel(0) is True
    assert eng.cancel(99) is False  # unknown rid: a no-op, not an error
    events.extend(eng.run())
    term = [e for e in events if e.rid == 0 and e.status == "cancelled"]
    assert len(term) == 1 and term[0].token == -1 and term[0].done
    assert eng.cancel(0) is False  # already terminal: a no-op
    got = eng.completions()
    for i in (1, 2):  # survivors: completely unaffected, bitwise
        assert got[i] == _oracle(CFG, params, i, 0.8, 10), f"req {i}"
    o0 = _oracle(CFG, params, 0, 0.8, 10)
    assert 0 < len(got[0]) < len(o0) and got[0] == o0[:len(got[0])]
    assert eng.sched.finished[0] == "cancelled"
    assert eng.health()["cancelled"] == 1
    eng.sched.pool.check_leaks()
    assert eng.live_blocks() == 0


def test_deadlines_expire_at_step_boundaries(params):
    """TTFT and total deadlines, measured from the ORIGINAL arrival and
    evaluated at step boundaries by the sweep. run()'s now=inf would
    expire every deadline instantly — deadlines need a clock-driving
    caller (docs/serving.md), so this test advances now explicitly."""
    eng = ServeEngine(CFG, params, slots=2, num_blocks=33, block_size=8,
                      prefill_chunk=8, temperature=0.0)
    p0, p1, p2 = PROMPTS
    eng.submit(Request(rid=0, prompt=p0, max_new_tokens=8,
                       rng=jax.random.PRNGKey(100)))
    # expires mid-decode: ~7 ticks of service at 0.01s/tick
    eng.submit(Request(rid=1, prompt=p1, max_new_tokens=6,
                       rng=jax.random.PRNGKey(101), deadline_s=0.075))
    # both slots are busy, so this one waits queued; TTFT 0 expires it
    # at the first swept boundary without it ever emitting
    eng.submit(Request(rid=2, prompt=p2, max_new_tokens=10,
                       rng=jax.random.PRNGKey(102), ttft_deadline_s=0.0))
    now, events, ticks = 0.0, [], 0
    while eng.sched.has_queued or eng.sched.has_resident:
        evs, kind = eng.step(now)
        events.extend(evs)
        now += 0.01
        ticks += 1
        assert ticks < 200
    statuses = {e.rid: e.status for e in events if e.token < 0}
    assert statuses == {1: "expired", 2: "expired"}
    got = eng.completions()
    assert got[0] == _oracle(CFG, params, 0, 0.0, None)  # no deadline set
    o1 = _oracle(CFG, params, 1, 0.0, None)
    assert 0 < len(got[1]) < len(o1) and got[1] == o1[:len(got[1])]
    assert got[2] == []  # expired while queued: zero tokens
    assert eng.health()["expired"] == 2
    eng.sched.pool.check_leaks()
    # the predicted-TTFT gate is warm now (finite clock above): a request
    # whose TTFT budget is already below recent TTFTs is shed at the door
    assert eng._ttft_ewma is not None and eng._ttft_ewma > 0
    with pytest.raises(EngineOverloaded, match="recent TTFT"):
        eng.submit(Request(rid=7, prompt=p0, max_new_tokens=4,
                           rng=jax.random.PRNGKey(7),
                           ttft_deadline_s=eng._ttft_ewma / 2))
    assert eng.health()["shed"] == 1


def test_overload_sheds_retriably_at_the_door(params):
    """Queue-depth admission control: past max_queue, submit raises the
    retriable EngineOverloaded and records NOTHING — the identical
    resubmission later yields the identical stream bitwise."""
    eng = ServeEngine(CFG, params, slots=2, num_blocks=33, block_size=8,
                      prefill_chunk=8, temperature=0.8, top_k=10,
                      max_queue=2)
    _submit_all(eng, prompts=PROMPTS[:2], max_new=MAX_NEW[:2])
    with pytest.raises(EngineOverloaded, match="retry"):
        eng.submit(Request(rid=2, prompt=PROMPTS[2],
                           max_new_tokens=MAX_NEW[2],
                           rng=jax.random.PRNGKey(102)))
    assert EngineOverloaded.retriable is True
    assert eng.sched.shed == 1 and 2 not in eng.sched.emitted
    eng.run()
    eng.submit(Request(rid=2, prompt=PROMPTS[2], max_new_tokens=MAX_NEW[2],
                       rng=jax.random.PRNGKey(102)))
    eng.run()
    assert eng.completions()[2] == _oracle(CFG, params, 2, 0.8, 10)
    assert eng.health()["shed"] == 1
    eng.sched.pool.check_leaks()


def test_step_exception_and_pool_pressure_storm_is_invisible(params):
    """An injected launch failure retries the SAME tick bitwise; a pool
    -pressure spike forces eviction/re-prefill. Neither may change a
    single emitted token, leak a block, or leave a fault unabsorbed."""
    sched = FaultSchedule([Fault("serve_step_exception", 2),
                           Fault("pool_pressure", 4, 4.0)])
    eng = ServeEngine(CFG, params, slots=2, num_blocks=33, block_size=8,
                      prefill_chunk=8, temperature=0.8, top_k=10,
                      chaos=sched, retry_base_delay_s=0.001)
    _submit_all(eng)
    eng.run()
    got = eng.completions()
    for i in range(len(PROMPTS)):
        assert got[i] == _oracle(CFG, params, i, 0.8, 10), f"req {i}"
    assert sched.serve_events() == [] and len(sched.fired) == 2
    eng.sched.pool.check_leaks()
    assert eng.live_blocks() == 0


def test_real_failure_on_a_donated_pool_is_not_retried(params):
    """Where the step programs donate the pool, a failure that lands
    mid-launch has consumed it: a retry could only fail on the deleted
    buffer and bury the first error. The FIRST exception propagates, once;
    injected failures (raised before the program runs) still retry."""
    import copy

    sched = FaultSchedule([Fault("serve_step_exception", 1)])
    eng = ServeEngine(CFG, params, slots=2, num_blocks=33, block_size=8,
                      prefill_chunk=8, chaos=sched,
                      retry_base_delay_s=0.001)
    eng.fns = copy.copy(eng.fns)  # the memoized pair is shared: keep it so
    eng.fns.donates_pool = True
    _submit_all(eng)
    for _ in range(4):  # tick 1's injected failure is retried and absorbed
        eng.step(float("inf"))
    assert eng.launch_failures == 1 and len(sched.fired) == 1

    calls = []

    def faulting(*args):
        calls.append(len(args))
        raise RuntimeError("first and only")

    eng.fns.decode = eng.fns.prefill = faulting
    with pytest.raises(RuntimeError, match="first and only"):
        eng.step(float("inf"))
    assert len(calls) == 1 and eng.launch_failures == 2


def test_arrival_burst_and_client_abandon(params):
    """A burst-injected request streams to completion bitwise like any
    other; a client_abandon fault cancels a live rid whose delivered
    tokens stay a bitwise prefix. check_leaks clean throughout."""
    def burst(n, now):
        assert n == 1
        return [Request(rid=1000, prompt=PROMPTS[0], max_new_tokens=4,
                        rng=jax.random.PRNGKey(42), arrival=now)]

    sched = FaultSchedule([Fault("arrival_burst", 3, 1.0),
                           Fault("client_abandon", 6, 0.0)])
    eng = ServeEngine(CFG, params, slots=2, num_blocks=33, block_size=8,
                      prefill_chunk=8, temperature=0.8, top_k=10,
                      chaos=sched, burst_factory=burst)
    _submit_all(eng)
    eng.run()
    assert sched.serve_events() == [] and len(sched.fired) == 2
    # the burst request == its own one-shot run, bitwise
    gen = make_generate_fn(CFG, max_new_tokens=4, temperature=0.8,
                           top_k=10)
    out = gen(params, PROMPTS[0][None], jax.random.PRNGKey(42))
    assert eng.completions()[1000] == \
        np.asarray(out)[0, len(PROMPTS[0]):].tolist()
    # abandon index 0 cancelled the lowest live rid (= 0, still serving)
    cancelled = [r for r, st in eng.sched.finished.items()
                 if st == "cancelled"]
    assert cancelled == [0]
    got0 = eng.completions()[0]
    o0 = _oracle(CFG, params, 0, 0.8, 10)
    assert got0 == o0[:len(got0)]
    eng.sched.pool.check_leaks()
    assert eng.live_blocks() == 0


def test_watchdog_breaks_hung_step_and_retry_is_bitwise(params):
    """A hung compiled step becomes WatchdogTimeout (not a silent stall)
    and retries like any transient — the re-run tick is bitwise the
    original. deadline=1.5s: the per-attempt deadline must cover a
    first-launch XLA compile (~0.25s on CPU), the operational footgun
    docs/serving.md calls out."""
    eng = ServeEngine(CFG, params, slots=2, num_blocks=33, block_size=8,
                      prefill_chunk=8, temperature=0.0,
                      step_deadline_s=1.5, retry_base_delay_s=0.01)
    # copy the memoized namespace before wrapping — mutating the shared
    # one would poison every other engine at this geometry
    eng.fns = SimpleNamespace(**vars(eng.fns))
    real = eng.fns.decode
    state = {"hung": False}

    def hang_once(*a, **kw):
        if not state["hung"]:
            state["hung"] = True
            end = time.monotonic() + 30.0
            while time.monotonic() < end:  # interruptible: small slices
                time.sleep(0.02)
        return real(*a, **kw)

    eng.fns.decode = hang_once
    _submit_all(eng, prompts=PROMPTS[:2], max_new=MAX_NEW[:2])
    t0 = time.perf_counter()
    eng.run()
    assert time.perf_counter() - t0 < 15.0  # the 30s hang was broken
    assert state["hung"]
    got = eng.completions()
    for i in range(2):
        assert got[i] == _oracle(CFG, params, i, 0.0, None), f"req {i}"
    eng.sched.pool.check_leaks()
    eng.close()


def test_engine_kill_restore_resumes_bitwise(params, tmp_path):
    """The tentpole pin: snapshot, keep serving, kill, restore a FRESH
    engine from the snapshot — every in-flight stream continues and ends
    bitwise identical to an uninterrupted run, and the span the kill
    dropped is re-emitted bitwise (position-derived keys; the pool is
    never saved, residents re-prefill as continuations)."""
    kw = dict(slots=2, num_blocks=33, block_size=8, prefill_chunk=8,
              temperature=0.8, top_k=10,
              snapshot_dir=str(tmp_path / "snap"))
    eng = ServeEngine(CFG, params, **kw)
    _submit_all(eng)
    for _ in range(7):
        eng.step()
    label = eng.save_snapshot()
    assert label is not None
    for _ in range(3):  # post-snapshot progress the restore must re-earn
        eng.step()
    pre = eng.completions()
    assert any(pre.values())  # the kill really drops emitted tokens
    eng.close()  # the "kill": nothing after the snapshot persists

    eng2 = ServeEngine(CFG, params, **kw)
    assert eng2.restore_latest_snapshot() == label
    eng2.run()
    got = eng2.completions()
    for i in range(len(PROMPTS)):
        assert got[i] == _oracle(CFG, params, i, 0.8, 10), f"req {i}"
        # everything delivered pre-kill is a prefix of the final stream
        assert pre[i] == got[i][:len(pre[i])]
    eng2.sched.pool.check_leaks()
    assert eng2.live_blocks() == 0
    eng2.close()


def test_snapshot_ladder_skips_corrupt_through_eviction(params, tmp_path):
    """snapshot_corrupt damages the newest snapshot post-commit; restore
    must ladder down to the previous valid one and STILL land every
    stream bitwise — here through the forced-eviction geometry, so the
    restore path composes with preemption/continuation."""
    prompts = [np.array([3, 5, 7, 9, 11], np.int32),
               np.array([2, 4, 6, 8, 10, 12, 14], np.int32)]
    max_new = [40, 40]
    sched = FaultSchedule([Fault("snapshot_corrupt", 24)])
    kw = dict(slots=2, num_blocks=9, block_size=8, prefill_chunk=8,
              temperature=0.7, top_k=12, snapshot_dir=str(tmp_path / "s"))
    eng = ServeEngine(CFG, params, chaos=sched, **kw)
    for i, (p, mn) in enumerate(zip(prompts, max_new)):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=mn,
                           rng=jax.random.PRNGKey(100 + i)))
    for t in range(26):  # saves land at ticks 8, 16, 24; corrupt at 24
        eng.step()
        if (t + 1) % 8 == 0:
            eng.save_snapshot()
    assert sched.serve_events() == []  # the corruption really landed
    eng.close()

    eng2 = ServeEngine(CFG, params, **kw)
    assert eng2.restore_latest_snapshot() == 16  # 24 is damaged: fall back
    eng2.run()
    got = eng2.completions()
    for i in range(2):
        assert got[i] == _oracle(CFG, params, i, 0.7, 12, prompts=prompts,
                                 max_new=max_new), f"req {i}"
    assert eng.sched.preemptions + eng2.sched.preemptions >= 1
    eng2.sched.pool.check_leaks()
    eng2.close()


@pytest.mark.parametrize("kv,impl", [("int8", "dense"), (None, "pallas")])
def test_snapshot_restore_across_decode_levers(params, kv, impl, tmp_path):
    """Kill+restore composes with the decode levers: the restored
    engine's re-prefilled continuations stay bitwise under int8 KV and
    the paged Pallas read path too."""
    cfg = dataclasses.replace(CFG, kv_dtype=kv, decode_impl=impl)
    prompts, max_new = PROMPTS[:2], MAX_NEW[:2]
    kw = dict(slots=2, num_blocks=17, block_size=8, prefill_chunk=8,
              temperature=0.8, top_k=10, snapshot_dir=str(tmp_path / "s"))
    eng = ServeEngine(cfg, params, **kw)
    _submit_all(eng, prompts=prompts, max_new=max_new)
    for _ in range(5):
        eng.step()
    assert eng.save_snapshot() is not None
    eng.step()
    eng.close()
    eng2 = ServeEngine(cfg, params, **kw)
    assert eng2.restore_latest_snapshot() is not None
    eng2.run()
    for i in range(2):
        assert eng2.completions()[i] == _oracle(
            cfg, params, i, 0.8, 10, prompts=prompts, max_new=max_new), \
            f"req {i} kv={kv} impl={impl}"
    eng2.sched.pool.check_leaks()
    eng2.close()


# ---- prefix sharing, tenancy, multi-LoRA (PR 12) ----------------------------
# The engine tests here reuse the geometries compiled above (the
# build_step_fns memo) wherever possible; the only new compiles are the
# tiny LoRA config's step pair and its one-shot oracle.


def test_block_pool_refcount_share():
    """Refcounted sharing: a full block may be claimed by ref-bump, every
    holder frees independently, the block returns to the free list only
    at refcount zero, and live_blocks() counts DISTINCT blocks (the dedup
    closed form the byte model charges)."""
    pool = BlockPool(6, 8)
    assert pool.alloc(1, 3) == [0, 1, 2]
    pool.share(2, [0, 1])                 # rid 2 claims rid 1's prefix
    assert pool.refcount(0) == 2 and pool.refcount(2) == 1
    assert pool.owned_by(2) == [0, 1]
    # 3 + 2 claimed block-refs, but only 3 distinct live blocks
    assert pool.live_blocks() == 3 and pool.free_blocks == 2
    pool.check_leaks()
    with pytest.raises(ValueError, match="already holds"):
        pool.share(2, [0])                # no double-claim by one holder
    with pytest.raises(ValueError, match="dead block"):
        pool.share(3, [4])                # only live blocks are shareable
    pool.free(1, [0, 1, 2])               # rid 1 exits; rid 2's refs hold
    assert pool.live_blocks() == 2 and pool.refcount(0) == 1
    assert pool.alloc(5, 4) is None       # 0,1 are NOT free: only 2,3,4
    assert pool.alloc(5, 3) == [2, 3, 4]
    pool.free(2, [0, 1])                  # last holder: now they recycle
    assert pool.alloc(5, 2) == [0, 1]
    pool.free(5, [0, 1, 2, 3, 4])
    assert pool.live_blocks() == 0
    pool.check_leaks()


def test_prefix_index_match_insert_evict():
    """The radix trie over a real pool: block-granularity match, existing
    -node-wins insert, LRU leaf-first eviction that never touches a block
    a resident still holds, and adapter keying."""
    from distributed_tensorflow_guide_tpu.serve.prefix_index import (
        CACHE_RID,
        PrefixIndex,
    )

    pool = BlockPool(8, 4)
    idx = PrefixIndex(4)
    toks = list(range(10))                # 2 full blocks + a partial
    blocks = pool.alloc(0, 3)
    assert idx.insert(toks, blocks, pool=pool) == 2   # partial never cached
    assert idx.size == 2 and pool.refcount(blocks[0]) == 2
    assert idx.match(toks) == blocks[:2]
    assert idx.match(toks[:7]) == blocks[:1]          # 1 full block only
    assert idx.match([9, 9, 9, 9]) == []
    assert idx.match(toks, adapter=1) == []           # adapter-keyed root
    # existing node wins: a concurrent duplicate's blocks are not cached
    dup = pool.alloc(1, 2)
    assert idx.insert(toks[:8], dup, pool=pool) == 0
    assert idx.match(toks) == blocks[:2]
    pool.free(1, dup)
    # the request exits; the cache's refs keep both blocks live
    pool.free(0, blocks)
    assert pool.live_blocks() == 2
    # eviction is leaf-first: node 1 (deeper) goes before node 0 even
    # though node 0 is colder — an inner node is never evictable
    assert idx.evict_one(pool) == blocks[1]
    assert idx.match(toks) == blocks[:1]
    # a resident's ref pins the survivor: nothing evictable
    pool.share(7, [blocks[0]])
    assert idx.evict_one(pool) is None
    pool.free(7, [blocks[0]])
    assert idx.evict_one(pool) == blocks[0]
    assert idx.size == 0 and pool.live_blocks() == 0
    pool.check_leaks()
    # drop releases everything at once (engine close)
    b2 = pool.alloc(3, 2)
    idx.insert(list(range(8)), b2, pool=pool)
    pool.free(3, b2)
    assert idx.drop(pool) == 2
    pool.check_leaks()
    assert pool.refcount(0) == 0 and CACHE_RID < 0


def test_prefix_sharing_bitwise_and_dedup(params):
    """The tentpole pin: with the prefix cache on, a repeat prompt claims
    its cached blocks by ref-bump and prefills only the suffix — and the
    stream stays bitwise identical to the same request served ALONE with
    the cache off. A diverging suffix (COW fork) also stays bitwise: the
    shared blocks are read-only, private blocks take every write."""
    fork = np.array([1] * 16 + [2], np.int32)   # shares 2 blocks with
    prompts = [PROMPTS[2], fork]                # PROMPTS[2] = [1]*17
    kw = dict(slots=2, num_blocks=33, block_size=8, prefill_chunk=8,
              temperature=0.8, top_k=10)
    eng = ServeEngine(CFG, params, prefix_cache=True, **kw)
    eng.submit(Request(rid=0, prompt=PROMPTS[2], max_new_tokens=MAX_NEW[2],
                       rng=jax.random.PRNGKey(102)))
    eng.run()
    warm_prefills = eng.steps["prefill"]        # 17 tokens -> 3 chunks
    assert eng.health()["prefix_nodes"] == 2    # [1]*8 twice, cached
    # repeat + COW fork, served concurrently off the shared prefix
    eng.submit(Request(rid=1, prompt=PROMPTS[2], max_new_tokens=MAX_NEW[2],
                       rng=jax.random.PRNGKey(102)))
    eng.submit(Request(rid=2, prompt=fork, max_new_tokens=MAX_NEW[2],
                       rng=jax.random.PRNGKey(100)))
    eng.run()
    got = eng.completions()
    # both claimed 16 tokens; each prefilled exactly 1 suffix chunk
    assert eng.steps["prefill"] == warm_prefills + 2
    assert eng.health()["prefill_tokens_saved"] == 32
    assert eng.health()["prefix_hit_tokens"] == 32
    # bitwise: repeat == the cache-off oracle of the SAME request alone
    assert got[1] == got[0] == _oracle(CFG, params, 2, 0.8, 10)
    assert got[2] == _oracle(CFG, params, 0, 0.8, 10,
                             prompts=[fork], max_new=[MAX_NEW[2]])
    eng.close()                                 # drops the cache's refs
    eng.sched.pool.check_leaks()
    assert eng.live_blocks() == 0


@pytest.mark.parametrize("kv,impl", [("int8", "dense"), (None, "pallas")])
def test_prefix_sharing_parity_across_decode_levers(params, kv, impl):
    """Prefix claims compose with the decode levers: the repeat request
    reads its shared blocks through the int8/pallas read path (scale
    blocks ride the same block ids) and still reproduces the cache-off
    one-shot stream bitwise. Same geometry as
    test_engine_parity_across_decode_levers — no new compiles."""
    cfg = dataclasses.replace(CFG, kv_dtype=kv, decode_impl=impl)
    eng = ServeEngine(cfg, params, prefix_cache=True, slots=2,
                      num_blocks=17, block_size=8, prefill_chunk=8,
                      temperature=0.8, top_k=10)
    eng.submit(Request(rid=0, prompt=PROMPTS[1], max_new_tokens=MAX_NEW[1],
                       rng=jax.random.PRNGKey(101)))
    eng.run()
    assert eng.health()["prefix_nodes"] == 1    # one full block cached
    eng.submit(Request(rid=1, prompt=PROMPTS[1], max_new_tokens=MAX_NEW[1],
                       rng=jax.random.PRNGKey(101)))
    eng.run()
    assert eng.health()["prefill_tokens_saved"] == 8
    got = eng.completions()
    assert got[0] == got[1] == _oracle(cfg, params, 1, 0.8, 10), \
        f"kv={kv} impl={impl}"
    eng.close()
    eng.sched.pool.check_leaks()


def test_prefix_dedup_charges_shared_blocks_once(params):
    """live_blocks() closed form while shared prefixes are RESIDENT: two
    claimers of a 2-block prefix plus their private suffixes count the
    shared blocks once — the paged byte model's denominator."""
    kw = dict(slots=2, num_blocks=33, block_size=8, prefill_chunk=8,
              temperature=0.0, top_k=None)
    eng = ServeEngine(CFG, params, prefix_cache=True, **kw)
    eng.submit(Request(rid=0, prompt=PROMPTS[2], max_new_tokens=MAX_NEW[2],
                       rng=jax.random.PRNGKey(102)))
    eng.run()
    eng.submit(Request(rid=1, prompt=PROMPTS[2], max_new_tokens=MAX_NEW[2],
                       rng=jax.random.PRNGKey(102)))
    eng.submit(Request(rid=2, prompt=PROMPTS[2], max_new_tokens=MAX_NEW[2],
                       rng=jax.random.PRNGKey(102)))
    eng.step()  # both admitted: shared prefix claimed, suffixes private
    pool = eng.sched.pool
    # the prompt needs 3 blocks: 2 shared (also the cache's 2) + 1
    # private tail each => 4 distinct live blocks, not 6 — the shared
    # pair is charged once
    assert pool.owned_by(1)[:2] == pool.owned_by(2)[:2]
    assert pool.live_blocks() == 4
    assert sum(len(pool.owned_by(r)) for r in (1, 2)) == 6
    eng.run()
    assert eng.completions()[1] == eng.completions()[2] \
        == _oracle(CFG, params, 2, 0.0, None)
    eng.close()
    eng.sched.pool.check_leaks()


def test_prefix_eviction_and_preemption_parity(params):
    """The tight pool (nb=9) with the cache on: cached blocks are evicted
    LRU leaf-first to feed decode growth BEFORE any resident is
    preempted, and every stream still lands bitwise. Prompts span 2 full
    blocks each so finishing really populates the trie."""
    prompts = [np.array([1] * 17, np.int32),
               np.array([2] * 17, np.int32),
               np.array([3] * 17, np.int32)]
    max_new = [30, 30, 30]
    eng = ServeEngine(CFG, params, slots=2, num_blocks=9, block_size=8,
                      prefill_chunk=8, temperature=0.7, top_k=12,
                      prefix_cache=True)
    _submit_all(eng, prompts=prompts[:2], max_new=max_new[:2])
    eng.run()
    # the finished prompts (and their preempted continuations) now fill
    # the trie; a cold third prompt must evict cached leaves to fit
    assert eng.health()["prefix_nodes"] >= 4
    eng.submit(Request(rid=2, prompt=prompts[2], max_new_tokens=max_new[2],
                       rng=jax.random.PRNGKey(102)))
    eng.run()
    assert eng.sched.prefix_evictions >= 1  # the cache yielded to decode
    got = eng.completions()
    for i in range(3):
        assert got[i] == _oracle(CFG, params, i, 0.7, 12, prompts=prompts,
                                 max_new=max_new), f"req {i}"
    eng.close()
    eng.sched.pool.check_leaks()
    assert eng.live_blocks() == 0


def test_scheduler_drr_interleaves_and_quotas_skip(params):
    """Host-side fair share: with a small quantum, deficit round-robin
    interleaves a backlogged tenant with a light one instead of FIFO
    head-of-line; a quota-blocked tenant is SKIPPED (never blocks the
    others); with the default quantum admission IS legacy FIFO."""
    key = np.asarray(jax.random.PRNGKey(0))
    p = np.array([1, 2, 3, 4, 5], np.int32)

    def mk(rid, tenant):
        return Request(rid=rid, prompt=p, max_new_tokens=8, rng=key,
                       tenant=tenant)

    # cost = blocks_for(5+8) = 2; quantum 1 -> every admit costs 2 rounds
    sch = Scheduler(slots=4, num_blocks=33, block_size=8, prefill_chunk=8,
                    max_len=64, drr_quantum=1)
    for r in [mk(0, 0), mk(1, 0), mk(2, 0), mk(3, 1)]:
        sch.submit(r)
    sch.admit(0.0)
    order = [s.rid for s in sorted(
        (s for s in sch.slots if s is not None),
        key=lambda s: s.admitted_seq)]
    assert order == [0, 3, 1, 2]  # tenant 1 jumps the tenant-0 backlog
    assert sch.tenants[0]["admitted"] == 3 and sch.tenants[1]["admitted"] == 1
    # a single tenant reduces to exact head-of-line FIFO (the PR-10/11
    # determinism pins above run through this same path unchanged)
    sch2 = Scheduler(slots=4, num_blocks=33, block_size=8, prefill_chunk=8,
                     max_len=64)
    for r in [mk(0, 0), mk(1, 0), mk(2, 0)]:
        sch2.submit(r)
    sch2.admit(0.0)
    order2 = [s.rid for s in sorted(
        (s for s in sch2.slots if s is not None),
        key=lambda s: s.admitted_seq)]
    assert order2 == [0, 1, 2]
    # a slots quota caps tenant 0 at 1 resident and SKIPS its backlog
    sch3 = Scheduler(slots=2, num_blocks=33, block_size=8, prefill_chunk=8,
                     max_len=64, tenant_quotas={0: {"slots": 1}})
    for r in [mk(0, 0), mk(1, 0), mk(2, 1)]:
        sch3.submit(r)
    sch3.admit(0.0)
    resident = {s.rid: s.tenant for s in sch3.slots if s is not None}
    assert resident == {0: 0, 2: 1}       # rid 1 waits; rid 2 not blocked
    assert [r.rid for r in sch3.queue] == [1]
    # a blocks quota below a request's worst-case footprint can NEVER be
    # satisfied — that is a caller error, rejected loudly at submit
    sch4 = Scheduler(slots=4, num_blocks=33, block_size=8, prefill_chunk=8,
                     max_len=64, tenant_quotas={0: {"blocks": 1}})
    with pytest.raises(ValueError, match="never fit"):
        sch4.submit(mk(9, 0))             # needs 2 blocks, quota caps at 1


def test_fair_share_absorbs_tenant_burst(params):
    """A chaos arrival_burst aimed at one tenant, with that tenant under
    a slots quota: the victim tenant's streams are untouched bitwise and
    the per-tenant health counters account for every burst request."""
    def burst(n, now, tenant):
        assert tenant == 0
        return [Request(rid=1000 + k, prompt=PROMPTS[0], max_new_tokens=4,
                        rng=jax.random.PRNGKey(42), arrival=now,
                        tenant=tenant) for k in range(n)]

    sched = FaultSchedule([Fault("arrival_burst", 3, 2.0, tenant=0)])
    eng = ServeEngine(CFG, params, slots=2, num_blocks=33, block_size=8,
                      prefill_chunk=8, temperature=0.8, top_k=10,
                      chaos=sched, burst_factory=burst,
                      tenant_quotas={0: {"slots": 1}})
    for i, (p, mn) in enumerate(zip(PROMPTS, MAX_NEW)):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=mn,
                           rng=jax.random.PRNGKey(100 + i), tenant=1))
    eng.run()
    assert sched.serve_events() == []
    got = eng.completions()
    for i in range(len(PROMPTS)):  # tenant 1: bitwise despite the burst
        assert got[i] == _oracle(CFG, params, i, 0.8, 10), f"req {i}"
    gen = make_generate_fn(CFG, max_new_tokens=4, temperature=0.8,
                           top_k=10)
    out = np.asarray(gen(params, PROMPTS[0][None],
                         jax.random.PRNGKey(42)))[0, len(PROMPTS[0]):]
    for rid in (1000, 1001):  # burst requests also land bitwise
        assert got[rid] == out.tolist()
    t = eng.health()["tenants"]
    assert t[0]["submitted"] == 2 and t[0]["done"] == 2
    assert t[1]["submitted"] == 3 and t[1]["done"] == 3
    eng.close()
    eng.sched.pool.check_leaks()


def test_multi_lora_batched_decode_bitwise(params):
    """Batched multi-LoRA: one shared decode step serves slots on
    different adapters via gathered low-rank deltas. Adapter 0 (the zero
    rows) is bitwise the BASE model; adapter k is bitwise the one-shot
    generate with that adapter's delta applied."""
    from distributed_tensorflow_guide_tpu.serve.engine import (
        init_adapter_bank,
    )

    cfg_l = dataclasses.replace(CFG, lora_rank=2, lora_adapters=2)
    bank = init_adapter_bank(cfg_l)
    keys = jax.random.split(jax.random.PRNGKey(7), len(jax.tree.leaves(bank)))
    # a bank large enough for the positive control at the end to bind: a
    # rank-2 delta is quadratic in the bank's scale, and at 0.05 it moved
    # the last prompt position's logits by 0.007 where the base's top two
    # lie 0.02 apart, so six sampled tokens came out the base's and the
    # control said nothing about the delta (at 0.2 it moves them by 0.2)
    bank = jax.tree.unflatten(
        jax.tree.structure(bank),
        [0.2 * jax.random.normal(k, l.shape, l.dtype).at[0].set(0.0)
         for k, l in zip(keys, jax.tree.leaves(bank))])
    eng = ServeEngine(cfg_l, params, slots=2, num_blocks=33, block_size=8,
                      prefill_chunk=8, temperature=0.8, top_k=10,
                      adapters=bank)
    eng.submit(Request(rid=0, prompt=PROMPTS[0], max_new_tokens=MAX_NEW[0],
                       rng=jax.random.PRNGKey(100), adapter=0))
    eng.submit(Request(rid=1, prompt=PROMPTS[1], max_new_tokens=MAX_NEW[1],
                       rng=jax.random.PRNGKey(101), adapter=1))
    eng.run()
    got = eng.completions()
    # adapter 0 == the base oracle, bitwise, even batched WITH adapter 1
    assert got[0] == _oracle(CFG, params, 0, 0.8, 10)
    gen1 = make_generate_fn(cfg_l, max_new_tokens=MAX_NEW[1],
                            temperature=0.8, top_k=10, adapters=bank,
                            adapter_id=1)
    o1 = np.asarray(gen1(params, PROMPTS[1][None],
                         jax.random.PRNGKey(101)))[0,
                                                   len(PROMPTS[1]):].tolist()
    assert got[1] == o1
    # the control: adapter 1 is not the base model by another name
    assert o1 != _oracle(CFG, params, 1, 0.8, 10)
    eng.close()
    eng.sched.pool.check_leaks()


def test_snapshot_restore_rebuilds_prefix_cache(params, tmp_path):
    """Kill+restore with sharing live: the trie is deliberately NOT in
    the snapshot — the restored engine's continuation re-prefills rebuild
    it deterministically, streams stay bitwise, and a post-restore repeat
    prompt hits the rebuilt cache."""
    kw = dict(slots=2, num_blocks=33, block_size=8, prefill_chunk=8,
              temperature=0.8, top_k=10, prefix_cache=True,
              snapshot_dir=str(tmp_path / "snap"))
    eng = ServeEngine(CFG, params, **kw)
    _submit_all(eng)
    for _ in range(7):
        eng.step()
    label = eng.save_snapshot()
    assert label is not None
    for _ in range(3):
        eng.step()
    eng.close()  # the kill: cache refs dropped, post-snapshot work lost

    eng2 = ServeEngine(CFG, params, **kw)
    assert eng2.restore_latest_snapshot() == label
    eng2.run()
    got = eng2.completions()
    for i in range(len(PROMPTS)):
        assert got[i] == _oracle(CFG, params, i, 0.8, 10), f"req {i}"
    # the rebuilt trie serves a repeat of the longest prompt from cache
    assert eng2.health()["prefix_nodes"] >= 2
    eng2.submit(Request(rid=9, prompt=PROMPTS[2],
                        max_new_tokens=MAX_NEW[2],
                        rng=jax.random.PRNGKey(102)))
    eng2.run()
    assert eng2.completions()[9] == _oracle(CFG, params, 2, 0.8, 10)
    # exactly the repeat's 16-token claim: the three distinct prompts
    # share no full block, so the restore continuations themselves save
    # nothing — a drift here means the claim path double-counted
    assert eng2.health()["prefill_tokens_saved"] == 16
    eng2.close()
    eng2.sched.pool.check_leaks()
    assert eng2.live_blocks() == 0


def test_tenant_adapter_submit_validation(params):
    eng = ServeEngine(CFG, params, slots=2, num_blocks=33, block_size=8,
                      prefill_chunk=8)
    with pytest.raises(ValueError, match="no lora_rank"):
        eng.submit(Request(rid=0, prompt=PROMPTS[0], max_new_tokens=4,
                           rng=jax.random.PRNGKey(0), adapter=1))
    with pytest.raises(ValueError, match="tenant"):
        eng.submit(Request(rid=1, prompt=PROMPTS[0], max_new_tokens=4,
                           rng=jax.random.PRNGKey(0), tenant=-1))
    with pytest.raises(ValueError, match="adapters"):
        ServeEngine(CFG, params, slots=2, num_blocks=33, block_size=8,
                    prefill_chunk=8, adapters={"x": jnp.zeros((1,))})
    with pytest.raises(ValueError, match="drr_quantum"):
        Scheduler(slots=2, num_blocks=9, block_size=8, prefill_chunk=8,
                  max_len=64, drr_quantum=0)


# ---- KV cache hierarchy: host-RAM spill tier (PR 16) ------------------------
# Demotion is the non-destructive rung under eviction: preempted residents
# and cold trie prefixes swap OUT to a host BlockStore and swap back IN at
# re-admission/claim time, so the streams below must equal the uninterrupted
# oracles BITWISE — the hierarchy buys goodput, never correctness. Every
# geometry here reuses step programs the tests above already compiled.


def test_block_store_holder_ledger():
    """The host tier mirrors the pool's refcounted discipline exactly:
    put=1 holder, share ref-bumps (double-hold raises), free deletes the
    payload only at refcount 0, a full store returns None with NO state
    change, and ids are never recycled."""
    store = BlockStore(capacity=2)
    row = [np.arange(4, dtype=np.float32)]
    h0 = store.put(1, row)
    h1 = store.put(1, [np.zeros((2,), np.int8)])
    assert store.put(1, row) is None            # full: rejected, no hold
    assert store.live_blocks() == 2
    store.share(2, [h0])
    assert store.refcount(h0) == 2
    with pytest.raises(ValueError, match="already holds"):
        store.share(2, [h0])
    with pytest.raises(ValueError, match="dead host block"):
        store.share(3, [99])
    store.free(1, [h0])                         # payload survives holder 2
    np.testing.assert_array_equal(store.get(h0)[0], row[0])
    with pytest.raises(ValueError, match="does not own"):
        store.free(3, [h1])
    store.free(2, [h0])
    with pytest.raises(ValueError, match="dead host block"):
        store.get(h0)
    assert store.owned_by(1) == [h1]
    assert store.bytes_stored() == 2
    assert store.stats() == {"live": 1, "shared": 0, "holds": 1,
                             "bytes": 2}
    h2 = store.put(2, row)                      # capacity freed back up
    assert h2 is not None and h2 > h1           # monotonic, not recycled
    store.check_leaks()


def test_spill_preemption_resumes_without_reprefill(params):
    """The eviction-parity pool squeeze, hierarchy ON: preemption demotes
    the victim's blocks to the host tier and re-admission swaps them back
    in instead of re-prefilling — same streams bitwise, strictly fewer
    prefill steps than the destructive run, both tiers leak-free. Also
    the zero-new-programs pin: the swap path is host-side by design, so
    an actively-spilling engine adds NOTHING to ``_STEP_FNS`` and shares
    the pool-only engine's memoized program pair outright."""
    from distributed_tensorflow_guide_tpu.serve.engine import _STEP_FNS
    prompts = [np.array([3, 5, 7, 9, 11], np.int32),
               np.array([2, 4, 6, 8, 10, 12, 14], np.int32)]
    max_new = [40, 40]
    base, _ = _serve(CFG, params, temp=0.7, top_k=12, prompts=prompts,
                     max_new=max_new, slots=2, num_blocks=9,
                     block_size=8, prefill_chunk=8)
    n0 = len(_STEP_FNS)
    eng, _ = _serve(CFG, params, temp=0.7, top_k=12, prompts=prompts,
                    max_new=max_new, slots=2, num_blocks=9,
                    block_size=8, prefill_chunk=8, host_blocks=16)
    sd = eng.sched
    assert sd.preemptions >= 1
    assert sd.spill_resumes >= 1                # demote->swap-in, not kill
    assert sd.spill_out_blocks > 0 and sd.spill_in_blocks > 0
    assert sd.swapin_tokens_saved > 0
    got = eng.completions()
    for i in range(2):
        assert got[i] == base.completions()[i] == _oracle(
            CFG, params, i, 0.7, 12, prompts=prompts, max_new=max_new), \
            f"req {i} diverged across demotion"
    assert eng.steps["prefill"] < base.steps["prefill"]
    assert len(_STEP_FNS) == n0                 # zero new step programs
    assert eng.fns is base.fns                  # the same memoized pair
    sd.check_leaks()                            # device + host, jointly
    assert eng.live_blocks() == 0
    assert eng.store.live_blocks() == 0         # all resumes drained


@pytest.mark.parametrize("kv,impl", [("int8", "dense"), (None, "pallas"),
                                     ("int8", "pallas")])
def test_spill_roundtrip_parity_across_levers(params, kv, impl):
    """Swap-out/swap-in is bitwise for every KV layout the pool can hold
    (f32 rows; int8 rows + f32 scale leaves; pallas decode): cache a
    prompt, demote its trie prefix to the host tier, then re-serve the
    same prompt — the claim promotes by h2d swap-in and the stream still
    equals the uninterrupted oracle."""
    cfg = dataclasses.replace(CFG, kv_dtype=kv, decode_impl=impl)
    prompts, max_new = PROMPTS[:2], MAX_NEW[:2]
    eng, _ = _serve(cfg, params, temp=0.8, top_k=10, prompts=prompts,
                    max_new=max_new, slots=2, num_blocks=17,
                    block_size=8, prefill_chunk=8, prefix_cache=True,
                    host_blocks=8)
    sd = eng.sched
    freed = sd.prefix.demote_many(sd.pool, sd._cache_demote_batch)
    assert freed                                # prompt 1 cached a block
    before = sd.spill_in_blocks
    eng.submit(Request(rid=9, prompt=prompts[1], max_new_tokens=max_new[1],
                       rng=jax.random.PRNGKey(101)))
    eng.run()
    assert sd.spill_in_blocks > before          # promoted by swap-in
    assert eng.completions()[9] == _oracle(
        cfg, params, 1, 0.8, 10, prompts=prompts, max_new=max_new), \
        f"spilled round-trip diverged kv={kv} impl={impl}"
    eng.close()
    sd.check_leaks()


def test_cow_shared_block_spills_once(params):
    """A device block with multiple holders crosses the tier boundary
    ONCE: the first demotion d2h-copies, the second ref-bumps the same
    host payload — pinned by exact byte accounting (one block's worth of
    d2h traffic for two demotions)."""
    eng = ServeEngine(CFG, params, temperature=0.0, top_k=None, slots=2,
                      num_blocks=33, block_size=8, prefill_chunk=8,
                      host_blocks=8)
    sd = eng.sched
    (b,) = sd.pool.alloc(7, 1)
    sd.pool.share(8, [b])                       # COW: two device holders
    h7 = sd._demote_block(7, b)
    once = sd.spill_d2h_bytes
    assert once == eng.store.bytes_stored() == spill_bytes_per_swap(
        CFG.num_layers, CFG.num_heads, 8, CFG.d_model // CFG.num_heads,
        None, activation_dtype_bytes=np.dtype(CFG.dtype).itemsize)
    h8 = sd._demote_block(8, b)
    assert h8 == h7                             # deduped onto one payload
    assert eng.store.refcount(h7) == 2
    assert sd.spill_out_blocks == 2             # both demotions counted...
    assert sd.spill_d2h_bytes == once           # ...but the bytes moved once
    sd.pool.free(7, [b])
    sd.pool.free(8, [b])
    eng.store.free(7, [h7])
    eng.store.free(8, [h8])
    sd.check_leaks()


@pytest.mark.parametrize("kv", [None, "int8"], ids=["f32", "int8"])
def test_spill_byte_model_is_exact(params, kv):
    """``spill_bytes_per_swap`` is EXACT, not a bound: one demoted
    block's host bytes equal the closed form for both KV layouts —
    activation-dtype K/V rows, plus the f32 scale leaves when
    quantized."""
    cfg = dataclasses.replace(CFG, kv_dtype=kv)
    eng = ServeEngine(cfg, params, temperature=0.8, top_k=10, slots=2,
                      num_blocks=33 if kv is None else 17, block_size=8,
                      prefill_chunk=8, host_blocks=4)
    sd = eng.sched
    (b,) = sd.pool.alloc(5, 1)
    h = sd._demote_block(5, b)
    model = spill_bytes_per_swap(
        CFG.num_layers, CFG.num_heads, 8, CFG.d_model // CFG.num_heads,
        kv, activation_dtype_bytes=np.dtype(CFG.dtype).itemsize)
    assert sd.spill_d2h_bytes == eng.store.bytes_stored() == model
    sd.pool.free(5, [b])
    eng.store.free(5, [h])
    sd.check_leaks()


def test_spilled_prefix_claim_promotes_by_swap_in(params):
    """The trie indexes prefixes BEYOND device residency: demote every
    cached prefix wholesale (trie keeps its structure, zero device
    blocks), then repeat the longest prompt — the claim swaps its two
    blocks back in, charges them to ``swapin_tokens_saved``, and the
    stream stays bitwise."""
    eng = ServeEngine(CFG, params, temperature=0.8, top_k=10, slots=2,
                      num_blocks=33, block_size=8, prefill_chunk=8,
                      prefix_cache=True, host_blocks=8)
    _submit_all(eng)
    eng.run()
    sd = eng.sched
    nodes = sd.prefix.size
    freed = sd.prefix.demote_many(sd.pool, sd._cache_demote_batch)
    assert len(freed) == nodes >= 3             # whole trie went host-side
    assert sd.prefix.stats()["spilled"] == nodes
    saved0 = sd.prefill_tokens_saved
    eng.submit(Request(rid=9, prompt=PROMPTS[2], max_new_tokens=MAX_NEW[2],
                       rng=jax.random.PRNGKey(102)))
    eng.run()
    assert eng.completions()[9] == _oracle(CFG, params, 2, 0.8, 10)
    assert sd.spill_in_blocks == 2              # the 16-token claim cap
    assert sd.swapin_tokens_saved == 16
    assert sd.prefill_tokens_saved - saved0 == 16
    eng.close()
    sd.check_leaks()


def test_warm_restart_reprefills_zero_cached_prefix_tokens(params,
                                                           tmp_path):
    """Kill + warm restore: with ``--persist-cache`` the snapshot carries
    the cache CONTENTS — the fresh engine's trie comes back entirely in
    the host tier (zero device blocks held), and a repeat prompt prefills
    ONLY its uncached suffix chunk: zero cached-prefix tokens are ever
    re-prefilled."""
    kw = dict(slots=2, num_blocks=33, block_size=8, prefill_chunk=8,
              temperature=0.8, top_k=10, prefix_cache=True,
              host_blocks=8, persist_cache=True,
              snapshot_dir=str(tmp_path / "snap"))
    eng = ServeEngine(CFG, params, **kw)
    _submit_all(eng)
    eng.run()
    nodes = eng.sched.prefix.size
    assert nodes >= 3
    assert eng.save_snapshot() is not None
    eng.close()                                 # the kill

    eng2 = ServeEngine(CFG, params, **kw)
    assert eng2.restore_latest_snapshot() is not None
    sd = eng2.sched
    assert sd.prefix.size == nodes              # the trie came back...
    assert sd.prefix.stats()["spilled"] == nodes
    assert sd.pool.live_blocks() == 0           # ...entirely host-side
    assert eng2.store.live_blocks() == nodes
    spill_in0 = sd.spill_in_blocks              # counters restore too —
    saved0 = sd.prefill_tokens_saved            # pin the DELTAS below
    pre0 = eng2.steps["prefill"]
    eng2.submit(Request(rid=9, prompt=PROMPTS[2],
                        max_new_tokens=MAX_NEW[2],
                        rng=jax.random.PRNGKey(102)))
    eng2.run()
    assert eng2.completions()[9] == _oracle(CFG, params, 2, 0.8, 10)
    # 17-token prompt, 16 cached: exactly ONE suffix-chunk prefill step
    assert eng2.steps["prefill"] - pre0 == 1
    assert sd.prefill_tokens_saved - saved0 == 16
    assert sd.spill_in_blocks - spill_in0 == 2
    eng2.close()
    sd.check_leaks()


def test_corrupt_cache_file_falls_back_to_cold(params, tmp_path):
    """The warm-cache file is best-effort, never load-bearing: a
    truncated payload, a flipped byte (CRC mismatch), or a missing
    sidecar each restore COLD — the snapshot restore itself still
    succeeds, the repeat prompt simply re-prefills, and the stream is
    still bitwise. Never a wrong token. (One shared warm run feeds all
    three corruption rungs — pristine file copies restored per rung.)"""
    import os
    import shutil
    kw = dict(slots=2, num_blocks=33, block_size=8, prefill_chunk=8,
              temperature=0.8, top_k=10, prefix_cache=True,
              host_blocks=8, persist_cache=True,
              snapshot_dir=str(tmp_path / "snap"))
    eng = ServeEngine(CFG, params, **kw)
    _submit_all(eng)
    eng.run()
    label = eng.save_snapshot()
    path = eng._cache_file(label)
    crc = path[:-4] + ".crc"
    eng.close()
    pristine = {p: open(p, "rb").read() for p in (path, crc)}

    for corruption in ("truncate", "bitflip", "no_crc"):
        for p, raw in pristine.items():
            with open(p, "wb") as f:
                f.write(raw)
        if corruption == "truncate":
            with open(path, "wb") as f:
                f.write(pristine[path][:len(pristine[path]) // 2])
        elif corruption == "bitflip":
            flipped = bytearray(pristine[path])
            flipped[len(flipped) // 2] ^= 0xFF
            with open(path, "wb") as f:
                f.write(bytes(flipped))
        else:
            os.remove(crc)

        eng2 = ServeEngine(CFG, params, **kw)
        assert eng2.restore_latest_snapshot() == label  # snapshot fine
        sd = eng2.sched
        assert sd.prefix.size == 0, corruption  # cache went cold, safely
        assert eng2.store.live_blocks() == 0
        pre0 = eng2.steps["prefill"]            # steps restore with the
        eng2.submit(Request(rid=9, prompt=PROMPTS[2],   # snapshot: deltas
                            max_new_tokens=MAX_NEW[2],
                            rng=jax.random.PRNGKey(102)))
        eng2.run()
        assert eng2.completions()[9] == _oracle(CFG, params, 2, 0.8, 10)
        assert eng2.steps["prefill"] - pre0 == 3, corruption  # full cold
        assert sd.spill_in_blocks == 0
        eng2.close()
        sd.check_leaks()
    shutil.rmtree(str(tmp_path / "snap"))


def test_spill_knob_validation(params):
    with pytest.raises(ValueError, match="host_blocks"):
        ServeEngine(CFG, params, slots=2, num_blocks=33, block_size=8,
                    prefill_chunk=8, host_blocks=-1)
    with pytest.raises(ValueError, match="persist_cache"):
        ServeEngine(CFG, params, slots=2, num_blocks=33, block_size=8,
                    prefill_chunk=8, persist_cache=True)


# ---- kill mid-snapshot, across real process boundaries (out of tier-1) ------


def _target_serve_kill_mid_snapshot(snap_dir, phase):
    """Subprocess target: phase "serve" snapshots durably, races an async
    snapshot against the parent's SIGKILL; phase "restore" restores the
    newest VALID snapshot in a fresh process and drains."""
    import pathlib
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_guide_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
    )
    from distributed_tensorflow_guide_tpu.serve.engine import (
        Request,
        ServeEngine,
    )

    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                            d_model=16, d_ff=32, max_len=64, causal=True,
                            dtype=jnp.float32)
    params = Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))["params"]
    eng = ServeEngine(cfg, params, slots=2, num_blocks=33, block_size=8,
                      prefill_chunk=8, temperature=0.8, top_k=10,
                      snapshot_dir=snap_dir)
    if phase == "serve":
        prompts = [np.array([3, 5, 7, 9, 11], np.int32),
                   np.array([2, 4, 6, 8, 10, 12, 14, 16, 18], np.int32)]
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=8,
                               rng=jax.random.PRNGKey(100 + i)))
        for _ in range(5):
            eng.step()
        eng.save_snapshot()  # the durable baseline
        for _ in range(3):
            eng.step()
        eng.save_snapshot(async_=True)  # the kill races this commit
        pathlib.Path(snap_dir, "saved_marker").touch()
        _time.sleep(600)  # hold still; the parent kills us here
    label = eng.restore_latest_snapshot()
    eng.run()
    eng.close()
    return {"label": label,
            "completions": {int(k): list(v)
                            for k, v in eng.completions().items()}}


@pytest.mark.chaos
@pytest.mark.slow
def test_kill_mid_snapshot_then_restore_bitwise(tmp_path, params):
    """Run 1 is SIGKILLed while an async snapshot may still be mid-write
    — a real engine crash. Run 2 (a fresh process) must restore the
    newest snapshot that VERIFIES (the torn one is skipped by the
    manifest ladder) and finish every stream bitwise."""
    import pathlib

    from distributed_tensorflow_guide_tpu.runtime.multiprocess import (
        MultiProcessRunner,
        run_multiprocess,
    )

    d = str(tmp_path / "snap")
    runner = MultiProcessRunner(
        _target_serve_kill_mid_snapshot, 1, args=(d, "serve"), timeout=120,
    ).start()
    marker = pathlib.Path(d) / "saved_marker"
    deadline = time.time() + 90
    while time.time() < deadline and not marker.exists():
        time.sleep(0.02)
    assert marker.exists(), "run 1 never reached its snapshot point"
    runner.kill(0)  # SIGKILL: no barriers, no atexit — a real engine crash
    results = runner.join(raise_on_error=False)
    assert not results[0].ok

    results = run_multiprocess(_target_serve_kill_mid_snapshot, 1,
                               args=(d, "restore"), timeout=120)
    r = results[0].result
    assert r["label"] is not None  # SOME durable snapshot verified
    prompts = [np.array([3, 5, 7, 9, 11], np.int32),
               np.array([2, 4, 6, 8, 10, 12, 14, 16, 18], np.int32)]
    for i in (0, 1):  # JSON round-trip: rid keys come back as strings
        assert r["completions"][str(i)] == _oracle(
            CFG, params, i, 0.8, 10, prompts=prompts, max_new=[8, 8]), \
            f"req {i} diverged across the kill"

# ---- expert-parallel MoE decode (PR 19) -------------------------------------

MOE_CFG = dataclasses.replace(CFG, moe_experts=4, moe_capacity=2)


@pytest.fixture(scope="module")
def moe_params():
    return Transformer(MOE_CFG).init(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))["params"]


@pytest.mark.parametrize("temp,top_k", [(0.0, None), (0.8, 10)],
                         ids=["greedy", "sampled"])
def test_moe_engine_matches_one_shot_bitwise(moe_params, temp, top_k):
    """The MoE acceptance pin: router dispatch + capacity-bounded expert
    contraction run INSIDE the fixed-slot serve programs, and every
    completed stream still equals the request's solo one-shot run
    exactly. The oracle decodes one token at a time (t=1 <= capacity,
    so it can never overflow); the engine batches slots and may stall —
    parity holding anyway is what degrade-to-overflow promises: a hot
    expert costs TIME, never tokens."""
    eng, _ = _serve(MOE_CFG, moe_params, temp=temp, top_k=top_k, slots=2,
                    num_blocks=33, block_size=8, prefill_chunk=8)
    got = eng.completions()
    for i in range(len(PROMPTS)):
        assert got[i] == _oracle(MOE_CFG, moe_params, i, temp, top_k), \
            f"req {i}"
    assert eng.sched.done == {0, 1, 2}
    eng.sched.pool.check_leaks()
    assert eng.live_blocks() == 0


def test_moe_parity_through_eviction(moe_params):
    """The forced-eviction geometry under the MoE model: preemption,
    continuation re-prefill and capacity stalls compose, and every
    stream still lands bitwise on its one-shot oracle."""
    prompts = [np.array([3, 5, 7, 9, 11], np.int32),
               np.array([2, 4, 6, 8, 10, 12, 14], np.int32)]
    max_new = [40, 40]
    eng, _ = _serve(MOE_CFG, moe_params, temp=0.7, top_k=12,
                    prompts=prompts, max_new=max_new, slots=2,
                    num_blocks=9, block_size=8, prefill_chunk=8)
    assert eng.sched.preemptions >= 1
    got = eng.completions()
    for i in range(2):
        assert got[i] == _oracle(MOE_CFG, moe_params, i, 0.7, 12,
                                 prompts=prompts, max_new=max_new), \
            f"req {i} diverged across eviction"
    eng.sched.pool.check_leaks()


def test_moe_wq8_expert_banks_parity(moe_params):
    """Weight-only int8 expert banks: quantize_params folds the (E, d,
    ff) bank kernels to per-expert qkernel+scale, the engine decodes
    through wq_bank_matmul, and streams still match the one-shot oracle
    running the SAME quantized model bitwise — quantization changes the
    model, never the serving discipline."""
    from distributed_tensorflow_guide_tpu.ops import quant

    wq_cfg = dataclasses.replace(MOE_CFG, weight_dtype="int8")
    wq_params = quant.quantize_params(moe_params, bits=8)
    eng, _ = _serve(wq_cfg, wq_params, temp=0.8, top_k=10, slots=2,
                    num_blocks=33, block_size=8, prefill_chunk=8)
    got = eng.completions()
    for i in range(len(PROMPTS)):
        assert got[i] == _oracle(wq_cfg, wq_params, i, 0.8, 10), f"req {i}"
    eng.sched.pool.check_leaks()
    # the routed banks really are stored int8 (f32 router exempt)
    mlp = wq_params["block_0"]["mlp"]
    assert mlp["w_in"]["qkernel"].dtype == jnp.int8
    assert mlp["w_out"]["qkernel"].dtype == jnp.int8
    router_k = mlp["router"]["kernel"]
    assert getattr(router_k, "value", router_k).dtype == jnp.float32


def test_moe_capacity_degrade_emits_census_and_stalls(moe_params):
    """capacity=1 with two live slots forces contention: the engine must
    report real stalls and overflow WITHOUT corrupting a stream, and the
    per-expert census must balance exactly — every routed token-slot is
    either seated (load) or overflowed (stall + retry), across all
    launches:  sum(load) + sum(overflow) ==
    L * (prompt tokens + (max_new - 1) decode ticks + stalled ticks)."""
    cap1 = dataclasses.replace(CFG, moe_experts=4, moe_capacity=1)
    eng, _ = _serve(cap1, moe_params, temp=0.8, top_k=10, slots=2,
                    num_blocks=33, block_size=8, prefill_chunk=8)
    got = eng.completions()
    for i in range(len(PROMPTS)):
        assert got[i] == _oracle(cap1, moe_params, i, 0.8, 10), f"req {i}"
    moe = eng.health()["moe"]
    assert moe["stall_slot_ticks"] >= 1  # contention really happened
    assert moe["stall_ticks"] >= 1
    # overflow counts per-layer routing events; every stalled slot
    # overflowed in at least one layer
    assert sum(moe["expert_overflow"]) >= moe["stall_slot_ticks"]
    L = cap1.num_layers
    routed = (sum(len(p) for p in PROMPTS)
              + sum(mn - 1 for mn in MAX_NEW)
              + moe["stall_slot_ticks"])
    assert (sum(moe["expert_load"]) + sum(moe["expert_overflow"])
            == L * routed)
    eng.sched.pool.check_leaks()


def test_moe_health_absorbs_into_metrics(moe_params):
    """health()["moe"] -> the declared dtg_moe_* metric names, one
    labeled series per expert (obs/metrics.py absorb_engine)."""
    from distributed_tensorflow_guide_tpu.obs import metrics

    cap1 = dataclasses.replace(CFG, moe_experts=4, moe_capacity=1)
    eng, _ = _serve(cap1, moe_params, temp=0.8, top_k=10, slots=2,
                    num_blocks=33, block_size=8, prefill_chunk=8)
    reg = metrics.Registry()
    metrics.absorb_engine(reg, eng.health())
    text = reg.to_prometheus()
    assert 'dtg_moe_expert_load_total{expert="0"}' in text
    assert 'dtg_moe_expert_overflow_total{expert="3"}' in text
    assert "dtg_moe_stall_slot_ticks_total" in text
    assert "dtg_moe_stall_ticks_total" in text


def test_moe_engine_kill_restore_resumes_bitwise(moe_params, tmp_path):
    """Snapshot/restore under the MoE model: a fresh engine restored
    from the snapshot finishes every stream bitwise (residents
    re-prefill as continuations; the dropless prefill path re-seats
    them without drops), exactly like the dense pin."""
    kw = dict(slots=2, num_blocks=33, block_size=8, prefill_chunk=8,
              temperature=0.8, top_k=10,
              snapshot_dir=str(tmp_path / "snap"))
    eng = ServeEngine(MOE_CFG, moe_params, **kw)
    _submit_all(eng)
    for _ in range(7):
        eng.step()
    label = eng.save_snapshot()
    assert label is not None
    for _ in range(3):
        eng.step()
    pre = eng.completions()
    eng.close()

    eng2 = ServeEngine(MOE_CFG, moe_params, **kw)
    assert eng2.restore_latest_snapshot() == label
    eng2.run()
    got = eng2.completions()
    for i in range(len(PROMPTS)):
        assert got[i] == _oracle(MOE_CFG, moe_params, i, 0.8, 10), \
            f"req {i}"
        assert pre[i] == got[i][:len(pre[i])]
    eng2.sched.pool.check_leaks()
    assert eng2.live_blocks() == 0
    eng2.close()


def test_non_moe_configs_compile_identical_programs(params):
    """The zero-regression gate in miniature: build_step_fns for a
    non-MoE config takes the plain step pair — the jaxprs contain no
    router, no expert contraction, no moe_stats plumbing."""
    fns = build_step_fns(CFG, slots=2, num_blocks=33, block_size=8,
                         prefill_chunk=8)
    assert not fns.moe
    from distributed_tensorflow_guide_tpu.serve.engine import (
        paged_cache_shapes,
    )

    pool = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        paged_cache_shapes(fns.cfg, 2))
    jaxpr = jax.make_jaxpr(fns.decode)(
        params, pool, jnp.zeros((2, fns.n_blk), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 2), jnp.uint32))
    assert "moe" not in str(jaxpr)
