"""Fleet tier (serve/fleet.py): the serving acceptance pin, fleet-wide —
every stream routed anywhere, migrated prefill->decode mid-flight, or
re-anchored through a replica loss must be bitwise identical to a
one-shot ``make_generate_fn`` run of that request alone.  Plus the
global invariants the placement tier owns: per-tenant conservation as a
disjoint sum across replicas (migration never double-counts), the
fleet-door shed gate staying retriable, prefix routing concentrating
locality on the warm replica, the closed-form byte model of the KV
migration path, and a joint ``check_leaks()`` over every replica's
ledgers.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import kv_migration_bytes, spill_bytes_per_swap
from distributed_tensorflow_guide_tpu.models.generation import (
    make_generate_fn,
)
from distributed_tensorflow_guide_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
)
from distributed_tensorflow_guide_tpu.serve import (
    EngineOverloaded,
    FleetScheduler,
    Request,
)
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

#: CFG serves f32 KV (itemsize 4) with head_dim = d_model / num_heads = 8
_PER_BLOCK = spill_bytes_per_swap(CFG.num_layers, CFG.num_heads, 8,
                                  CFG.d_model // CFG.num_heads,
                                  activation_dtype_bytes=4)


@pytest.fixture(scope="module")
def params():
    return Transformer(CFG).init(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))["params"]


_ORACLE_CACHE: dict = {}  # every make_generate_fn call is a fresh compile


def _oracle(cfg, params, i, temp, top_k, *, prompts=PROMPTS,
            max_new=MAX_NEW):
    """The one-shot stream request ``i`` must reproduce bitwise (the
    test_serving.py memoized oracle, same keys, same seeds)."""
    p, mn = prompts[i], max_new[i]
    key = (repr(cfg), i, temp, top_k, tuple(p.tolist()), mn)
    if key not in _ORACLE_CACHE:
        gen = make_generate_fn(cfg, max_new_tokens=mn, temperature=temp,
                               top_k=top_k)
        out = gen(params, p[None], jax.random.PRNGKey(100 + i))
        _ORACLE_CACHE[key] = np.asarray(out)[0, len(p):].tolist()
    return list(_ORACLE_CACHE[key])


def _fleet(params, *, temp=0.0, top_k=None, **kw):
    kw.setdefault("replicas", 2)
    kw.setdefault("slots", 2)
    kw.setdefault("num_blocks", 33)
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return FleetScheduler(CFG, params, temperature=temp, top_k=top_k,
                          **kw)


def _submit_all(fl, prompts=PROMPTS, max_new=MAX_NEW):
    for i, (p, mn) in enumerate(zip(prompts, max_new)):
        fl.submit(Request(rid=i, prompt=p, max_new_tokens=mn,
                          rng=jax.random.PRNGKey(100 + i), tenant=i % 2))


# ---- the acceptance pin, fleet-wide ----------------------------------------


@pytest.mark.parametrize("temp,top_k", [(0.0, None), (0.8, 10)],
                         ids=["greedy", "sampled"])
def test_fleet_matches_one_shot_bitwise(params, temp, top_k):
    """Two colocated replicas behind the global DRR door: every stream,
    wherever routed, equals that request's solo one-shot run exactly —
    position-derived sampling keys make the placement invisible."""
    fl = _fleet(params, temp=temp, top_k=top_k)
    _submit_all(fl)
    events = fl.run()
    got = fl.completions()
    for i in range(len(PROMPTS)):
        assert got[i] == _oracle(CFG, params, i, temp, top_k), f"req {i}"
    assert sorted(e.rid for e in events if e.done) == [0, 1, 2]
    h = fl.health()
    assert h["completed"] == 3 and h["queued"] == 0
    # both replicas actually served (least-loaded routing spreads 3
    # requests over 2 replicas — neither side idles)
    assert all(r["completed"] >= 1 for r in h["replicas"])
    sig = fl.autoscale_signal()
    assert sig["goodput_tokens"] == sum(MAX_NEW)
    assert not sig["want_more_replicas"]
    fl.check_leaks()
    fl.close()


# ---- one replica, one chip (PR 21) -------------------------------------------


def test_replica_devices_one_chip_each_and_cpu_left_alone():
    from types import SimpleNamespace

    from distributed_tensorflow_guide_tpu.serve.fleet import replica_devices

    chips = [SimpleNamespace(platform="tpu", id=i) for i in range(4)]
    assert replica_devices(4, chips) == chips
    assert [d.id for d in replica_devices(6, chips)] == [0, 1, 2, 3, 0, 1]
    # virtual CPU devices: one host's cores, a compile per device
    assert replica_devices(3) == [None, None, None]


def test_a_replica_lives_where_its_params_live(params, devices):
    """Per-replica params placed on two different devices: each engine's
    pool is allocated beside its weights and stays there through serving,
    and the streams are the ones the one-shot path gives — this is what
    puts four fleet replicas on four chips."""
    placed = [jax.device_put(params, d) for d in devices[1:3]]
    fl = _fleet(placed)
    assert [e.device for e in fl.engines] == list(devices[1:3])
    _submit_all(fl)
    fl.run()
    for eng, dev in zip(fl.engines, devices[1:3]):
        assert eng.health()["completed"] >= 1
        assert all(leaf.devices() == {dev}
                   for leaf in jax.tree.leaves(eng.pool))
    got = fl.completions()
    for i in range(len(PROMPTS)):
        assert got[i] == _oracle(CFG, params, i, 0.0, None), f"req {i}"
    fl.check_leaks()
    fl.close()


@pytest.mark.parametrize("temp,top_k", [(0.0, None), (0.8, 10)],
                         ids=["greedy", "sampled"])
def test_disagg_migration_is_bitwise(params, temp, top_k):
    """Disaggregated roles: every stream prefills on the prefill
    replica, ships its KV blocks at the phase flip, and finishes on the
    decode replica — and still continues bitwise (migration ships the
    same bytes the source wrote; sampling keys derive from position)."""
    fl = _fleet(params, temp=temp, top_k=top_k, roles="disagg")
    _submit_all(fl)
    fl.run()
    got = fl.completions()
    for i in range(len(PROMPTS)):
        assert got[i] == _oracle(CFG, params, i, temp, top_k), f"req {i}"
    # every stream has budget left at its phase flip, so all 3 migrate —
    # exactly once each (the rid list is the bench's bitwise audit set)
    assert fl.migrations == 3
    assert sorted(fl.migrated_rids) == [0, 1, 2]
    assert fl.migration_bytes > 0
    h = fl.health()
    roles = {r["role"]: r for r in h["replicas"]}
    assert roles["prefill"]["migrated_out"] == 3
    assert roles["decode"]["migrated_in"] == 3
    assert roles["prefill"]["completed"] == 0
    assert roles["decode"]["completed"] == 3
    fl.check_leaks()
    fl.close()


# ---- chaos: storms at both roles, replica loss/regrow ----------------------


def test_migration_under_chaos_zero_dropped_streams(params):
    """Serve-storm kinds firing at BOTH roles (launch failures and pool
    pressure on the prefill side, the same mid-decode on the decode
    side): the storms are invisible — zero dropped streams, every
    completion bitwise, every migration still accounted."""
    chaos = [
        FaultSchedule([Fault("serve_step_exception", 2),
                       Fault("pool_pressure", 4, 4.0)]),   # prefill role
        FaultSchedule([Fault("serve_step_exception", 3),
                       Fault("pool_pressure", 6, 4.0)]),   # decode role
    ]
    fl = _fleet(params, temp=0.8, top_k=10, roles="disagg", chaos=chaos)
    _submit_all(fl)
    fl.run()
    got = fl.completions()
    for i in range(len(PROMPTS)):
        assert got[i] == _oracle(CFG, params, i, 0.8, 10), f"req {i}"
    for c in chaos:
        assert c.serve_events() == []  # every scheduled fault absorbed
        assert len(c.fired) == 2
    assert fl.migrations >= 1
    fl.check_leaks()
    fl.close()


def test_replica_loss_and_regrow_keeps_streams_and_drr(params):
    """Elastic capacity: a ``slice_loss`` mid-flight sheds a replica
    (its live streams re-anchor through the fleet queue and re-prefill
    elsewhere, KV lost with the replica), a later ``slice_return``
    reabsorbs it cold — every stream still completes bitwise and the
    GLOBAL per-tenant ledger stays a conserved disjoint sum."""
    world = FaultSchedule([Fault("slice_loss", 2, 1.0),
                           Fault("slice_return", 6, 1.0)])
    fl = _fleet(params, world_chaos=world)
    _submit_all(fl)
    fl.run()
    got = fl.completions()
    for i in range(len(PROMPTS)):
        assert got[i] == _oracle(CFG, params, i, 0.0, None), f"req {i}"
    assert world.world_events() == []
    h = fl.health()
    assert h["replicas_shed"] == 1 and h["replicas_regrown"] == 1
    assert h["generation"] == 2 and h["live_replicas"] == 2
    assert [t["kind"] for t in fl.timeline] == ["slice_loss",
                                                "slice_return"]
    # the loss-window autoscale signal asked for capacity back
    assert fl.timeline[0]["signal"]["want_more_replicas"]
    # global conservation: submitted once at first dispatch, terminal
    # status once where the stream ended — re-anchoring re-counts nothing
    assert h["tenants"][0]["submitted"] == 2 == h["tenants"][0]["done"]
    assert h["tenants"][1]["submitted"] == 1 == h["tenants"][1]["done"]
    assert fl._deficit == {}  # DRR state drains with the queue
    fl.check_leaks()
    fl.close()


# ---- per-tenant conservation through migration -----------------------------


def test_tenant_conservation_through_migration(params):
    """The health() aggregation is a disjoint sum across replicas:
    submitted == done per tenant even though every stream submitted on
    the prefill replica and finished on the decode replica, and each
    migration shows up as exactly one source-side preemption."""
    fl = _fleet(params, roles="disagg")
    _submit_all(fl)
    fl.run()
    h = fl.health()
    for t, c in h["tenants"].items():
        assert c["submitted"] == c["done"], f"tenant {t}: {c}"
        assert c["shed"] == c["cancelled"] == c["expired"] == 0
    assert sum(c["submitted"] for c in h["tenants"].values()) == 3
    # detach-at-export bumps the source tenant's preempted counter:
    # migrations and preemptions reconcile exactly in a pressure-free run
    assert sum(c["preempted"]
               for c in h["tenants"].values()) == fl.migrations
    assert fl.migrations == 3
    fl.check_leaks()
    fl.close()


# ---- the fleet door --------------------------------------------------------


def test_fleet_door_sheds_retriably(params):
    """The GLOBAL queue-depth gate: the overflow submit raises
    EngineOverloaded without recording the request anywhere, the shed is
    counted fleet-side under the tenant, and a later resubmit of the
    same request completes bitwise."""
    fl = _fleet(params, max_queue=2)
    fl.submit(Request(rid=0, prompt=PROMPTS[0], max_new_tokens=MAX_NEW[0],
                      rng=jax.random.PRNGKey(100), tenant=0))
    fl.submit(Request(rid=1, prompt=PROMPTS[1], max_new_tokens=MAX_NEW[1],
                      rng=jax.random.PRNGKey(101), tenant=1))
    with pytest.raises(EngineOverloaded):
        fl.submit(Request(rid=2, prompt=PROMPTS[2],
                          max_new_tokens=MAX_NEW[2],
                          rng=jax.random.PRNGKey(102), tenant=0))
    assert fl.shed == 1
    fl.run()
    # the door reopens once the queue drains; the retry is a fresh
    # submit, bitwise-identical to a never-shed run
    fl.submit(Request(rid=2, prompt=PROMPTS[2], max_new_tokens=MAX_NEW[2],
                      rng=jax.random.PRNGKey(102), tenant=0))
    fl.run()
    got = fl.completions()
    for i in range(len(PROMPTS)):
        assert got[i] == _oracle(CFG, params, i, 0.0, None), f"req {i}"
    h = fl.health()
    assert h["shed"] == 1
    assert h["tenants"][0]["shed"] == 1  # the fleet-door shed, by tenant
    assert h["tenants"][0]["submitted"] == 2  # rid 2 counted ONCE, on retry
    fl.check_leaks()
    fl.close()


# ---- fleet-level prefix routing --------------------------------------------


def test_prefix_routing_routes_to_warm_replica(params):
    """A request whose prompt shares a cached prefix routes to the
    replica already holding it (probed against each candidate's radix
    trie) instead of the least-loaded one — locality concentrates, and
    the COW reuse is still bitwise."""
    sys_p = (np.arange(16, dtype=np.int32) % 61) + 1
    prompts = [np.concatenate([sys_p, np.array([33, 34, 35, 36],
                                               np.int32)]),
               np.concatenate([sys_p, np.array([40, 41, 42, 43],
                                               np.int32)])]
    max_new = [6, 6]
    fl = _fleet(params, prefix_cache=True)
    fl.submit(Request(rid=0, prompt=prompts[0], max_new_tokens=6,
                      rng=jax.random.PRNGKey(100)))
    fl.run()
    assert fl.prefix_route_hits == 0  # cold fleet: nothing to match yet
    fl.submit(Request(rid=1, prompt=prompts[1], max_new_tokens=6,
                      rng=jax.random.PRNGKey(101)))
    fl.run()
    assert fl.prefix_route_hits == 1
    assert fl.prefix_route_hit_tokens >= 8  # >= one full cached block
    # both requests landed on the SAME replica — the warm one
    homes = [[i for i, eng in enumerate(fl.engines)
              if rid in eng.completions()] for rid in (0, 1)]
    assert homes[0] == homes[1] and len(homes[0]) == 1
    got = fl.completions()
    for i in (0, 1):
        assert got[i] == _oracle(CFG, params, i, 0.0, None,
                                 prompts=prompts, max_new=max_new)
    fl.check_leaks()
    fl.close()


# ---- the migration byte model ----------------------------------------------


def test_migration_bytes_match_closed_form(params):
    """The traced ``migration_bytes`` counter equals the closed form
    (blocks x the spill-tier per-block payload — migration and demotion
    share the fused d2h gather), and equals the decode side's swap-in
    traffic: every shipped block lands in the host store and swaps in
    exactly once."""
    fl = _fleet(params, roles="disagg")
    _submit_all(fl)
    fl.run()
    mb = fl.migration_bytes
    assert mb > 0 and mb % _PER_BLOCK == 0
    n_blocks = int(mb // _PER_BLOCK)
    assert mb == kv_migration_bytes(
        n_blocks, CFG.num_layers, CFG.num_heads, 8,
        CFG.d_model // CFG.num_heads, activation_dtype_bytes=4)
    h = fl.health()
    decode = [r for r in h["replicas"] if r["role"] == "decode"]
    assert sum(r["spill_in_blocks"] for r in decode) == n_blocks
    assert sum(r["spill_h2d_bytes"] for r in decode) == mb
    fl.check_leaks()
    fl.close()


# ---- construction contracts (no engines built on a bad config) -------------


def test_fleet_config_validation(params):
    with pytest.raises(ValueError, match="replicas must be >= 1"):
        _fleet(params, replicas=0)
    with pytest.raises(ValueError, match="disagg needs >= 2"):
        _fleet(params, replicas=1, roles="disagg")
    with pytest.raises(ValueError, match="come as a pair"):
        _fleet(params, replicas=2, roles=["prefill", "prefill"])
    with pytest.raises(ValueError, match="roles length"):
        _fleet(params, replicas=2, roles=["colocated"])
    with pytest.raises(ValueError, match="unknown role"):
        _fleet(params, replicas=2, roles=["colocated", "verifier"])
    with pytest.raises(ValueError, match="prefix_routing needs"):
        _fleet(params, prefix_routing=True)
    fl = _fleet(params)
    with pytest.raises(ValueError, match="empty prompt"):
        fl.submit(Request(rid=0, prompt=np.array([], np.int32),
                          max_new_tokens=4, rng=jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="out of vocabulary"):
        fl.submit(Request(rid=0, prompt=np.array([99], np.int32),
                          max_new_tokens=4, rng=jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="exceeds max_len"):
        fl.submit(Request(rid=0, prompt=PROMPTS[2], max_new_tokens=63,
                          rng=jax.random.PRNGKey(0)))
    fl.close()


# ---- autoscale policy (PR 19): advisory target-replica recommendation ------


def test_autoscale_policy_hysteresis_bounds_and_health(params):
    """autoscale_signal -> target-replica recommendation: the signal
    must lean the same way for ``hysteresis`` consecutive evaluations
    before the target moves (by one), the target clamps to
    [min_replicas, max_replicas], and the whole thing is ADVISORY —
    the fleet's live set never changes. Surfaced in health()."""
    fl = _fleet(params)

    # idle fleet, empty queue: pressure 0 leans scale-down, but the
    # target holds at live until the streak reaches the hysteresis
    p = fl.autoscale_policy()
    assert p["direction"] == -1 and p["streak"] == 1
    assert p["target_replicas"] == 2  # no move yet
    assert fl.autoscale_policy()["target_replicas"] == 2
    p = fl.autoscale_policy()
    assert p["streak"] == 3 and p["target_replicas"] == 1
    # the min bound overrides a mature scale-down streak
    assert fl.autoscale_policy(min_replicas=2)["target_replicas"] == 2

    # queue pressure: 9 queued over 2x2 capacity leans scale-up; the
    # direction flip resets the streak, so again no move until 3 in a
    # row, and the default max bound is the PROVISIONED width (2)
    for i in range(9):
        fl.submit(Request(rid=100 + i, prompt=PROMPTS[0],
                          max_new_tokens=4,
                          rng=jax.random.PRNGKey(i)))
    p = fl.autoscale_policy()
    assert p["direction"] == 1 and p["streak"] == 1
    assert p["target_replicas"] == 2
    fl.autoscale_policy()
    assert fl.autoscale_policy()["target_replicas"] == 2  # clamped
    # with headroom granted, the mature streak recommends ONE more
    p = fl.autoscale_policy(max_replicas=4)
    assert p["target_replicas"] == 3
    assert p["signal"]["pressure"] > 1.0

    # advisory only: nothing above touched the live set
    assert len(fl._live) == 2
    h = fl.health()
    assert h["autoscale"]["target_replicas"] >= 2
    assert h["autoscale"]["signal"]["queued"] == 9

    with pytest.raises(ValueError, match="min_replicas"):
        fl.autoscale_policy(min_replicas=0)
    with pytest.raises(ValueError, match="max_replicas"):
        fl.autoscale_policy(min_replicas=2, max_replicas=1)
    fl.run()
    fl.check_leaks()
    fl.close()
