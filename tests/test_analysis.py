"""The contract-linter subsystem (analysis/): walker completeness, the
five rule families each with a deliberately-violating positive control,
registry mechanics, and the --changed-only selection.

The violating programs are the point of the suite: a linter that has
never been seen to FAIL is not evidence of anything. Each rule family
gets a minimal program constructed to break exactly it, and the assertion
is on the specific finding — not just report.ok.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from distributed_tensorflow_guide_tpu.analysis import lint, walker
from distributed_tensorflow_guide_tpu.analysis.contracts import (
    DonationSpec,
    ProgramContract,
    registered_contracts,
)
from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh


# ---- fake-equation shells (the walker duck-types on purpose) ----------------


class _Prim:
    def __init__(self, name):
        self.name = name


class _Eqn:
    def __init__(self, name, params=None, invars=(), outvars=()):
        self.primitive = _Prim(name)
        self.params = dict(params or {})
        self.invars = list(invars)
        self.outvars = list(outvars)


class _Jaxpr:
    def __init__(self, eqns, invars=(), outvars=()):
        self.eqns = list(eqns)
        self.invars = list(invars)
        self.outvars = list(outvars)


def _old_count(jaxpr, name):
    """The pin_utils-era traversal verbatim: tuple/list params only —
    kept here as the negative control for the dict blind spot."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            n += 1
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if hasattr(sub, "eqns"):
                    n += _old_count(sub, name)
    return n


# ---- walker blind-spot positive controls ------------------------------------


def test_walker_sees_subjaxpr_in_dict_valued_eqn_param():
    """A sub-jaxpr carried in a dict param (e.g. a name-keyed branches
    table) is invisible to the old tuple-only loop but found by walk()."""
    inner = _Jaxpr([_Eqn("psum", params={"axes": ("data",)})])
    outer = _Jaxpr([_Eqn("cond_like",
                         params={"branches": {"hot": inner}})])
    assert _old_count(outer, "psum") == 0  # the blind spot, reproduced
    assert walker.count_primitives(outer, "psum") == 1
    assert walker.collective_census(outer)["psum[data]"] == 1


def test_walker_sees_subjaxpr_in_mixed_nested_containers():
    inner = _Jaxpr([_Eqn("ppermute", params={"axis_name": "pipe"})])
    outer = _Jaxpr([_Eqn("call_like",
                         params={"table": ({"k": [inner]},)})])
    assert walker.count_primitives(outer, "ppermute") == 1


def test_input_use_counts_counts_invar_aliasing():
    """dot(x, x) references its input twice in ONE equation — list
    occurrences, not set membership (the invar-aliasing blind spot)."""
    jaxpr = jax.make_jaxpr(lambda x: x @ x)(
        jax.ShapeDtypeStruct((4, 4), jnp.float32))
    assert walker.input_use_counts(jaxpr) == [2]


def test_deep_input_used_resolves_through_call_primitives():
    """An argument that only flows into a pjit whose body ignores it is
    dead; the flat top-level count alone would report it as used."""
    def f(x, y):
        return jax.jit(lambda a, b: a * 2.0)(x, y)

    jaxpr = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((4,), jnp.float32),
        jax.ShapeDtypeStruct((4,), jnp.float32))
    assert walker.deep_input_used(jaxpr) == [True, False]


def test_walk_covers_scan_and_cond_bodies():
    def f(x):
        def body(c, _):
            c = jax.lax.cond(c[0] > 0, jnp.sin, jnp.cos, c)
            return c, None
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    jaxpr = jax.make_jaxpr(f)(jnp.ones((2,)))
    census = walker.primitive_census(jaxpr)
    assert census["sin"] >= 1 and census["cos"] >= 1


# ---- shared harness for the violating programs ------------------------------


def _lint_one(contract):
    report = lint.run_contracts([contract])
    assert len(report.programs) == 1
    return report.programs[0]


def _rule(program_report, name):
    return next(r for r in program_report.rules if r.rule == name)


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---- 1. memory: naive full-logits CE must FAIL ------------------------------


def test_violation_memory_naive_full_logits_ce():
    N, D, V = 32, 16, 128

    def _build():
        t = jnp.zeros((N,), jnp.int32)

        def naive_ce(x, w):
            logits = x @ w  # the (N, V) f32 materialization fused-CE avoids
            lse = jax.nn.logsumexp(logits, axis=-1)
            return jnp.mean(lse - logits[jnp.arange(N), t])

        return naive_ce, (_sds((N, D)), _sds((D, V)))

    bad = ProgramContract(name="viol_naive_ce", build=_build,
                          vocab_dim=V, vocab_rows=2, max_vocab_f32_elems=0,
                          collectives={})
    rep = _lint_one(bad)
    assert not rep.ok
    mem = _rule(rep, "memory")
    assert not mem.ok
    assert mem.observed["vocab_materialized_elems"] >= N * V
    assert any("logits-shaped" in f.message for f in mem.findings)


# ---- 2. precision: f32 matmul / bf16 accumulation under bf16 policy ---------


def test_violation_precision_f32_matmul_under_bf16_policy():
    def _build():
        return (lambda x, w: (x @ w).sum()), (_sds((64, 64)), _sds((64, 64)))

    bad = ProgramContract(name="viol_f32_matmul", build=_build,
                          policy="bf16", collectives={})
    rep = _lint_one(bad)
    prec = _rule(rep, "precision")
    assert prec.observed["bad_operand_matmuls"] >= 1
    assert any("compute dtype" in f.message for f in prec.findings)


def test_violation_precision_bf16_accumulation():
    """bf16 operands WITHOUT preferred_element_type accumulate in bf16 —
    the numerics hazard the policy's accum_dtype=f32 exists to prevent."""
    def _build():
        def f(x, w):
            return jax.lax.dot(x, w)  # no preferred_element_type

        return f, (_sds((8, 128), jnp.bfloat16), _sds((128, 8), jnp.bfloat16))

    bad = ProgramContract(name="viol_bf16_accum", build=_build,
                          policy="bf16", collectives={})
    rep = _lint_one(bad)
    prec = _rule(rep, "precision")
    assert prec.observed["bad_accum_ops"] >= 1
    assert any("preferred_element_type" in f.message for f in prec.findings)


# ---- 3. collectives: stray + miscounted psums -------------------------------


def test_violation_collectives_stray_and_miscounted():
    def _build():
        mesh = build_mesh(MeshSpec(data=-1))

        def body(x):
            x = jax.lax.psum(x, "data")
            x = jax.lax.psum(x, "data")  # one too many
            return jax.lax.pmax(x, "data")  # never declared at all

        fn = shard_map(body, mesh=mesh, in_specs=P("data"),
                       out_specs=P(), check_vma=False)
        return fn, (_sds((8,)),)

    bad = ProgramContract(name="viol_stray_psum", build=_build,
                          collectives={"psum[data]": 1})
    rep = _lint_one(bad)
    coll = _rule(rep, "collectives")
    assert coll.observed["census"]["psum[data]"] == 2
    msgs = [f.message for f in coll.findings]
    assert any("psum[data]: expected 1, traced 2" in m for m in msgs)
    assert any("undeclared collective pmax[data]" in m for m in msgs)


def test_collectives_range_and_census_only_modes():
    def _build():
        mesh = build_mesh(MeshSpec(data=-1))
        fn = shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                       in_specs=P("data"), out_specs=P(), check_vma=False)
        return fn, (_sds((8,)),)

    ranged = ProgramContract(name="ok_range", build=_build,
                             collectives={"psum[data]": (1, 2)})
    assert _lint_one(ranged).ok
    census_only = ProgramContract(name="ok_census", build=_build,
                                  collectives=None)
    assert _lint_one(census_only).ok


# ---- 4. donation: dropped alias / dead buffer / double reference ------------


def test_violation_donation_dropped_no_matching_output():
    def _build():
        return (lambda s: jnp.sum(s)), (_sds((16, 16)),)

    bad = ProgramContract(name="viol_dropped_donation", build=_build,
                          collectives={},
                          donation=DonationSpec(argnums=(0,)))
    rep = _lint_one(bad)
    don = _rule(rep, "donation")
    assert don.observed["alias_unmatched"] == 1
    assert any("no matching output" in f.message for f in don.findings)


def test_violation_donation_dead_buffer():
    def _build():
        return (lambda x, y: jnp.sin(y)), (_sds((8,)), _sds((8,)))

    bad = ProgramContract(name="viol_dead_donation", build=_build,
                          collectives={},
                          donation=DonationSpec(argnums=(0,),
                                                mode="scratch"))
    rep = _lint_one(bad)
    assert any("dead donation" in f.message
               for f in _rule(rep, "donation").findings)


def test_violation_donation_double_reference():
    def _build():
        return (lambda x: x @ x), (_sds((4, 4)),)

    bad = ProgramContract(name="viol_double_ref", build=_build,
                          collectives={},
                          donation=DonationSpec(argnums=(0,)))
    rep = _lint_one(bad)
    assert any("referenced 2x" in f.message
               for f in _rule(rep, "donation").findings)


# ---- 5. determinism: host callback inside the step --------------------------


def test_violation_determinism_debug_callback_in_step():
    def _build():
        def f(x):
            jax.debug.print("step {}", x[0])
            return x * 2.0

        return f, (_sds((4,)),)

    bad = ProgramContract(name="viol_callback", build=_build,
                          collectives={})
    rep = _lint_one(bad)
    det = _rule(rep, "determinism")
    assert det.observed["hits"].get("debug_callback", 0) >= 1
    assert not det.ok
    # the same program with the callback allow-listed passes
    ok = ProgramContract(name="ok_callback", build=_build, collectives={},
                         allowed_callbacks=("debug_callback",))
    assert _rule(_lint_one(ok), "determinism").ok


# ---- linter mechanics -------------------------------------------------------


def test_broken_build_fails_lint_not_crashes():
    def _build():
        raise RuntimeError("fixture exploded")

    rep = _lint_one(ProgramContract(name="viol_broken", build=_build))
    assert not rep.ok and "fixture exploded" in rep.error


def test_registry_has_all_shipped_programs_and_they_pass():
    """The acceptance pin: >= 8 registered programs, and the cheapest two
    actually lint clean in-process (the full registry runs in the
    bench_lint SMOKE subprocess — and, standalone, via dtg-lint)."""
    contracts = lint._registered(None)
    names = [c.name for c in contracts]
    assert len(names) == len(set(names)) >= 8
    for expected in ("dp_train_step", "fsdp_prefetch_train_step",
                     "pipeline_fused_ce_train_step", "fused_ce_loss_grad",
                     "decode_step", "multislice_outer_off_round"):
        assert expected in names
    small = lint.run_contracts(registered_contracts(
        ("dp_train_step", "fused_ce_loss_grad")))
    assert small.ok, lint.render_text(small)


def test_unknown_program_name_is_an_error():
    lint._registered(None)  # ensure providers registered
    with pytest.raises(KeyError, match="no_such_program"):
        registered_contracts(("no_such_program",))


def test_report_json_roundtrip_and_render():
    def _build():
        return (lambda x: x * 2.0), (_sds((4,)),)

    rep = lint.run_contracts([
        ProgramContract(name="ok_tiny", build=_build, collectives={})])
    d = json.loads(json.dumps(rep.to_dict()))
    assert d["ok"] and d["n_programs"] == 1 and d["n_findings"] == 0
    text = lint.render_text(rep)
    assert "PASS" in text and "ok_tiny" in text


def test_changed_only_selection(monkeypatch):
    a = ProgramContract(
        name="sel_a", build=lambda: None,
        sources=("distributed_tensorflow_guide_tpu.parallel.fsdp",))
    b = ProgramContract(
        name="sel_b", build=lambda: None,
        sources=("distributed_tensorflow_guide_tpu.ops.fused_ce",))

    monkeypatch.setattr(
        lint, "_changed_files",
        lambda base: ["distributed_tensorflow_guide_tpu/parallel/fsdp.py"])
    picked, why = lint.select_changed([a, b], "HEAD")
    assert [c.name for c in picked] == ["sel_a"] and "1 changed" in why

    # any analysis/-layer edit re-lints everything
    monkeypatch.setattr(
        lint, "_changed_files",
        lambda base: ["distributed_tensorflow_guide_tpu/analysis/rules.py"])
    assert len(lint.select_changed([a, b], "HEAD")[0]) == 2

    # unreadable git falls back to the full audit, not a vacuous pass
    monkeypatch.setattr(lint, "_changed_files", lambda base: None)
    picked, why = lint.select_changed([a, b], "HEAD")
    assert len(picked) == 2 and "full lint" in why

    # benchmarks/common.py holds the closed-form byte/FLOP models every
    # CostSpec pin is checked against: editing it invalidates EVERY pin,
    # so --changed-only must widen to the full registry, not just the
    # programs whose own sources changed
    monkeypatch.setattr(
        lint, "_changed_files", lambda base: ["benchmarks/common.py"])
    picked, why = lint.select_changed([a, b], "HEAD")
    assert len(picked) == 2
    assert why == "benchmarks/common.py changed -> full lint"


def test_walker_traced_text_normalizes_addresses():
    text = walker.traced_text(lambda x: x + 1.0, np.zeros((2,), np.float32))
    assert "add" in text and "0x" not in text.replace("0x•", "")
