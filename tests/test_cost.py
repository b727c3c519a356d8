"""The static cost auditor (analysis/cost.py + fingerprint.py): the
interpreter's arithmetic on known programs, the ring collective model,
donation-aware liveness, and — the point of the suite — every new
failure mode demonstrated to actually FAIL: a peak-live budget blown, a
byte model drifted beyond tolerance, a dead donation charged as live,
and a fingerprint mutated without a bless. Each assertion lands on the
specific finding or drift line, not just report.ok.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from distributed_tensorflow_guide_tpu.analysis import cost, fingerprint, lint
from distributed_tensorflow_guide_tpu.analysis.contracts import (
    CostPin,
    CostSpec,
    DonationSpec,
    ProgramContract,
)
from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh


def _lint_one(contract):
    report = lint.run_contracts([contract])
    assert len(report.programs) == 1
    return report.programs[0]


def _cost_rule(program_report):
    return next(r for r in program_report.rules if r.rule == "cost")


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _vec(fn, *args, contract=None):
    contract = contract or ProgramContract(name="probe", build=lambda: None)
    traced_jaxpr = jax.make_jaxpr(fn)(*args)

    class _T:
        jaxpr = traced_jaxpr
        arg_leaf_avals = [[a] for a in args]

    return cost.program_cost(_T(), contract)


# ---- interpreter arithmetic on known programs -------------------------------


def test_matmul_flops_and_fusion_boundary_bytes():
    """(8,16)@(16,4) f32: FLOPs = 2*m*k*n; HBM = operands read once,
    output written once; peak = both inputs + the output live together."""
    vec = _vec(lambda x, w: x @ w, _sds((8, 16)), _sds((16, 4)))
    assert vec.flops == 2 * 8 * 16 * 4
    assert vec.hbm_bytes_read == (8 * 16 + 16 * 4) * 4
    assert vec.hbm_bytes_written == 8 * 4 * 4
    assert vec.peak_live_bytes == (8 * 16 + 16 * 4 + 8 * 4) * 4


def test_elementwise_chain_is_fusion_free():
    """tanh/add/mul chains charge ZERO HBM traffic (XLA fuses them) —
    the convention that makes the derived numbers comparable to the
    minimal-traffic closed forms in benchmarks/common.py."""
    vec = _vec(lambda x: jnp.tanh(x * 2.0) + 1.0, _sds((128,)))
    assert vec.flops == 0
    assert vec.hbm_bytes == 0


def test_scan_trip_count_multiplies_body_cost():
    def stepper(c, _):
        return jnp.tanh(c @ c), None

    def fn(c):
        out, _ = jax.lax.scan(stepper, c, None, length=5)
        return out

    vec = _vec(fn, _sds((4, 4)))
    assert vec.flops == 5 * (2 * 4 * 4 * 4)


def test_collective_bytes_ring_model():
    """psum inside shard_map prices at the ring closed form:
    2 * P * (n-1)/n per device, keyed by the census spelling."""
    mesh = build_mesh(MeshSpec(data=-1))
    n = np.prod(list(mesh.shape.values()))

    def step(x):
        return jax.lax.psum(x, "data")

    fn = shard_map(step, mesh=mesh, in_specs=P("data"), out_specs=P())
    per_device = 64 // n
    vec = _vec(fn, _sds((64,)))
    want = 2.0 * (per_device * 4) * (n - 1) / n
    assert vec.collective_bytes == {"psum[data]": want}
    assert vec.quantity("collective_bytes[psum[data]]") == want
    # absent keys resolve to 0.0 — the exact-zero pin mechanism the
    # multislice outer=off contract uses
    assert vec.quantity("collective_bytes[psum[dcn]]") == 0.0


# ---- donation-aware liveness ------------------------------------------------


def test_donated_and_used_input_dies_at_last_use():
    """After `big`'s last use, a donated buffer frees — so the peak over
    the later phase drops by exactly big's bytes vs the undonated run."""

    def fn(big, x):
        h = jnp.sum(big) + x          # big's last (only) use
        # the post-use phase (two 16 KiB tensors) dwarfs big's 4 KiB, so
        # the peak lands AFTER big dies and the donated-vs-not delta is
        # exactly big's footprint
        return jnp.concatenate([x] * 512) * h[:1]

    jaxpr = jax.make_jaxpr(fn)(_sds((1024,)), _sds((8,)))
    donated = cost.peak_live_bytes(jaxpr, donated_flat=frozenset({0}))
    undonated = cost.peak_live_bytes(jaxpr, donated_flat=frozenset())
    assert undonated - donated == 1024 * 4


def test_dead_donation_stays_live():
    """A donated-but-NEVER-READ input cannot alias anything: XLA drops
    the donation and the buffer sits allocated for the whole program —
    the auditor charges it as live, so donating it buys nothing."""

    def fn(big, x):
        return x * 2.0                # big is dead

    jaxpr = jax.make_jaxpr(fn)(_sds((1024,)), _sds((8,)))
    dead_donated = cost.peak_live_bytes(jaxpr, donated_flat=frozenset({0}))
    undonated = cost.peak_live_bytes(jaxpr, donated_flat=frozenset())
    assert dead_donated == undonated
    assert dead_donated >= 1024 * 4


def test_alias_donation_zeroes_passthrough_copy():
    """A state->state passthrough output costs a defensive copy UNLESS
    its input is donated in alias mode — the visible byte delta between
    donate=True and donate=False on the same train step."""

    def fn(state, x):
        return state, jnp.sum(x)

    def contract(donation):
        return ProgramContract(name="p", build=lambda: None,
                               donation=donation)

    aliased = _vec(fn, _sds((256,)), _sds((8,)),
                   contract=contract(DonationSpec(argnums=(0,))))
    copied = _vec(fn, _sds((256,)), _sds((8,)), contract=contract(None))
    assert copied.hbm_bytes - aliased.hbm_bytes == 2 * 256 * 4  # r + w


# ---- failure modes: each must produce its specific finding ------------------


def _matmul_contract(name, cost_spec):
    def _build():
        return (lambda x, w: x @ w), (_sds((8, 16)), _sds((16, 4)))

    return ProgramContract(name=name, build=_build, collectives={},
                           cost=cost_spec)


def test_peak_live_over_budget_fails():
    rep = _lint_one(_matmul_contract(
        "viol_peak", CostSpec(max_peak_live_bytes=100)))
    assert not rep.ok
    [finding] = _cost_rule(rep).findings
    assert "peak live bytes" in finding.message
    assert "over the declared" in finding.message
    assert finding.observed == (8 * 16 + 16 * 4 + 8 * 4) * 4


def test_byte_model_mismatch_beyond_tolerance_fails():
    rep = _lint_one(_matmul_contract(
        "viol_bytes", CostSpec(pins=(
            CostPin("hbm_bytes", 999_999.0, rel_tol=0.01,
                    note="deliberately wrong closed form"),))))
    assert not rep.ok
    [finding] = _cost_rule(rep).findings
    assert "hbm_bytes drifted from the closed-form model" in finding.message
    assert "deliberately wrong closed form" in finding.message
    assert finding.observed == (8 * 16 + 16 * 4 + 8 * 4) * 4


def test_exact_and_tolerant_pins_pass():
    """Positive control: exact pins on the derived numbers, a callable
    expectation (the closed-form-lambda mechanism the providers use),
    and a tolerant pin just inside its band."""
    rep = _lint_one(_matmul_contract(
        "ok_pins", CostSpec(pins=(
            CostPin("flops", 2 * 8 * 16 * 4),
            CostPin("hbm_bytes_written", lambda: 8 * 4 * 4),
            CostPin("flops", 2 * 8 * 16 * 4 * 1.05, rel_tol=0.1),),
            max_peak_live_bytes=4096)))
    assert rep.ok, [f.message for r in rep.rules for f in r.findings]


def test_uninterpretable_trace_with_pins_fails_without_pins_observes():
    """Interpreter crash semantics: observe-only when the contract pins
    nothing (fake-jaxpr micro-programs), a FAIL finding when a CostSpec
    declared numbers it now cannot verify."""

    class _Boom:
        def __getattr__(self, name):
            raise RuntimeError("not a jaxpr")

    from distributed_tensorflow_guide_tpu.analysis import rules

    traced = rules.TracedProgram(name="x", jaxpr=_Boom(),
                                 arg_leaf_avals=[])
    observe = rules.rule_cost(traced, ProgramContract(
        name="x", build=lambda: None))
    assert observe.ok and "error" in observe.observed

    pinned = rules.rule_cost(traced, ProgramContract(
        name="x", build=lambda: None,
        cost=CostSpec(pins=(CostPin("flops", 1.0),))))
    assert not pinned.ok
    assert "cost interpreter failed" in pinned.findings[0].message


# ---- fingerprints: drift gates, bless path ----------------------------------


def test_fingerprint_drift_without_bless_then_bless(tmp_path):
    golden = tmp_path / "goldens.json"

    def contract(scale):
        def _build():
            return (lambda x: x * scale), (_sds((4,)),)

        return ProgramContract(name="fp_prog", build=_build, collectives={})

    rep1 = lint.run_contracts([contract(2.0)])
    lint.bless_fingerprints(rep1, "initial", golden_path=golden)
    lint.check_fingerprints(rep1, full_registry=False, golden_path=golden)
    assert rep1.fingerprint_drift == [] and rep1.ok

    # mutate the program (2.0 -> 3.0): structure hash moves; the SAME
    # goldens must now flag drift and flip the report to FAIL
    rep2 = lint.run_contracts([contract(3.0)])
    lint.check_fingerprints(rep2, full_registry=False, golden_path=golden)
    assert rep2.fingerprint_drift and not rep2.ok
    assert any("fp_prog" in line and "structure" in line
               for line in rep2.fingerprint_drift)

    # the bless path: rewrite goldens with a reason, drift clears
    lint.bless_fingerprints(rep2, "intentional retrace", golden_path=golden)
    goldens = fingerprint.load_goldens(golden)
    assert goldens["fp_prog"]["reason"] == "intentional retrace"
    rep3 = lint.run_contracts([contract(3.0)])
    lint.check_fingerprints(rep3, full_registry=False, golden_path=golden)
    assert rep3.fingerprint_drift == [] and rep3.ok


def test_cost_only_drift_is_caught(tmp_path):
    """Same structure hash, different cost vector (a pure-cost change,
    e.g. an aval growing) must still drift — the fingerprint is the
    PAIR, not just the normalized jaxpr text."""
    golden = tmp_path / "goldens.json"
    rep = lint.run_contracts([ProgramContract(
        name="cv_prog", collectives={},
        build=lambda: ((lambda x: x @ x), (_sds((4, 4)),)))])
    lint.bless_fingerprints(rep, "initial", golden_path=golden)

    fp = rep.programs[0].fingerprint
    mutated = fingerprint.Fingerprint(
        program=fp.program, structure=fp.structure,
        cost=dict(fp.cost, flops=fp.cost["flops"] + 1))
    lines = fingerprint.diff_fingerprint(
        mutated, fingerprint.load_goldens(golden))
    assert lines and any("flops" in line for line in lines)


def test_bless_refuses_failing_registry(tmp_path):
    rep = lint.run_contracts([_matmul_contract(
        "viol_refuse", CostSpec(max_peak_live_bytes=1))])
    with pytest.raises(RuntimeError, match="refusing to bless"):
        lint.bless_fingerprints(rep, "nope",
                                golden_path=tmp_path / "g.json")


def test_stale_golden_flagged_on_full_registry(tmp_path):
    """A golden whose program no longer exists is drift on a full run
    (deleting a judged program silently would un-gate it forever)."""
    golden = tmp_path / "goldens.json"
    rep = lint.run_contracts([ProgramContract(
        name="live_prog", collectives={},
        build=lambda: ((lambda x: x + 1.0), (_sds((4,)),)))])
    lint.bless_fingerprints(rep, "initial", golden_path=golden)
    ghost = fingerprint.Fingerprint(program="ghost_prog",
                                    structure="0" * 64, cost={})
    fingerprint.save_goldens(
        [rep.programs[0].fingerprint, ghost], "adds ghost", path=golden)

    lint.check_fingerprints(rep, full_registry=True, golden_path=golden)
    assert any("ghost_prog" in line for line in rep.fingerprint_drift)
    # partial runs (--programs) must NOT flag it: absence is not evidence
    rep2 = lint.run_contracts([ProgramContract(
        name="live_prog", collectives={},
        build=lambda: ((lambda x: x + 1.0), (_sds((4,)),)))])
    lint.check_fingerprints(rep2, full_registry=False, golden_path=golden)
    assert rep2.fingerprint_drift == []


def test_shipped_goldens_match_registry_names():
    """The committed golden file covers exactly the registered programs
    (names only — the hashes themselves are verified by the bench_lint
    tier-1 subprocess at the pinned 8-device geometry)."""
    goldens = fingerprint.load_goldens()
    live = {c.name for c in lint._registered(None)}
    assert set(goldens) == live


# ---- kernel cost registry ---------------------------------------------------


def test_registered_decode_kernel_model_prices_pallas_call():
    """The decode-attention kernels' registered models price a traced
    pallas_call at decode_kernel_hbm_bytes exactly — auditor and kernel
    microbench can never disagree about the same call."""
    from distributed_tensorflow_guide_tpu.ops import decode_attention as da

    assert "_decode_kernel" in cost._KERNEL_COST_MODELS
    assert "_paged_decode_kernel" in cost._KERNEL_COST_MODELS

    runner = da.make_decode_runner(64, b=2, h=2, s=128, d=64,
                                   dtype=jnp.bfloat16, chunk=1)
    vec = cost.CostVector()
    cost._interpret(jax.make_jaxpr(runner)().jaxpr, vec, mult=1.0,
                    axis_sizes={})
    closed = da.decode_kernel_hbm_bytes(b=2, h=2, s=128, d=64,
                                        dtype=jnp.bfloat16, chunk=8)
    assert vec.hbm_bytes == closed
    assert vec.flops == 4.0 * 2 * 2 * 128 * 8 * 64
