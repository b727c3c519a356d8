"""Quantitative cost semantics over traced programs (round 17).

PR 8 gave every judged program *predicate* checks (a psum census, a
donation audit); this module extends the same walker into an abstract
cost interpreter, so the numbers the repo's closed-form models promise
(``benchmarks/common.py``) are DERIVED from the jaxpr that will actually
compile — and drift between model and program becomes a lint failure
instead of a stale doc. Four quantities per program:

* **MXU FLOPs** — ``dot_general``/``conv_general_dilated`` contraction
  shapes, ``lax.scan`` bodies multiplied by their static trip count,
  ``cond``/``switch`` charged at the max branch (exact when the expensive
  branch is taken), ``while`` bodies once (documented undercount — no hot
  path here uses a raw while_loop for compute), ``pallas_call`` charged
  through the kernel cost-model registry (:func:`register_kernel_cost`)
  with an inner-jaxpr × grid fallback. Rematerialized regions are charged
  on recompute by construction: the remat body appears again inside the
  backward, and the interpreter charges equations as scheduled.

* **HBM bytes read/written** — the fusion-boundary byte model: only
  memory-bound equations (matmul-class, gather/scatter, dynamic slices,
  sort/top_k, pallas) touch HBM; elementwise/shape/convert chains are
  assumed XLA-fused (zero traffic), which makes this MINIMAL algorithmic
  traffic exactly like the closed forms it is diffed against.
  Matmul operand reads are *narrow-origin* aware (round 19): a
  ``convert_element_type`` chain carries the smallest storage the value
  ever had, so a weight stored int8 and upcast inside the fused matmul
  is charged 1 byte/elem — the widening cast is compute, not traffic.
  Narrowing converts (f32 -> bf16) are the identity under the ``min``,
  so every pre-existing program's bytes are unchanged.
  Gather charges the *touched rows* (output size), not the whole table —
  the ``decode_hbm_bytes_per_step`` "gathered embedding rows" convention
  — and ``dynamic_update_slice`` charges the update size, in-place.
  Donation-awareness at the program boundary: an output leaf that is a
  bare passthrough of an input costs a defensive copy UNLESS that input
  is donated in alias mode (XLA aliases it — zero bytes), so an
  undonated state->state program is visibly more expensive than the
  donated one.

* **Collective bytes** — every census key (``"prim[axis,...]"``) priced
  per participating device with the same ring accounting as
  ``benchmarks/common.py``: psum 2·P·(n−1)/n, all_gather (n−1)/n of the
  gathered output, reduce/psum_scatter (n−1)/n of the scattered input,
  all_to_all (n−1)/n of the buffer, ppermute one ring-averaged hop with
  the wrap pair carrying no payload. Axis sizes come from the enclosing
  ``shard_map`` equation's mesh, so the interpreter needs no device
  globals.

* **Peak live bytes** — a linear scan over the equation schedule with
  last-use liveness: non-donated inputs and constants are live for the
  whole program (the caller owns those buffers), donated inputs die at
  their last use — and a donated-but-DEAD input never dies (XLA drops
  the unusable donation and the buffer sits allocated), which is how a
  dead donation shows up as a peak-live regression, not just a warning.

Import discipline matches the package: no jax at module import.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

from distributed_tensorflow_guide_tpu.analysis import walker

# ---- the cost vector ---------------------------------------------------------


@dataclasses.dataclass
class CostVector:
    """One program's derived costs. ``collective_bytes`` is keyed exactly
    like the walker census (``"psum[data]"``) so a contract can pin the
    bytes of the same collective family it already counts."""

    flops: float = 0.0
    hbm_bytes_read: float = 0.0
    hbm_bytes_written: float = 0.0
    collective_bytes: dict[str, float] = dataclasses.field(
        default_factory=dict)
    peak_live_bytes: int = 0

    @property
    def hbm_bytes(self) -> float:
        return self.hbm_bytes_read + self.hbm_bytes_written

    @property
    def collective_bytes_total(self) -> float:
        return float(sum(self.collective_bytes.values()))

    def quantity(self, name: str) -> float:
        """Resolve a CostPin quantity string: a scalar field name
        (``"flops"``, ``"hbm_bytes"``, ``"peak_live_bytes"``,
        ``"collective_bytes_total"``) or one census-keyed entry spelled
        ``"collective_bytes[psum[data]]"`` (0.0 when the key never
        traced — an absent collective moved zero bytes)."""
        if name.startswith("collective_bytes[") and name.endswith("]"):
            return float(self.collective_bytes.get(name[17:-1], 0.0))
        if not hasattr(self, name) and name not in (
                "hbm_bytes", "collective_bytes_total"):
            raise KeyError(f"unknown cost quantity {name!r}")
        return float(getattr(self, name))

    def to_dict(self) -> dict:
        return {
            "flops": self.flops,
            "hbm_bytes_read": self.hbm_bytes_read,
            "hbm_bytes_written": self.hbm_bytes_written,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": dict(sorted(self.collective_bytes.items())),
            "peak_live_bytes": self.peak_live_bytes,
        }


# ---- kernel cost-model registry ---------------------------------------------

#: kernel name (pallas_call ``name_and_src_info.name``) -> model.
#: A model maps one pallas_call equation to
#: ``{"flops": f, "read": r, "write": w}``; kernels register next to
#: their implementation (the autotune pattern), e.g.
#: ops/decode_attention.py registers the paged decode kernel's model.
_KERNEL_COST_MODELS: dict[str, Callable[[Any], dict]] = {}


def register_kernel_cost(name: str, model: Callable[[Any], dict]) -> None:
    """Register (idempotently) the cost model for one Pallas kernel."""
    _KERNEL_COST_MODELS[name] = model


def _pallas_name(eqn) -> str:
    """The kernel function's name: where this jax keeps it, the kernel
    jaxpr's debug info (a ``functools.partial`` gives its function's)."""
    nsi = eqn.params.get("name_and_src_info")
    dbg = getattr(eqn.params.get("jaxpr"), "debug_info", None)
    return (getattr(nsi, "name", None) or eqn.params.get("name")
            or getattr(dbg, "func_name", None) or "?")


def _pallas_grid(eqn) -> int:
    gm = eqn.params.get("grid_mapping")
    grid = getattr(gm, "grid", None) or ()
    return int(math.prod(int(g) for g in grid)) or 1


def _pallas_cost(eqn) -> dict:
    """Registered model, else the fallback: kernel-body FLOPs × grid
    cells, operands read once, outputs written once (the minimal-DMA
    ceiling — BlockSpec revisits push real traffic above it, which is
    the same "spills push the fraction down" convention as every
    roofline model in benchmarks/common.py)."""
    model = _KERNEL_COST_MODELS.get(_pallas_name(eqn))
    if model is not None:
        return model(eqn)
    body = walker.iter_subjaxprs(eqn.params.get("jaxpr"))
    flops = sum(_jaxpr_flops(b) for b in body) * _pallas_grid(eqn)
    return {
        "flops": flops,
        "read": sum(_aval_bytes(v.aval) for v in eqn.invars),
        "write": sum(_aval_bytes(v.aval) for v in eqn.outvars),
    }


# ---- aval helpers ------------------------------------------------------------


def _aval_bytes(aval) -> int:
    import numpy as np

    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:  # tokens / abstract refs
        return 0
    try:
        itemsize = int(np.dtype(dtype).itemsize)
    except TypeError:  # extended dtypes (PRNG keys: fry = 2 x uint32)
        itemsize = int(getattr(dtype, "itemsize", 8))
    return int(math.prod(shape) or 1) * itemsize


def _dot_general_flops(eqn) -> float:
    lhs, rhs = (v.aval for v in eqn.invars[:2])
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    batch = math.prod(lhs.shape[i] for i in lb)
    contract = math.prod(lhs.shape[i] for i in lc)
    lhs_free = math.prod(
        d for i, d in enumerate(lhs.shape) if i not in set(lb) | set(lc))
    rhs_free = math.prod(
        d for i, d in enumerate(rhs.shape) if i not in set(rb) | set(rc))
    return 2.0 * batch * contract * lhs_free * rhs_free


def _conv_flops(eqn) -> float:
    lhs, rhs = (v.aval for v in eqn.invars[:2])
    out = eqn.outvars[0].aval
    dn = eqn.params["dimension_numbers"]
    k_spatial = math.prod(rhs.shape[i] for i in dn.rhs_spec[2:])
    c_in = rhs.shape[dn.rhs_spec[1]]
    c_out = out.shape[dn.out_spec[1]]
    batch = out.shape[dn.out_spec[0]]
    out_spatial = math.prod(out.shape[i] for i in dn.out_spec[2:])
    groups = eqn.params.get("feature_group_count", 1)
    return 2.0 * batch * out_spatial * c_out * c_in * k_spatial / groups


def _jaxpr_flops(jaxpr) -> float:
    """FLOPs only (the pallas fallback needs this without the rest of the
    interpreter — kernel bodies have no collectives or HBM boundary)."""
    vec = CostVector()
    _interpret(jaxpr, vec, mult=1.0, axis_sizes={}, flops_only=True)
    return vec.flops


# ---- the HBM fusion-boundary classification ---------------------------------

#: Equations that move HBM bytes themselves. Everything else is assumed
#: fused by XLA (elementwise chains, reshapes, converts, broadcasts) and
#: charged zero — the byte totals are MINIMAL algorithmic traffic by
#:  construction, same convention as the closed forms they're diffed with.
_MATMUL_PRIMS = frozenset({"dot_general", "conv_general_dilated"})
_TOUCHED_ROWS_PRIMS = frozenset({"gather", "take", "take_along_axis"})
_INPLACE_UPDATE_PRIMS = frozenset({"dynamic_update_slice", "scatter",
                                   "scatter-add", "scatter_add"})
_SLICE_PRIMS = frozenset({"dynamic_slice"})
_REORDER_PRIMS = frozenset({"sort", "top_k", "argmax", "argmin",
                            "cumsum", "cumlogsumexp", "cummax"})

#: Branch/loop primitives the interpreter schedules explicitly.
_SCAN, _WHILE = "scan", "while"
_BRANCH_PRIMS = frozenset({"cond", "switch", "platform_index"})


def _eqn_hbm(eqn, narrow: dict[int, int] | None = None,
             ) -> tuple[float, float]:
    """(read, write) bytes one memory-bound equation moves; (0, 0) for
    fused-class equations. ``narrow`` maps ``id(var)`` to the smallest
    storage bytes the value had anywhere on its convert chain — applied
    ONLY to matmul operand reads (the weight-only-quant case: the int8
    buffer in HBM is what the MXU pipeline actually streams; the f32
    upcast lives in registers)."""
    name = eqn.primitive.name
    in_b = sum(_aval_bytes(v.aval) for v in eqn.invars)
    out_b = sum(_aval_bytes(v.aval) for v in eqn.outvars)
    if name in _MATMUL_PRIMS or name in _REORDER_PRIMS:
        if name in _MATMUL_PRIMS and narrow:
            in_b = sum(narrow.get(id(v), _aval_bytes(v.aval))
                       for v in eqn.invars)
        return float(in_b), float(out_b)
    if name in _TOUCHED_ROWS_PRIMS:
        # the touched rows, not the whole table (decode counts GATHERED
        # embedding rows); indices are noise next to the rows
        return float(out_b), float(out_b)
    if name in _INPLACE_UPDATE_PRIMS:
        upd = sum(_aval_bytes(v.aval) for v in eqn.invars[1:])
        return float(upd), float(upd)
    if name in _SLICE_PRIMS:
        return float(out_b), float(out_b)
    return 0.0, 0.0


# ---- the interpreter ---------------------------------------------------------


def _merge_collectives(dst: dict, src: dict, mult: float) -> None:
    for k, v in src.items():
        dst[k] = dst.get(k, 0.0) + v * mult


def _collective_bytes(eqn, axis_sizes: dict[str, int]) -> float:
    """Per-device ring bytes of one collective equation — the SAME
    accounting as benchmarks/common.py's closed forms, derived from the
    equation instead of hand-fed."""
    name = walker.prim_name(eqn)
    n = 1
    for ax in walker.eqn_axis_names(eqn):
        n *= int(axis_sizes.get(ax, 1))
    if n <= 1:
        return 0.0  # compiles to a no-op on a 1-device axis
    frac = (n - 1) / n
    in_b = sum(_aval_bytes(v.aval) for v in eqn.invars)
    out_b = sum(_aval_bytes(v.aval) for v in eqn.outvars)
    if name in ("psum", "pmax", "pmin"):
        return 2.0 * in_b * frac        # ring = reduce-scatter + all-gather
    if name == "all_gather":
        return out_b * frac             # receive everyone else's shard
    if name in ("psum_scatter", "reduce_scatter"):
        return in_b * frac              # send everyone else's shard
    if name == "all_to_all":
        return in_b * frac              # keep own 1/n, exchange the rest
    if name == "ppermute":
        # one hop per non-wrap pair, ring-averaged over the axis — the
        # pipeline model's (P-1)/P with the wrap carrying no payload
        perm = eqn.params.get("perm", ())
        hops = max(0, len(perm) - 1) if len(perm) == n else len(perm)
        return in_b * hops / n
    return in_b * frac  # pbroadcast and friends: one pass


def _subjaxprs(eqn) -> list:
    return [s for p in eqn.params.values() for s in walker.iter_subjaxprs(p)]


def _interpret(jaxpr, vec: CostVector, *, mult: float,
               axis_sizes: dict[str, int], flops_only: bool = False) -> None:
    """Accumulate ``jaxpr``'s costs into ``vec`` with multiplier ``mult``
    (scan trip counts compose multiplicatively through nesting)."""
    jaxpr = walker._as_open_jaxpr(jaxpr)
    # narrow-origin storage bytes, per jaxpr: convert_element_type chains
    # carry min(chain, own aval) forward — monotone, so a pure-widening
    # chain (int8 weight -> f32 matmul operand) remembers the 1-byte HBM
    # buffer it streams from, while narrowing (f32 -> bf16) is a no-op
    # relative to the plain aval bytes. Chain-breaking ops (the int4
    # unpack's shifts/concats) deliberately reset to aval bytes: once the
    # program *computes* a wider value, that value is what moves.
    narrow: dict[int, int] = {}
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "convert_element_type" and eqn.invars and eqn.outvars:
            inv, outv = eqn.invars[0], eqn.outvars[0]
            narrow[id(outv)] = min(
                narrow.get(id(inv), _aval_bytes(inv.aval)),
                _aval_bytes(outv.aval))
        if name == "pallas_call":
            cost = _pallas_cost(eqn)
            vec.flops += cost.get("flops", 0.0) * mult
            if not flops_only:
                vec.hbm_bytes_read += cost.get("read", 0.0) * mult
                vec.hbm_bytes_written += cost.get("write", 0.0) * mult
            continue
        if name in _MATMUL_PRIMS:
            vec.flops += (_dot_general_flops(eqn) if name == "dot_general"
                          else _conv_flops(eqn)) * mult
        if not flops_only:
            r, w = _eqn_hbm(eqn, narrow)
            vec.hbm_bytes_read += r * mult
            vec.hbm_bytes_written += w * mult
            cname = walker.prim_name(eqn)
            if cname in walker.COLLECTIVE_PRIMS:
                key = (f"{cname}"
                       f"[{','.join(walker.eqn_axis_names(eqn))}]")
                b = _collective_bytes(eqn, axis_sizes)
                vec.collective_bytes[key] = (
                    vec.collective_bytes.get(key, 0.0) + b * mult)
        # -- recurse ----------------------------------------------------------
        if name == _SCAN:
            trips = int(eqn.params.get("length", 1))
            for sub in _subjaxprs(eqn):
                _interpret(sub, vec, mult=mult * trips,
                           axis_sizes=axis_sizes, flops_only=flops_only)
        elif name == _WHILE:
            # dynamic trip count: body charged once (documented undercount)
            for key in ("cond_jaxpr", "body_jaxpr"):
                for sub in walker.iter_subjaxprs(eqn.params.get(key)):
                    _interpret(sub, vec, mult=mult,
                               axis_sizes=axis_sizes, flops_only=flops_only)
        elif name in _BRANCH_PRIMS:
            # runtime takes ONE branch: charge the max (exact when the
            # expensive branch is the taken one)
            best, best_vec = -1.0, None
            for sub in walker.iter_subjaxprs(eqn.params.get("branches")):
                bv = CostVector()
                _interpret(sub, bv, mult=1.0, axis_sizes=axis_sizes,
                           flops_only=flops_only)
                score = bv.flops + bv.hbm_bytes
                if score > best:
                    best, best_vec = score, bv
            if best_vec is not None:
                vec.flops += best_vec.flops * mult
                if not flops_only:
                    vec.hbm_bytes_read += best_vec.hbm_bytes_read * mult
                    vec.hbm_bytes_written += (
                        best_vec.hbm_bytes_written * mult)
                    _merge_collectives(vec.collective_bytes,
                                       best_vec.collective_bytes, mult)
        else:
            if name == "shard_map":
                mesh = eqn.params.get("mesh")
                shape = getattr(mesh, "shape", None)
                if shape:
                    axis_sizes = {**axis_sizes,
                                  **{str(k): int(v)
                                     for k, v in dict(shape).items()}}
            for sub in _subjaxprs(eqn):
                _interpret(sub, vec, mult=mult,
                           axis_sizes=axis_sizes, flops_only=flops_only)


# ---- program boundary (donation-aware) --------------------------------------


_TRIVIAL_CALLS = frozenset({"pjit", "closed_call", "core_call", "xla_call",
                            "remat", "checkpoint", "custom_jvp_call",
                            "custom_vjp_call", "shard_map"})


def _unwrap_trivial(jaxpr):
    """Descend through whole-program wrappers (``make_jaxpr`` of a jitted
    shard_map program traces as one ``pjit`` eqn around one ``shard_map``
    eqn). Only unwraps when the wrapper consumes the program inputs in
    order and returns the body outputs unchanged, so flat invar positions
    (and therefore donation indices) carry through positionally. Inside
    ``shard_map`` the avals are the per-device block shapes — peak-live
    and boundary bytes become PER-DEVICE quantities, which is what one
    TPU core's HBM actually holds and what every closed form models."""
    jaxpr = walker._as_open_jaxpr(jaxpr)
    while (len(jaxpr.eqns) == 1
           and jaxpr.eqns[0].primitive.name in _TRIVIAL_CALLS):
        eqn = jaxpr.eqns[0]
        subs = [walker._as_open_jaxpr(s) for s in _subjaxprs(eqn)]
        if len(subs) != 1:
            break
        if [id(v) for v in eqn.invars] != [id(v) for v in jaxpr.invars]:
            break
        if [id(v) for v in eqn.outvars] != [id(v) for v in jaxpr.outvars]:
            break
        if len(subs[0].invars) != len(eqn.invars):
            break
        jaxpr = subs[0]
    return jaxpr


def _boundary_bytes(closed_jaxpr, donated_flat: set[int],
                    donation_mode: str | None) -> tuple[float, float]:
    """Copy traffic at the program boundary: an output leaf that is a bare
    passthrough of an input materializes a defensive copy — UNLESS the
    input is donated in alias mode, where XLA aliases it in place and the
    copy costs zero (the "donations charge zero for the aliased buffer"
    semantics). Computed traffic is charged by the producing equations;
    only passthroughs can hide at the boundary."""
    jaxpr = _unwrap_trivial(closed_jaxpr)
    invar_pos = {id(v): i for i, v in enumerate(jaxpr.invars)}
    read = written = 0.0
    for out in jaxpr.outvars:
        pos = invar_pos.get(id(out))
        if pos is None:
            continue  # produced by an equation — already charged
        if donation_mode == "alias" and pos in donated_flat:
            continue  # aliased in place: zero
        b = _aval_bytes(out.aval)
        read += b
        written += b
    return read, written


# ---- peak live bytes ---------------------------------------------------------


def _inner_peak(eqn, axis_sizes: dict[str, int]) -> int:
    """Internal peak of one equation's sub-jaxpr bodies (intermediates the
    body allocates beyond the operands the outer scan already counts)."""
    peak = 0
    for sub in _subjaxprs(eqn):
        peak = max(peak, peak_live_bytes(sub, donated_flat=frozenset()))
    return peak


def peak_live_bytes(closed_jaxpr, *,
                    donated_flat: frozenset[int] | set[int] = frozenset(),
                    ) -> int:
    """Linear-scan peak over the equation schedule.

    Liveness rules: constants and NON-donated inputs are live for the
    whole program (the caller owns those buffers; XLA cannot free them).
    Donated inputs die after their last use — and a donated input with NO
    use never dies: XLA drops the unusable donation and the buffer sits
    allocated to the end, which is exactly the dead-donation hazard the
    donation rule flags and this scan *prices*. Equation outputs are live
    from their equation to their last use (program outputs to the end).
    Sub-jaxpr bodies contribute their own internal peak at the equation
    that runs them.
    """
    jaxpr = _unwrap_trivial(closed_jaxpr)
    eqns = list(jaxpr.eqns)
    n = len(eqns)
    last_use: dict[int, int] = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            last_use[id(v)] = i
    for v in jaxpr.outvars:
        last_use[id(v)] = n  # program outputs survive the whole schedule

    live = sum(_aval_bytes(v.aval)
               for v in getattr(jaxpr, "constvars", ()))
    # inputs: donated-and-USED die after their last use; everything else
    # is whole-program — non-donated because the caller owns the buffer,
    # donated-but-DEAD because XLA drops the unusable donation
    deaths: dict[int, float] = {}
    for pos, v in enumerate(jaxpr.invars):
        b = _aval_bytes(v.aval)
        live += b
        death = last_use.get(id(v), n)
        if pos in donated_flat and death < n:
            deaths[death] = deaths.get(death, 0.0) + b
    peak = live
    out_death: dict[int, float] = {}
    seen_out: set[int] = set()
    for i, eqn in enumerate(eqns):
        alloc = 0
        for v in eqn.outvars:
            if id(v) in seen_out:
                continue
            seen_out.add(id(v))
            b = _aval_bytes(v.aval)
            alloc += b
            death = last_use.get(id(v), i)
            if death < n:
                out_death[death] = out_death.get(death, 0.0) + b
        live += alloc
        peak = max(peak, int(live + _inner_peak(eqn, {})))
        live -= out_death.pop(i, 0.0)
        live -= deaths.get(i, 0.0)
    return int(peak)


# ---- public entry ------------------------------------------------------------


def closed_forms():
    """``benchmarks.common`` — the closed-form models the CostSpec pins
    diff against. The benchmarks tree lives NEXT to the package (repo
    root), not inside it, so the CLI run from an arbitrary cwd needs the
    path fallback."""
    try:
        import benchmarks.common as common
    except ImportError:
        import pathlib
        import sys
        root = str(pathlib.Path(__file__).resolve().parents[2])
        if root not in sys.path:
            sys.path.insert(0, root)
        import benchmarks.common as common
    return common


def donated_flat_indices(contract, arg_leaf_avals) -> frozenset[int]:
    """Flat invar positions of the contract's donated argument leaves
    (same flattening the donation rule uses)."""
    spec = getattr(contract, "donation", None)
    if spec is None:
        return frozenset()
    starts, pos = [], 0
    for leaves in arg_leaf_avals:
        starts.append(pos)
        pos += len(leaves)
    idx: set[int] = set()
    for argnum in spec.argnums:
        if argnum < len(arg_leaf_avals):
            idx.update(starts[argnum] + k
                       for k in range(len(arg_leaf_avals[argnum])))
    return frozenset(idx)


def program_cost(traced, contract) -> CostVector:
    """The full cost vector of one traced contract program."""
    vec = CostVector()
    _interpret(traced.jaxpr, vec, mult=1.0, axis_sizes={})
    donated = donated_flat_indices(contract, traced.arg_leaf_avals)
    mode = getattr(getattr(contract, "donation", None), "mode", None)
    r, w = _boundary_bytes(traced.jaxpr, set(donated), mode)
    vec.hbm_bytes_read += r
    vec.hbm_bytes_written += w
    vec.peak_live_bytes = peak_live_bytes(
        traced.jaxpr, donated_flat=donated)
    return vec
