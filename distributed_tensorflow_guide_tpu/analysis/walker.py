"""The sub-jaxpr-complete walker every analysis rule is built on.

One recursive traversal replaces the per-test scanners that grew in
tests/pin_utils.py. Two blind spots of the old pattern are fixed here and
pinned by positive controls in tests/test_analysis.py:

* **dict-valued / nested-container eqn params** — the old loop only
  looked inside tuple/list param values, so a sub-jaxpr carried in a dict
  (or a dict nested in a tuple, e.g. a branches table keyed by name) was
  silently skipped. :func:`iter_subjaxprs` recurses arbitrary dict /
  tuple / list nests.
* **``eqn.invars`` aliasing** — the old walkers never read invars at all,
  so a donated buffer consumed twice by one equation (``dot(x, x)``)
  counted as one use. :func:`input_use_counts` counts list occurrences.

Everything duck-types on the ``jax.extend.core`` surface (``eqns`` /
``jaxpr`` / ``invars`` / ``outvars`` / ``primitive.name``) so tests can
feed the walker hand-built equation shells as positive controls.
"""

from __future__ import annotations

import re
from collections import Counter
from typing import Any, Iterator

import numpy as np

# ---- traversal --------------------------------------------------------------


def _as_open_jaxpr(j):
    """ClosedJaxpr -> its open jaxpr; open jaxprs pass through. (ClosedJaxpr
    also *forwards* ``eqns``, so test on the ``jaxpr`` attribute alone.)"""
    return j.jaxpr if hasattr(j, "jaxpr") else j


def _is_jaxpr_like(v) -> bool:
    return hasattr(v, "eqns") or (
        hasattr(v, "jaxpr") and hasattr(getattr(v, "jaxpr"), "eqns")
    )


def iter_subjaxprs(value: Any) -> Iterator[Any]:
    """Every jaxpr reachable inside one eqn-param *value*, however nested.

    Handles the containers real primitives use today — ``scan``'s bare
    ClosedJaxpr, ``cond``'s tuple of branches, ``custom_vjp``'s
    dict-free params — plus dict- and mixed-nested containers, which the
    pin_utils-era loop missed entirely.
    """
    if _is_jaxpr_like(value):
        yield _as_open_jaxpr(value)
    elif isinstance(value, dict):
        for v in value.values():
            yield from iter_subjaxprs(v)
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from iter_subjaxprs(v)


def walk(jaxpr) -> Iterator[Any]:
    """Depth-first over every equation of ``jaxpr`` and all sub-jaxprs
    (scan/while/pjit/cond/custom_vjp/shard_map bodies included)."""
    jaxpr = _as_open_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in iter_subjaxprs(p):
                yield from walk(sub)


# ---- census helpers ---------------------------------------------------------


def count_primitives(jaxpr, name: str) -> int:
    """Occurrences of one primitive across the jaxpr and every sub-jaxpr
    — e.g. how many ``psum`` binds a bucketed backward emits."""
    return sum(1 for eqn in walk(jaxpr) if eqn.primitive.name == name)


def primitive_census(jaxpr) -> Counter:
    """primitive name -> equation count, across every sub-jaxpr."""
    return Counter(eqn.primitive.name for eqn in walk(jaxpr))


#: Cross-device communication primitives the collective audit reports.
#: ``pmean`` lowers to ``psum`` + divide and ``cc.reduce_scatter`` binds
#: jax's scatter primitive (spelled ``reduce_scatter`` on this line,
#: ``psum_scatter`` on others — both are listed), so expectations are
#: written in primitive spelling, not wrapper spelling.
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "all_gather", "psum_scatter", "reduce_scatter",
    "ppermute", "pbroadcast", "all_to_all",
})

#: shard_map's replication-checking rewrite (``check_rep=True``) rebinds
#: ``psum`` as the distinct ``psum2`` primitive; the data movement is
#: identical, so censuses and cost pricing spell both as ``psum``.
_PRIM_ALIASES = {"psum2": "psum"}


def prim_name(eqn) -> str:
    """``eqn``'s primitive name with rewrite aliases normalized."""
    name = eqn.primitive.name
    return _PRIM_ALIASES.get(name, name)


def eqn_axis_names(eqn) -> tuple[str, ...]:
    """The *named* mesh axes one collective equation reduces over (its
    positional integer axes, if any, are dropped)."""
    p = eqn.params
    axes = p.get("axes", p.get("axis_name", p.get("axis_names", ())))
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def collective_census(jaxpr) -> Counter:
    """``"prim[axis,...]" -> count`` over every sub-jaxpr — the static
    counterpart of ``collectives.trace_comm`` (which counts Python call
    sites during tracing and can see shard_map bodies traced twice; an
    equation census of the final jaxpr is single-valued)."""
    census: Counter = Counter()
    for eqn in walk(jaxpr):
        name = prim_name(eqn)
        if name in COLLECTIVE_PRIMS:
            key = f"{name}[{','.join(eqn_axis_names(eqn))}]"
            census[key] += 1
    return census


# ---- shape / dtype scans ----------------------------------------------------


def _f32_elems(aval) -> int:
    import jax.numpy as jnp

    if getattr(aval, "dtype", None) != jnp.float32:
        return 0
    return int(np.prod(getattr(aval, "shape", ()) or (1,)))


def largest_f32_intermediate(jaxpr) -> tuple[int, tuple[int, ...]]:
    """(elements, shape) of the biggest f32 value any equation produces —
    the single-tensor lower bound on live memory the memory rule reports."""
    worst, shape = 0, ()
    for eqn in walk(jaxpr):
        for var in eqn.outvars:
            n = _f32_elems(var.aval)
            if n > worst:
                worst, shape = n, tuple(var.aval.shape)
    return worst, shape


def max_f32_elems_with_vocab_dim(jaxpr, n: int, v: int) -> int:
    """Largest f32 intermediate of shape (..., V) with >= n rows, walked
    through every sub-jaxpr — the fused-CE "no full logits" instrument
    (the ``n`` floor excludes the legitimate (D, V) head weight/grad)."""
    import jax.numpy as jnp

    worst = 0
    for eqn in walk(jaxpr):
        for var in eqn.outvars:
            aval = var.aval
            shape = getattr(aval, "shape", ())
            if (getattr(aval, "dtype", None) == jnp.float32
                    and len(shape) >= 2 and shape[-1] == v
                    and int(np.prod(shape[:-1])) >= n):
                worst = max(worst, int(np.prod(shape)))
    return worst


# ---- input-use analysis (donation rule) -------------------------------------

#: Call-like primitives whose eqn.invars map positionally onto their
#: sub-jaxpr's invars, letting use-analysis see through the call boundary.
_CALL_PRIMS = frozenset({
    "pjit", "jit", "closed_call", "core_call", "xla_call", "shard_map",
    "remat", "remat2", "checkpoint", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr",
})


def _single_subjaxpr(eqn):
    subs = [s for p in eqn.params.values() for s in iter_subjaxprs(p)]
    return subs[0] if len(subs) == 1 else None


def input_use_counts(jaxpr) -> list[int]:
    """Per input position: how many times the top-level equations (and the
    jaxpr's own outputs) reference that variable — *list* occurrences, so
    ``dot(x, x)`` counts x twice (the invar-aliasing blind spot)."""
    jaxpr = _as_open_jaxpr(jaxpr)
    refs = Counter()
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            refs[id(v)] += 1
    for v in jaxpr.outvars:
        refs[id(v)] += 1
    return [refs[id(v)] for v in jaxpr.invars]


def deep_input_used(jaxpr) -> list[bool]:
    """Per input position: is the value *actually read* by any compute —
    resolved recursively through call-like equations (a buffer that only
    flows into a ``pjit`` whose body ignores it is dead, and donating a
    dead buffer is a contract violation the flat count can't see)."""
    jaxpr = _as_open_jaxpr(jaxpr)
    used: set[int] = {id(v) for v in jaxpr.outvars}
    for eqn in jaxpr.eqns:
        sub = (_single_subjaxpr(eqn)
               if eqn.primitive.name in _CALL_PRIMS else None)
        if sub is not None and len(sub.invars) == len(eqn.invars):
            inner = deep_input_used(sub)
            for v, u in zip(eqn.invars, inner):
                if u:
                    used.add(id(v))
        else:
            for v in eqn.invars:
                used.add(id(v))
    return [id(v) in used for v in jaxpr.invars]


# ---- byte-identity instrument ----------------------------------------------


def traced_text(fn, *args) -> str:
    """The full textual trace of ``fn`` at ``args`` (every sub-jaxpr
    printed) — the byte-identity instrument: two code paths that must
    trace the same program compare equal here. Variable naming is
    deterministic within a process, so equal programs compare equal and
    any structural drift shows as a diff. Raw object addresses (repr'd
    closures/meshes in eqn params) are normalized away — they differ per
    Python instance, not per program."""
    import jax

    return re.sub(r"0x[0-9a-f]+", "0x•", str(jax.make_jaxpr(fn)(*args)))
