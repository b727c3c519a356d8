"""Two-tier multi-slice training — dense DP inside a slice over ICI,
infrequent outer parameter sync across slices over DCN.

The reference guide's answer to "more capacity than one machine" was the
PS/worker cluster; this framework's answer so far was one ICI slice. DCN —
the data-center network between slices — is orders of magnitude slower than
ICI (benchmarks/common.py `_TPU_DCN_PEAK` vs `_TPU_ICI_PEAK`), so a naive
mesh that runs the per-step gradient all-reduce across slices is
wire-bound. The DiLoCo-style composition (Douillard et al. 2023; the same
bandwidth economics as DOWNPOUR, see :class:`~.async_ps.LocalSGD`) keeps
the dense per-step collective entirely on ICI and crosses DCN once every
``sync_period`` steps with a parameter *delta*:

  * **inner tier** — each slice runs ``sync_period`` synchronous DP steps:
    per-step gradient ``pmean`` over the within-slice ``data`` axis only
    (pure ICI), local optimizer update.
  * **outer tier** — slices average the round's parameter delta
    ``anchor - params`` over the ``dcn`` axis (the only collective that
    touches DCN) and apply it through a Nesterov-style outer optimizer;
    float inner-optimizer state is pmean'd across slices alongside so
    every slice re-enters the next round bit-identical.

With ``sync_period=1``, ``outer_lr=1`` and ``outer_momentum=0`` the outer
update collapses to ``params = mean_slices(params_s)`` — plain sync DP
split into a two-level reduction (pinned against :class:`DataParallel` in
tests/test_multislice.py, the same parity LocalSGD pins at period 1).

The mesh is explicit about the two tiers: :func:`two_tier_mesh` builds a
``(dcn, data, model, pipe, context, expert)`` mesh whose leading ``dcn``
axis is the slice index — the slice-spanning factor that
``core.mesh.build_mesh`` folds into one logical axis is a *named axis*
here, so shard_map can address "across slices" and "within a slice" as
different collectives.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

import distributed_tensorflow_guide_tpu.collectives as cc
from distributed_tensorflow_guide_tpu.core.mesh import (
    AXES,
    MeshSpec,
    _slice_groups,
    axis_sizes,
    num_slices,
)

DCN_AXIS = "dcn"

LossFn = Callable[[Any, Any], tuple[jax.Array, dict]]


def _is_float(x) -> bool:
    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)


def _pmean_floats(tree: Any, axis: str) -> Any:
    """pmean float leaves; pass through ints (identical across replicas —
    e.g. optax step counts), which integer pmean would corrupt."""
    return jax.tree.map(
        lambda x: cc.pmean(x, axis) if _is_float(x) else x, tree
    )


def two_tier_mesh(
    spec: MeshSpec | None = None,
    devices=None,
    *,
    n_slices: int | None = None,
) -> Mesh:
    """Build a ``(dcn, data, model, pipe, context, expert)`` mesh: the
    leading ``dcn`` axis indexes slices, ``spec`` describes the PER-SLICE
    (ICI) mesh and is resolved against ``len(devices) / n_slices``.

    Real multi-slice deployments group by ``device.slice_index`` so only
    the ``dcn`` axis crosses DCN. Backends with no slice info (CPU fake
    devices — the test/bench harness) are split into ``n_slices``
    contiguous groups ordered by ``(process_index, id)``: each fake
    "slice" is a contiguous block of processes, which is exactly the
    process→slice mapping the elastic harness (train/elastic_world.py)
    assigns, and keeps batch sharding under ``P((dcn, data))``
    process-contiguous for ``make_array_from_process_local_data``.
    """
    devices = list(devices if devices is not None else jax.devices())
    if n_slices is None:
        n_slices = max(num_slices(devices), 1)
    if n_slices < 1:
        raise ValueError(f"n_slices must be >= 1, got {n_slices}")
    if len(devices) % n_slices:
        raise ValueError(
            f"{len(devices)} devices do not split into {n_slices} slices"
        )
    per = len(devices) // n_slices
    spec = spec or MeshSpec()
    sizes = spec.resolve(per)
    inner_shape = tuple(sizes[a] for a in AXES)
    groups = _slice_groups(devices)
    if len(groups) != n_slices:
        if len(groups) > 1:
            # devices DO expose slice topology and it disagrees: chunking
            # would silently straddle real DCN boundaries, putting the
            # per-step inner pmean on the slow wire — the exact mistake
            # this module exists to prevent. Refuse.
            raise ValueError(
                f"devices span {len(groups)} real slice(s) but "
                f"n_slices={n_slices} was requested; pass n_slices="
                f"{len(groups)} (or omit it) so slice boundaries stay on "
                "DCN")
        # no slice info (CPU fake devices): contiguous fake slices
        devices = sorted(
            devices,
            key=lambda d: (getattr(d, "process_index", 0), d.id),
        )
        groups = [devices[i * per:(i + 1) * per] for i in range(n_slices)]
    arrs = []
    for g in groups:
        if len(g) != per:
            raise ValueError(
                f"uneven slice sizes {[len(x) for x in groups]}; every "
                f"slice must contribute {per} devices"
            )
        try:
            from jax.experimental import mesh_utils

            arrs.append(
                mesh_utils.create_device_mesh(inner_shape, devices=list(g))
            )
        except Exception:
            arrs.append(np.asarray(g, dtype=object).reshape(inner_shape))
    return Mesh(np.stack(arrs), (DCN_AXIS, *AXES))


@dataclasses.dataclass
class TwoTierState:
    """Carried state of one outer round: the per-slice inner TrainState
    plus the outer optimizer's momentum (a float-params-shaped tree).
    Registered as a pytree so it checkpoints/shard_maps like any state."""

    inner: Any
    outer_momentum: Any


jax.tree_util.register_pytree_node(
    TwoTierState,
    lambda s: ((s.inner, s.outer_momentum), None),
    lambda _, kids: TwoTierState(*kids),
)


class MultiSliceLocalSGD:
    """DiLoCo-style two-tier strategy over a :func:`two_tier_mesh`.

    One call of the compiled train step = one outer round:
    ``sync_period`` inner sync-DP steps (``lax.scan``; gradient pmean over
    ``inner_axis`` — within-slice ICI) followed by the one DCN collective:
    the round's parameter delta pmean'd over ``outer_axis`` and applied
    through the Nesterov outer optimizer

        m   <- outer_momentum * m + delta_mean
        upd <- delta_mean + outer_momentum * m        (nesterov)
               m                                      (heavy-ball)
        params <- anchor - outer_lr * upd

    plus a pmean of the float inner-optimizer state. ``outer="off"``
    emits NO DCN collective at all — outer sync, opt-state sync, and the
    metric scalar (slices train fully independently — numerically wrong
    on purpose; the timing control benchmarks use to measure the exposed
    DCN cost must not pay even one latency-bound round-trip per round).

    The super-batch contract matches LocalSGD: leaves shaped
    ``(sync_period, global_batch, ...)``, global batch sharded over
    ``(dcn, data)`` jointly — slices take contiguous row blocks, the
    within-slice data axis subdivides them.
    """

    def __init__(
        self,
        mesh: Mesh,
        sync_period: int,
        *,
        outer_lr: float = 1.0,
        outer_momentum: float = 0.0,
        nesterov: bool = True,
        inner_axis: str = "data",
        outer_axis: str = DCN_AXIS,
        outer: str = "on",
        compress: str | None = None,
    ):
        from distributed_tensorflow_guide_tpu.parallel.overlap import (
            resolve_compress,
        )

        sizes = axis_sizes(mesh)
        for ax in (inner_axis, outer_axis):
            if ax not in sizes:
                raise ValueError(
                    f"mesh has no axis {ax!r} (axes: {tuple(sizes)}); build "
                    "it with two_tier_mesh()"
                )
        if sync_period < 1:
            raise ValueError(f"sync_period must be >= 1, got {sync_period}")
        if outer not in ("on", "off"):
            raise ValueError(f"outer must be 'on' or 'off', got {outer!r}")
        # int8-compressed outer sync (ops/quant.int8_pmean): the delta and
        # float opt-state cross DCN at 1 byte/elem with one shared-scale
        # f32 pmax each — outer_sync_bytes(..., compress="int8") is the
        # closed form. The round's OUTER delta is exactly the signal that
        # tolerates coarse quantization (DiLoCo's premise: it is already
        # an average of sync_period updates); inner ICI grads stay f32.
        self.compress = resolve_compress(compress)
        self.mesh = mesh
        self.sync_period = sync_period
        self.outer_lr = float(outer_lr)
        self.outer_momentum = float(outer_momentum)
        self.nesterov = nesterov
        self.inner_axis = inner_axis
        self.outer_axis = outer_axis
        self.outer = outer
        self.n_slices = sizes[outer_axis]
        self.slice_world = sizes[inner_axis]
        self.world = self.n_slices * self.slice_world

    # ---- state / data placement -------------------------------------------

    def init(self, state: Any) -> TwoTierState:
        """Wrap an inner TrainState with zeroed outer momentum (same
        structure and dtypes as ``params``; non-float leaves stay zeros
        and are never updated — the outer optimizer only moves floats)."""
        momentum = jax.tree.map(jnp.zeros_like, state.params)
        return TwoTierState(inner=state, outer_momentum=momentum)

    def replicate(self, tt_state: TwoTierState) -> TwoTierState:
        return jax.device_put(tt_state, NamedSharding(self.mesh, P()))

    def batch_spec(self, *, leading_time_axis: bool = True) -> P:
        axes = (self.outer_axis, self.inner_axis)
        return P(None, axes) if leading_time_axis else P(axes)

    def shard_batch(self, batch: Any, *, leading_time_axis: bool = True):
        """Place a host super-batch. Single-process: the full global
        super-batch. Multi-process: this process's contiguous row block
        (see :func:`~.elastic_world.shard_bounds`)."""
        sharding = NamedSharding(
            self.mesh, self.batch_spec(leading_time_axis=leading_time_axis)
        )
        if jax.process_count() > 1:
            return jax.tree.map(
                lambda x: jax.make_array_from_process_local_data(sharding, x),
                batch,
            )
        return jax.device_put(batch, sharding)

    # ---- accounting --------------------------------------------------------

    def outer_float_bytes(self, tt_state: TwoTierState) -> int:
        """Bytes the outer sync moves per slice per round: the float param
        delta plus the float inner-optimizer state (what the two DCN
        pmeans carry). Feed to ``benchmarks.common.outer_sync_bytes`` for
        the ring-model per-device wire traffic."""
        total = 0
        for tree in (tt_state.inner.params, tt_state.inner.opt_state):
            for leaf in jax.tree.leaves(tree):
                if hasattr(leaf, "dtype") and _is_float(leaf):
                    total += int(leaf.size) * leaf.dtype.itemsize
        return total

    # ---- the compiled outer round -----------------------------------------

    def _outer_pmean(self, tree: Any) -> Any:
        """The outer-tier float pmean: int8 wire format when compressed,
        the historical per-leaf f32 pmean otherwise (byte-identical
        default trace)."""
        if self.compress == "int8":
            from distributed_tensorflow_guide_tpu.ops import quant

            return quant.int8_pmean(tree, self.outer_axis)
        return _pmean_floats(tree, self.outer_axis)

    def make_train_step(self, loss_fn: LossFn, *, donate: bool = True):
        mu = self.outer_momentum

        def sm_step(tt, batches):
            state = tt.inner
            anchor = state.params

            def inner_step(carry, sub):
                params, opt_state = carry
                (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, sub
                )
                # dense sync DP *within the slice*: ICI-only collective
                g = cc.pmean(g, self.inner_axis)
                updates, opt_state = state.tx.update(g, opt_state, params)
                params = optax.apply_updates(params, updates)
                return (params, opt_state), loss

            (params, opt_state), losses = lax.scan(
                inner_step, (anchor, state.opt_state), batches
            )
            momentum = tt.outer_momentum
            if self.outer == "on":
                delta = jax.tree.map(jnp.subtract, anchor, params)
                # the ONLY collectives on the DCN tier: one param-delta
                # pmean + the float opt-state pmean, per round
                delta = self._outer_pmean(delta)
                momentum = jax.tree.map(
                    lambda m, d: mu * m + d if _is_float(d) else m,
                    tt.outer_momentum,
                    delta,
                )
                if self.nesterov:
                    update = jax.tree.map(
                        lambda d, m: d + mu * m if _is_float(d) else d,
                        delta,
                        momentum,
                    )
                else:
                    update = jax.tree.map(
                        lambda d, m: m if _is_float(d) else d,
                        delta,
                        momentum,
                    )
                params = jax.tree.map(
                    lambda a, u: a - self.outer_lr * u
                    if _is_float(a) else a,
                    anchor,
                    update,
                )
                opt_state = self._outer_pmean(opt_state)
            new_inner = state.replace(
                step=state.step + self.sync_period,
                params=params,
                opt_state=opt_state,
            )
            # outer="off" must be genuinely DCN-free — including the
            # metric scalar (on real DCN one latency-bound round-trip per
            # round would contaminate the bench's exposed-frac control),
            # so its loss is the within-slice mean only
            met_axes = ((self.outer_axis, self.inner_axis)
                        if self.outer == "on" else self.inner_axis)
            mets = {"loss": cc.pmean(losses.mean(), met_axes)}
            return TwoTierState(new_inner, momentum), mets

        sharded = shard_map(
            sm_step,
            mesh=self.mesh,
            in_specs=(P(), self.batch_spec()),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def make_kv_block_transfer(mesh: Mesh, *, src_slice: int = 0,
                           dst_slice: int = 1):
    """Compiled-side model of the fleet's disaggregated prefill->decode
    KV handoff (PR 18): ship a ``(blocks, payload)`` buffer from the
    prefill-role slice to the decode-role slice with ONE single-hop
    ``lax.ppermute`` over the ``dcn`` axis — a point-to-point send, not
    a ring, because a migration has exactly one producer and one
    consumer.  The buffer is donated (alias mode: the transfer replaces
    it in place on the wire's far side).  The host-side fleet moves the
    same bytes through its d2h/h2d path today; this program is what the
    cost walker prices so the `collective_bytes` pin can hold the
    closed-form migration model (``kv_migration_bytes``) against an
    auditable trace, and what a future device-to-device DCN fast path
    compiles to."""
    n = axis_sizes(mesh)[DCN_AXIS]
    if n < 2:
        raise ValueError(
            f"kv block transfer needs >= 2 slices on {DCN_AXIS!r}, "
            f"got {n}")
    perm = [(src_slice % n, dst_slice % n)]

    def xfer(buf):
        return lax.ppermute(buf, DCN_AXIS, perm)

    sharded = shard_map(xfer, mesh=mesh, in_specs=P(DCN_AXIS),
                        out_specs=P(DCN_AXIS), check_vma=False)
    return jax.jit(sharded, donate_argnums=(0,))


# ---- program contracts (analysis/) ------------------------------------------


def lint_contracts():
    """Contracts for one outer round at both outer-sync settings. The
    load-bearing expectation is the ``outer="off"`` program: ZERO
    collectives on the dcn axis — including the metric scalar — because
    the bench that measures exposed DCN cost uses it as the no-DCN timing
    control; one stray latency-bound round-trip per round would poison
    the measurement. ``outer="on"`` pins the full DCN budget: one delta
    pmean per float param leaf + per float optimizer leaf, and the metric
    pmean over (dcn, data)."""
    from distributed_tensorflow_guide_tpu.analysis.contracts import (
        CostPin,
        CostSpec,
        DonationSpec,
        ProgramContract,
    )
    from distributed_tensorflow_guide_tpu.analysis.cost import closed_forms

    # tiny_mlp float state the outer sync pmeans: params + SGD momentum
    float_state_bytes = 2 * 4288
    sync_period, n_slices = 2, 2

    def _dcn_expect():
        return closed_forms().outer_sync_bytes(float_state_bytes, n_slices)

    def _inner_expect(n_metric_pmeans):
        def expect():
            import jax

            common = closed_forms()
            ici_world = jax.device_count() // n_slices
            return sync_period * common.dp_allreduce_bytes(
                4288, ici_world) + n_metric_pmeans * \
                common.dp_allreduce_bytes(4, ici_world)

        return expect

    def build(outer):
        def _build():
            from distributed_tensorflow_guide_tpu.analysis.fixtures import (
                tiny_mlp,
            )
            from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec

            loss_fn, state, batch = tiny_mlp()
            mesh = two_tier_mesh(MeshSpec(data=-1), n_slices=2)
            ms = MultiSliceLocalSGD(mesh, sync_period=2, outer=outer)
            tt = ms.init(state)
            step = ms.make_train_step(loss_fn, donate=True)
            batches = jax.tree.map(
                lambda x: jnp.stack([x, x]), batch)  # (sync_period, B, ...)
            return step, (tt, batches)

        return _build

    sources = ("distributed_tensorflow_guide_tpu.parallel.multislice",
               "distributed_tensorflow_guide_tpu.collectives.collectives")
    # tiny_mlp: 4 float param leaves (delta pmean) + 4 float momentum
    # leaves in the SGD trace state (opt-state pmean)
    n_dcn = 4 + 4
    return [
        ProgramContract(
            name="multislice_outer_on_round",
            build=build("on"),
            policy="f32",
            collectives={
                "psum[data]": 1,       # the inner grad pmean (scan body)
                "psum[dcn]": n_dcn,    # delta + float-opt-state sync
                "psum[dcn,data]": 1,   # the metric pmean over both tiers
            },
            donation=DonationSpec(argnums=(0,)),
            sources=sources,
            cost=CostSpec(
                pins=(
                    CostPin("collective_bytes[psum[dcn]]", _dcn_expect,
                            note="outer_sync_bytes over the float state "
                                 "(params + momentum), once per round"),
                    CostPin("collective_bytes[psum[data]]",
                            _inner_expect(0),
                            note="sync_period inner grad allreduces over "
                                 "the within-slice data axis; the metric "
                                 "pmean rides psum[dcn,data]"),
                ),
                max_peak_live_bytes=49152),
            notes="two-tier round: dense ICI inner steps, one DCN sync"),
        ProgramContract(
            name="multislice_outer_off_round",
            build=build("off"),
            policy="f32",
            # strict census: the inner grad pmean + the within-slice
            # metric pmean and NOTHING else — any dcn-axis collective
            # showing up here fails the lint
            collectives={"psum[data]": 2},
            donation=DonationSpec(argnums=(0,)),
            sources=sources,
            cost=CostSpec(
                pins=(
                    # the byte-level version of the DCN-free promise: the
                    # quantity resolves to 0.0 when the key is absent
                    CostPin("collective_bytes[psum[dcn]]", 0.0,
                            note="outer=off moves ZERO bytes over DCN"),
                    CostPin("collective_bytes[psum[data]]",
                            _inner_expect(1),
                            note="inner grad allreduces + the one "
                                 "within-slice scalar metric pmean"),
                ),
                max_peak_live_bytes=49152),
            notes="outer=off is DCN-free by contract (bench timing "
                  "control)"),
        _kv_transfer_contract(),
    ]


def _kv_transfer_contract():
    """Contract for the fleet KV-block migration program (PR 18): one
    point-to-point ppermute on the dcn axis and NOTHING else (strict
    census — a stray psum here would mean the migration path grew a
    synchronization it must not have), with an EXACT ``collective_bytes``
    pin against the closed-form migration model: the fixture's
    ``kv_migration_bytes`` divided by the slice count (the cost walker's
    per-device ppermute convention — bytes x hops / n_devices, one hop
    for a point-to-point send)."""
    from distributed_tensorflow_guide_tpu.analysis.contracts import (
        CostPin,
        CostSpec,
        DonationSpec,
        ProgramContract,
    )
    from distributed_tensorflow_guide_tpu.analysis.cost import closed_forms

    # fixture geometry mirrors the serve lint fixtures (L=2 layers, H=2
    # heads, 8-token blocks, head_dim 8) with 4 migrated blocks per
    # slice; payload rows are f32 here (the lint policy dtype), so the
    # closed form is evaluated at 4 bytes/elem
    L, H, BS, HD, NB = 2, 2, 8, 8, 4
    n_slices = 2

    def _xfer_expect():
        return closed_forms().kv_migration_bytes(
            NB, L, H, BS, HD, activation_dtype_bytes=4) / n_slices

    def _build():
        from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec

        mesh = two_tier_mesh(MeshSpec(data=-1), n_slices=n_slices)
        fn = make_kv_block_transfer(mesh)
        elems_per_block = 2 * L * H * BS * HD  # k and v rows
        buf = jnp.zeros((n_slices * NB, elems_per_block), jnp.float32)
        return fn, (buf,)

    return ProgramContract(
        name="serve_kv_block_transfer_dcn",
        build=_build,
        policy="f32",
        collectives={"ppermute[dcn]": 1},
        donation=DonationSpec(argnums=(0,)),
        sources=("distributed_tensorflow_guide_tpu.parallel.multislice",),
        cost=CostSpec(
            pins=(CostPin(
                "collective_bytes[ppermute[dcn]]", _xfer_expect,
                note="kv_migration_bytes(4 blocks, f32) / n_slices — "
                     "the closed-form migration model at the walker's "
                     "per-device single-hop convention"),),
            max_peak_live_bytes=49152),
        notes="point-to-point KV block handoff over DCN: the compiled "
              "model the fleet's migration counters reconcile against")
