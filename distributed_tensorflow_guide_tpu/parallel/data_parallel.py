"""Synchronous data parallelism — the Synchronous-SGD / MirroredStrategy track.

Reference equivalents:
  * ``SyncReplicasOptimizer``
    (tensorflow/python/training/sync_replicas_optimizer.py:42): workers push
    grads to per-variable accumulators on the PS; the chief applies once
    ``replicas_to_aggregate`` arrive and releases workers via a token queue.
  * Modern surface: ``MirroredStrategy``
    (tensorflow/python/distribute/mirrored_strategy.py:200) /
    ``CollectiveAllReduceStrategy``
    (tensorflow/python/distribute/collective_all_reduce_strategy.py:57) with
    NCCL allreduce (cross_device_ops.py:961).

TPU-native inversion: the accumulator + token-queue barrier *is* ``psum`` on
the ICI ring — hardware-synchronous, no chief, no PS. One compiled SPMD step:
per-shard forward/backward, explicit ``pmean`` of grads over the ``data``
axis, identical optimizer update everywhere. ``check_vma=False`` because the
collective is explicit (with vma checking on, jax.grad w.r.t. replicated
params already inserts the psum and an explicit pmean would double-reduce).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

import distributed_tensorflow_guide_tpu.collectives as cc
from distributed_tensorflow_guide_tpu.core.mesh import axis_sizes
from distributed_tensorflow_guide_tpu.parallel.grad_accum import (
    accumulate_grads,
)

# loss_fn(params, batch) -> (scalar loss, dict of scalar metrics)
LossFn = Callable[[Any, Any], tuple[jax.Array, dict]]


class DataParallel:
    """Build compiled sync-DP train/eval steps over a mesh's ``data`` axis.

    ``overlap`` ("auto"|True|False, default off) routes the gradient
    all-reduce through the bucketed backward path (parallel/overlap.py):
    per-bucket ``custom_vjp`` boundary markers on the parameter tree emit
    each bucket's pmean mid-backward — where XLA's latency-hiding
    scheduler can hide it under the remaining backward compute — instead
    of one monolithic pmean after the full gradient tree. Bitwise-
    identical gradients (all-reduce is elementwise per leaf; pinned in
    tests/test_overlap.py); ``auto`` resolves on only for TPU, so CPU
    tier-1 traces stay byte-identical to the overlap-off program.
    ``bucket_bytes`` overrides the autotune-table bucket budget.
    """

    def __init__(self, mesh: Mesh, axis: str = "data", *,
                 overlap="off", bucket_bytes: int | None = None,
                 compress: str | None = None):
        from distributed_tensorflow_guide_tpu.parallel import (
            overlap as overlap_mod,
        )

        self.mesh = mesh
        self.axis = axis
        self.world = axis_sizes(mesh)[axis]
        self.overlap = overlap_mod.resolve_overlap(overlap)
        self.bucket_bytes = bucket_bytes
        # int8-compressed gradient all-reduce (ops/quant.int8_pmean):
        # rides the bucket seams, so it requires the bucketed backward —
        # the mono pmean stays the bitwise-exact historical program.
        self.compress = overlap_mod.resolve_compress(compress)
        if self.compress and not self.overlap:
            raise ValueError(
                "compress='int8' rides the bucketed backward — it "
                "requires overlap=True (the monolithic pmean path stays "
                "bitwise-exact by contract)")

    # ---- data placement ----------------------------------------------------
    def shard_batch(self, batch: Any) -> Any:
        """Place a host batch onto the mesh, sharded along the leading axis.

        Single-process: ``batch`` is the global batch. Multi-process SPMD:
        ``batch`` is this process's equal share (global/process_count rows,
        e.g. from a process-sharded data loader) and the global array is
        assembled shard-wise — each host's rows land on its own devices, no
        cross-host transfer (the TF analogue is per-worker input pipelines
        under MultiWorkerMirroredStrategy, not one host scattering to all).
        """
        sharding = NamedSharding(self.mesh, P(self.axis))
        if jax.process_count() > 1:
            return jax.tree.map(
                lambda x: jax.make_array_from_process_local_data(sharding, x),
                batch,
            )
        return jax.device_put(batch, sharding)

    def batch_sharding(self, stacked: bool = False) -> NamedSharding:
        """The placement of a step's batch argument: leading axis sharded
        over ``data`` — or, for a ``stacked_batch`` multi-step super-batch,
        the SECOND axis (the leading one is the inner-step index)."""
        spec = P(None, self.axis) if stacked else P(self.axis)
        return NamedSharding(self.mesh, spec)

    def shard_packed_batch(self, packed: Any) -> Any:
        """Place one ``steps_per_call`` super-batch (leading axis = inner
        step, from data/prefetch.py ``pack_batches``) onto the mesh."""
        sharding = self.batch_sharding(stacked=True)
        if jax.process_count() > 1:
            return jax.tree.map(
                lambda x: jax.make_array_from_process_local_data(sharding, x),
                packed,
            )
        return jax.device_put(packed, sharding)

    def prefetch(self, source, *, depth: int = 2, steps_per_call: int = 1,
                 drop_remainder: bool = True):
        """Wrap a host-batch iterable in the device-prefetch overlap stage
        (data/prefetch.py), placed with this strategy's sharding. With
        ``steps_per_call > 1`` each yielded item is a packed super-batch
        ready for the multi-step compiled step."""
        from distributed_tensorflow_guide_tpu.data.prefetch import (
            prefetch_to_device,
        )

        put = (self.shard_packed_batch if steps_per_call > 1
               else self.shard_batch)
        return prefetch_to_device(source, depth=depth, put_fn=put,
                                  steps_per_call=steps_per_call,
                                  drop_remainder=drop_remainder)

    def replicate(self, state: Any) -> Any:
        """Replicate a state pytree across every device (params live
        everywhere — the anti-PS: no parameter server holds them)."""
        return jax.device_put(state, NamedSharding(self.mesh, P()))

    # ---- compiled steps ----------------------------------------------------
    def _compile_step(self, sm_step, donate: bool, steps_per_call: int = 1,
                      stacked_batch: bool = False,
                      per_step_metrics: bool = False):
        """shard_map + jit a per-device ``(state, batch) -> (state, metrics)``
        body: state replicated, batch sharded on its leading axis,
        explicit collectives (hence check_vma=False).

        ``steps_per_call > 1`` runs that many optimizer steps inside ONE
        compiled program (a ``lax.scan`` around the sharded step) — the TF
        ``steps_per_run`` / Keras ``steps_per_execution`` knob. Each
        executable dispatch costs host time; the round-3 ResNet-50 trace had
        a 46.9 ms device step in 62 ms of wall-clock — ~15 ms/step between
        dispatches, which this knob amortizes away (not re-measured on this
        machine). With ``stacked_batch`` the
        batch carries a leading ``steps_per_call`` axis (one microbatch per
        inner step — the real-training mode); otherwise the same batch is
        re-used every inner step (synthetic benchmarking mode). Metrics
        returned are the LAST inner step's, unless ``per_step_metrics``:
        then every metric keeps the scan's leading ``steps_per_call`` axis,
        one slice per inner step — what lets TrainLoop keep hooks observing
        every optimizer step across a fused dispatch.
        """
        if steps_per_call < 1:
            raise ValueError(
                f"steps_per_call must be >= 1, got {steps_per_call}"
            )
        if steps_per_call == 1:
            if stacked_batch:
                raise ValueError(
                    "stacked_batch requires steps_per_call > 1 (a stacked "
                    "batch's leading axis is consumed one slice per inner "
                    "step)"
                )
            sharded = shard_map(
                sm_step,
                mesh=self.mesh,
                in_specs=(P(), P(self.axis)),
                out_specs=(P(), P()),
                check_vma=False,
            )
            return jax.jit(sharded, donate_argnums=(0,) if donate else ())

        def pick(ms):
            return ms if per_step_metrics else jax.tree.map(
                lambda x: x[-1], ms)

        if stacked_batch:
            def multi(state, batch):
                lead = {jax.tree.leaves(batch)[0].shape[0]}
                if lead != {steps_per_call}:
                    raise ValueError(
                        f"stacked batch leading axis {lead} != "
                        f"steps_per_call={steps_per_call}; the scan would "
                        "silently run a different number of optimizer steps"
                    )

                state, ms = lax.scan(sm_step, state, batch)
                return state, pick(ms)
        else:
            def multi(state, batch):
                def body(st, _):
                    st, m = sm_step(st, batch)
                    return st, m

                state, ms = lax.scan(
                    body, state, None, length=steps_per_call
                )
                return state, pick(ms)

        batch_spec = (P(None, self.axis) if stacked_batch
                      else P(self.axis))
        multi_sharded = shard_map(
            multi,
            mesh=self.mesh,
            in_specs=(P(), batch_spec),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return jax.jit(multi_sharded, donate_argnums=(0,) if donate else ())

    def _pmean_metrics(self, mets: dict) -> dict:
        return {k: cc.pmean(v, self.axis) for k, v in mets.items()}

    def _grad_loss_fn(self, loss_fn):
        """The loss the backward differentiates: with overlap on, params
        are wrapped in per-bucket sync markers so gradients come out
        already pmean-ed (the call sites then skip the monolithic pmean);
        with overlap off it is ``loss_fn`` itself — the identical object,
        so the traced program cannot drift byte-wise."""
        if not self.overlap:
            return loss_fn
        from distributed_tensorflow_guide_tpu.parallel import (
            overlap as overlap_mod,
        )

        return overlap_mod.bucketed_loss_fn(
            loss_fn, self.axis, self.bucket_bytes, compress=self.compress)

    def make_train_step(self, loss_fn: LossFn, *, donate: bool = True,
                        accum_steps: int = 1, steps_per_call: int = 1,
                        stacked_batch: bool = False,
                        per_step_metrics: bool = False):
        """Compile ``(state, batch) -> (state, metrics)``.

        ``state`` is a flax TrainState (replicated); ``batch`` a pytree
        sharded on its leading axis. Gradients are explicitly pmean-ed: the
        update is bit-identical on every device, which is what keeps replicas
        in lockstep without ever broadcasting parameters.

        ``accum_steps > 1`` splits each device's shard into that many
        microbatches and accumulates gradients over a ``lax.scan`` before the
        single pmean + update — the DOWNPOUR 'fetch_period' knob reborn as a
        memory knob: identical numerics to the full batch (mean-of-means over
        equal microbatches), activation memory divided by ``accum_steps``,
        and still exactly one collective per step. The per-device shard
        length must divide by ``accum_steps``.
        """
        if self.overlap and accum_steps > 1:
            # pmean-per-microbatch then mean != mean then pmean bitwise
            # (summation order), and per-microbatch collectives would
            # multiply the wire traffic by accum_steps — the knobs solve
            # different problems (memory vs exposure); pick one.
            raise ValueError(
                "overlap=True is incompatible with accum_steps > 1: the "
                "bucketed backward reduces per microbatch backward, which "
                "breaks the bitwise-identity contract with the single "
                "post-accumulation pmean and multiplies collective traffic "
                f"by accum_steps={accum_steps}")
        grad_loss_fn = self._grad_loss_fn(loss_fn)

        def sm_step(state, batch):
            if accum_steps == 1:
                (loss, mets), grads = jax.value_and_grad(
                    grad_loss_fn, has_aux=True
                )(state.params, batch)
            else:
                shard_len = jax.tree.leaves(batch)[0].shape[0]
                if shard_len % accum_steps:
                    raise ValueError(
                        f"per-device batch shard of {shard_len} rows is not "
                        f"divisible by accum_steps={accum_steps}; pick a "
                        "global batch size that is a multiple of "
                        f"data_parallel_size * accum_steps"
                    )
                micro = jax.tree.map(
                    lambda x: x.reshape(
                        accum_steps, x.shape[0] // accum_steps, *x.shape[1:]
                    ),
                    batch,
                )
                grads, (losses, metas) = accumulate_grads(
                    loss_fn, state.params, micro, accum_steps
                )
                loss = jnp.mean(losses)
                mets = jax.tree.map(jnp.mean, metas)
            if not self.overlap:  # bucketed bwd already reduced them
                grads = cc.pmean(grads, self.axis)
            state = state.apply_gradients(grads=grads)
            return state, self._pmean_metrics({"loss": loss, **mets})

        return self._compile_step(sm_step, donate, steps_per_call,
                                  stacked_batch, per_step_metrics)

    def make_train_step_with_stats(self, loss_fn, *, donate: bool = True,
                                   steps_per_call: int = 1,
                                   stacked_batch: bool = False,
                                   per_step_metrics: bool = False):
        """Like :meth:`make_train_step` for models with non-trainable state
        (BatchNorm running stats).

        ``loss_fn(params, model_state, batch) ->
        (loss, (metrics, new_model_state))``; ``state`` is a
        :class:`~distributed_tensorflow_guide_tpu.train.state.TrainStateWithStats`.
        New model state is pmean-ed across replicas — synchronized running
        statistics, matching MultiWorkerMirroredStrategy's aggregation of
        BN updates rather than the reference PS examples' last-writer-wins
        race on PS-resident stats.
        """

        grad_loss_fn = self._grad_loss_fn(loss_fn)

        def sm_step(state, batch):
            (loss, (mets, new_ms)), grads = jax.value_and_grad(
                grad_loss_fn, has_aux=True
            )(state.params, state.model_state, batch)
            if not self.overlap:  # bucketed bwd already reduced them
                grads = cc.pmean(grads, self.axis)
            new_ms = cc.pmean(new_ms, self.axis)
            state = state.apply_gradients(grads=grads, model_state=new_ms)
            return state, self._pmean_metrics({"loss": loss, **mets})

        return self._compile_step(sm_step, donate, steps_per_call,
                                  stacked_batch, per_step_metrics)

    def make_eval_step(self, metric_fn: Callable[[Any, Any], dict]):
        """Compile ``(state, batch) -> metrics`` with pmean-ed metrics."""

        def sm_eval(state, batch):
            mets = metric_fn(state.params, batch)
            return {k: cc.pmean(v, self.axis) for k, v in mets.items()}

        sharded = shard_map(
            sm_eval,
            mesh=self.mesh,
            in_specs=(P(), P(self.axis)),
            out_specs=P(),
            check_vma=False,
        )
        return jax.jit(sharded)

    def make_eval_step_with_stats(self, metric_fn):
        """:meth:`make_eval_step` for models with non-trainable state:
        ``metric_fn(params, model_state, batch) -> {name: scalar}``.
        Evaluation reads the (already replica-synchronized) running stats
        — BatchNorm in inference mode — and never writes them back."""

        def sm_eval(state, batch):
            mets = metric_fn(state.params, state.model_state, batch)
            return {k: cc.pmean(v, self.axis) for k, v in mets.items()}

        sharded = shard_map(
            sm_eval,
            mesh=self.mesh,
            in_specs=(P(), P(self.axis)),
            out_specs=P(),
            check_vma=False,
        )
        return jax.jit(sharded)


# ---- program contracts (analysis/) ------------------------------------------


def lint_contracts():
    """Contracts for the static-analysis linter: the mono train step
    (one grad pmean + one pmean per metric) and the bucketed-overlap step,
    whose collective count is DERIVED from the bucket partition — N
    buckets must mean exactly N mid-backward grad psums, the structure
    the latency-hiding scheduler needs."""
    import dataclasses

    import numpy as np

    from distributed_tensorflow_guide_tpu.analysis.contracts import (
        CostPin,
        CostSpec,
        DonationSpec,
        ProgramContract,
    )
    from distributed_tensorflow_guide_tpu.analysis.cost import closed_forms
    from distributed_tensorflow_guide_tpu.core.mesh import (
        MeshSpec,
        build_mesh,
    )
    from distributed_tensorflow_guide_tpu.parallel import overlap

    def build(overlap_on, compress=None):
        def _build():
            from distributed_tensorflow_guide_tpu.analysis.fixtures import (
                tiny_mlp,
            )

            loss_fn, state, batch = tiny_mlp()
            mesh = build_mesh(MeshSpec(data=-1))
            dp = DataParallel(mesh, overlap=overlap_on,
                              bucket_bytes=1 if overlap_on else None,
                              compress=compress)
            step = dp.make_train_step(loss_fn, donate=True)
            return step, (state, batch)

        return _build

    sources = ("distributed_tensorflow_guide_tpu.parallel.data_parallel",
               "distributed_tensorflow_guide_tpu.parallel.overlap",
               "distributed_tensorflow_guide_tpu.collectives.collectives")
    # the tiny_mlp param tree at bucket_bytes=1: one bucket per leaf
    leaf_shapes = [(16, 32), (32,), (32, 16), (16,)]
    grad_bytes = sum(int(np.prod(s)) * 4 for s in leaf_shapes)  # 4288
    n_buckets = len(overlap.bucket_assignment(
        [np.zeros(s, np.float32) for s in leaf_shapes], bucket_bytes=1))

    def _grad_allreduce_expect():
        # grad-tree ring allreduce + the loss and mae scalar metric pmeans
        import jax

        common = closed_forms()
        world = jax.device_count()
        return (common.dp_allreduce_bytes(grad_bytes, world)
                + 2 * common.dp_allreduce_bytes(4, world))

    def _int8_allreduce_expect():
        # the same grad tree at 1 byte/elem on the wire (the int8 payload
        # of the compressed buckets) + the 2 f32 scalar metric pmeans
        import jax

        common = closed_forms()
        world = jax.device_count()
        return (common.dp_allreduce_bytes(grad_bytes, world,
                                          compress="int8")
                + 2 * common.dp_allreduce_bytes(4, world))

    def _scale_sidechannel_expect():
        # one f32 amax scalar rides a ring pmax per bucket
        import jax

        common = closed_forms()
        world = jax.device_count()
        return n_buckets * common.dp_allreduce_bytes(4, world)

    def _flops_expect():
        # the 3x-forward MFU convention counts 6 forward-equivalent
        # matmuls per step; the real backward of a 2-layer MLP skips the
        # first layer's input-grad matmul, so the trace holds 5 of them —
        # and the auditor sees PER-DEVICE shapes inside shard_map
        import jax

        from distributed_tensorflow_guide_tpu.analysis.fixtures import (
            tiny_mlp,
        )

        loss_fn, state, batch = tiny_mlp()
        common = closed_forms()
        full = common.model_flops_per_step(loss_fn, state.params, batch)
        return full / jax.device_count() * 5.0 / 6.0

    dp_cost = CostSpec(
        pins=(
            CostPin("collective_bytes[psum[data]]", _grad_allreduce_expect,
                    note="comm_bytes_model: 2*G*(n-1)/n grad ring + 2 "
                         "scalar metric pmeans"),
            CostPin("flops", _flops_expect,
                    note="5/6 of the 3x-fwd convention (no input-grad "
                         "matmul at layer 0), per device"),
        ),
        max_peak_live_bytes=20480)
    return [
        ProgramContract(
            name="dp_train_step",
            build=build(False),
            policy="f32",
            # 1 grad-tree pmean + the loss and mae metric pmeans
            collectives={"psum[data]": 3},
            donation=DonationSpec(argnums=(0,)),
            sources=sources,
            cost=dp_cost,
            notes="sync-DP mono step: one gradient collective per step"),
        ProgramContract(
            name="dp_overlap_train_step",
            build=build(True),
            policy="f32",
            # one psum per gradient bucket (emitted mid-backward) + the
            # 2 metric pmeans — the bucket partition IS the expectation
            collectives={"psum[data]": n_buckets + 2},
            donation=DonationSpec(argnums=(0,)),
            sources=sources,
            # same bytes as the mono step (bucketing changes WHEN psums
            # fire, not how much they move); buckets die mid-backward so
            # the peak sits ~2KiB below the mono step's
            cost=dataclasses.replace(dp_cost, max_peak_live_bytes=18432),
            notes=f"bucketed backward: {n_buckets} buckets -> "
                  f"{n_buckets} grad psums"),
        ProgramContract(
            name="dp_overlap_int8_round",
            build=build(True, compress="int8"),
            policy="f32",
            # one int8 psum per gradient bucket + the 2 f32 metric pmeans,
            # plus one scalar pmax per bucket — the shared-scale f32
            # side-channel of the compressed wire format
            collectives={"psum[data]": n_buckets + 2,
                         "pmax[data]": n_buckets},
            donation=DonationSpec(argnums=(0,)),
            sources=sources,
            cost=dataclasses.replace(
                dp_cost,
                pins=(
                    CostPin("collective_bytes[psum[data]]",
                            _int8_allreduce_expect,
                            note="grad ring at 1 byte/elem "
                                 "(compress='int8') + 2 scalar metric "
                                 "pmeans at f32"),
                    CostPin("collective_bytes[pmax[data]]",
                            _scale_sidechannel_expect,
                            note="one f32 amax scalar per bucket: the "
                                 "shared-scale side-channel"),
                    dp_cost.pins[1],  # same matmul flops: only the wire
                                      # representation changed
                ),
                # measured 21212: the f32 bucket budget (18432) plus the
                # transient int8 shadow buffers + f32 scales the quantize/
                # dequant seam holds while the wire copy is in flight
                max_peak_live_bytes=22528),
            notes=f"int8-compressed bucketed backward: {n_buckets} "
                  "buckets at a quarter of the grad bytes + "
                  f"{n_buckets} scale pmaxes"),
    ]
