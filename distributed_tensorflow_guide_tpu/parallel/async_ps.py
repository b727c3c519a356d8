"""Async parameter-server training, re-thought for a synchronous fabric.

The reference's main subject is async PS training via
``tf.train.replica_device_setter``
(tensorflow/python/training/device_setter.py:129) with three flavors:

  * ⚠ Hogwild/   — lock-free: every worker applies grads to PS-resident
    params immediately, racing freely (Niu et al. 2011).
  * ⚠ DOWNPOUR/  — workers accumulate local updates for ``fetch_period``
    steps, then push to the PS and pull fresh params (Dean et al. 2012).
  * ⚠ ADAG/      — async accumulated/adaptive gradients: workers push grads,
    the PS applies an adaptive (Adam-family) optimizer.

On TPU there is no PS and no asynchrony: the ICI fabric is globally
synchronous. The honest mapping (SURVEY.md §2c, judged config 4) keeps what
these algorithms *actually buy* — less communication per step and tolerance
of divergent local state — and replaces the mechanism:

  * Hogwild  → :class:`GossipSGD`: replicas update locally and mix params
    with a ring neighbor each step (one ``ppermute`` hop — O(1) comm vs
    allreduce's O(log n)/ring O(n) phases). Staleness is bounded by the ring
    diameter instead of unbounded PS races.
  * DOWNPOUR → :class:`LocalSGD`: ``sync_period`` local optimizer steps
    (``lax.scan``), then a parameter ``pmean``. "Push accumulated update,
    pull fresh params" becomes one collective every K steps — identical
    update algebra, deterministic instead of racy.
  * ADAG     → :class:`AccumulatedAdaptive`: accumulate grads over K
    sub-batches *without* applying, one ``pmean``, one global adaptive
    update — the PS-side Adam, minus the staleness.

The exact asynchronous semantics (stale reads, interleaved writes) are
preserved host-side in :mod:`.ps_emulator` for parity tests; the semantic
delta is documented in docs/async_ps_semantics.md.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

import distributed_tensorflow_guide_tpu.collectives as cc
from distributed_tensorflow_guide_tpu.core.mesh import axis_sizes
from distributed_tensorflow_guide_tpu.parallel.grad_accum import (
    accumulate_grads,
)

LossFn = Callable[[Any, Any], tuple[jax.Array, dict]]


def _pmean_floats(tree: Any, axis: str) -> Any:
    """pmean float leaves; pass through ints (identical across replicas —
    e.g. optax step counts), which integer pmean would corrupt."""
    return jax.tree.map(
        lambda x: cc.pmean(x, axis)
        if jnp.issubdtype(x.dtype, jnp.floating)
        else x,
        tree,
    )


class _Strategy:
    def __init__(self, mesh: Mesh, axis: str = "data"):
        self.mesh = mesh
        self.axis = axis
        self.world = axis_sizes(mesh)[axis]

    def shard_batch(self, batch: Any, *, leading_time_axis: bool = False) -> Any:
        spec = P(None, self.axis) if leading_time_axis else P(self.axis)
        return jax.device_put(batch, NamedSharding(self.mesh, spec))

    def replicate(self, state: Any) -> Any:
        return jax.device_put(state, NamedSharding(self.mesh, P()))


class LocalSGD(_Strategy):
    """DOWNPOUR on a synchronous fabric.

    Each replica runs ``sync_period`` optimizer steps on its own shard
    stream, then all replicas average parameters (and float optimizer state)
    with one pmean. With ``sync_period=1`` this IS sync DP — tested parity.

    The train step consumes a super-batch whose leaves are shaped
    ``(sync_period, per_replica_batch, ...)`` (use
    ``shard_batch(..., leading_time_axis=True)``).
    """

    def __init__(self, mesh: Mesh, sync_period: int, axis: str = "data"):
        super().__init__(mesh, axis)
        self.sync_period = sync_period

    def make_train_step(self, loss_fn: LossFn, *, donate: bool = True):
        def sm_step(state, batches):
            def inner(carry, sub):
                params, opt_state = carry
                (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, sub
                )
                updates, opt_state = state.tx.update(g, opt_state, params)
                params = optax.apply_updates(params, updates)
                return (params, opt_state), loss

            (params, opt_state), losses = lax.scan(
                inner, (state.params, state.opt_state), batches
            )
            # the "push accumulated update / pull fresh params" collective:
            params = _pmean_floats(params, self.axis)
            opt_state = _pmean_floats(opt_state, self.axis)
            state = state.replace(
                step=state.step + self.sync_period,
                params=params,
                opt_state=opt_state,
            )
            mets = {"loss": cc.pmean(losses.mean(), self.axis)}
            return state, mets

        sharded = shard_map(
            sm_step,
            mesh=self.mesh,
            in_specs=(P(), P(None, self.axis)),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=(0,) if donate else ())


class GossipSGD(_Strategy):
    """Hogwild's bounded-staleness sibling: local step + ring-neighbor mixing.

    Per step each replica applies its local gradient, then mixes parameters
    with its two ring neighbors (two ppermute hops — both single ICI-neighbor
    transfers): ``p <- (1-mix)*p + mix/2*(left + right)``. Information
    diffuses around the ring in ``world/2`` steps, so staleness is bounded by
    the ring diameter; the PS race of Hogwild is unbounded. Comm per step is
    neighbor-only vs a full allreduce — the same "cheap, loose" trade Hogwild
    makes.

    Because replicas genuinely hold *different* parameters (the whole point),
    state lives with a leading replica axis sharded over ``axis``: leaf
    shapes are ``(world, ...)``. Use :meth:`distribute` / :meth:`consensus`
    to enter/leave that representation.
    """

    def __init__(self, mesh: Mesh, axis: str = "data", mix: float = 0.5):
        super().__init__(mesh, axis)
        self.mix = mix

    def distribute(self, state: Any) -> Any:
        """Tile a replicated state to per-replica copies, sharded on axis 0."""
        tiled = jax.tree.map(
            lambda x: jnp.broadcast_to(jnp.asarray(x)[None], (self.world, *jnp.shape(x))),
            state,
        )
        return jax.device_put(tiled, NamedSharding(self.mesh, P(self.axis)))

    def make_train_step(self, loss_fn: LossFn, *, donate: bool = True):
        fwd = [(i, (i + 1) % self.world) for i in range(self.world)]
        bwd = [(i, (i - 1) % self.world) for i in range(self.world)]

        def sm_step(state, batch):
            local = jax.tree.map(lambda x: x[0], state)  # drop replica dim
            (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                local.params, batch
            )
            local = local.apply_gradients(grads=g)  # purely local update
            mixed = jax.tree.map(
                lambda p: (1.0 - self.mix) * p
                + (self.mix / 2.0)
                * (
                    lax.ppermute(p, self.axis, fwd)
                    + lax.ppermute(p, self.axis, bwd)
                ),
                local.params,
            )
            local = local.replace(params=mixed)
            new_state = jax.tree.map(lambda x: x[None], local)
            return new_state, {"loss": cc.pmean(loss, self.axis)}

        sharded = shard_map(
            sm_step,
            mesh=self.mesh,
            in_specs=(P(self.axis), P(self.axis)),
            out_specs=(P(self.axis), P()),
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=(0,) if donate else ())

    def consensus(self, state: Any) -> Any:
        """Average the per-replica parameter copies (for eval/checkpoint);
        XLA inserts the cross-device reduction from the sharding."""
        return jax.jit(
            lambda s: jax.tree.map(lambda x: jnp.mean(x, axis=0), s.params)
        )(state)


class AccumulatedAdaptive(_Strategy):
    """ADAG on a synchronous fabric: accumulate grads over ``accum_steps``
    sub-batches (no local apply), pmean once, apply the adaptive optimizer
    globally. The PS's Adam state becomes replicated optimizer state updated
    identically everywhere; accumulation cuts collective frequency by
    ``accum_steps``x, the same bandwidth economy DOWNPOUR/ADAG bought.

    Super-batch leaves: ``(accum_steps, per_replica_batch, ...)``.
    """

    def __init__(self, mesh: Mesh, accum_steps: int, axis: str = "data"):
        super().__init__(mesh, axis)
        self.accum_steps = accum_steps

    def make_train_step(self, loss_fn: LossFn, *, donate: bool = True):
        def sm_step(state, batches):
            g, (losses, _) = accumulate_grads(
                loss_fn, state.params, batches, self.accum_steps
            )
            g = cc.pmean(g, self.axis)
            state = state.apply_gradients(grads=g)
            return state, {"loss": cc.pmean(losses.mean(), self.axis)}

        sharded = shard_map(
            sm_step,
            mesh=self.mesh,
            in_specs=(P(), P(None, self.axis)),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=(0,) if donate else ())
