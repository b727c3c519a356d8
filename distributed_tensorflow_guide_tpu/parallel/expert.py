"""Expert parallelism (MoE) over the ``expert`` mesh axis.

Not in the reference (SURVEY.md §2c EP row — the guide predates MoE); built
because the framework mandate makes every parallelism family first-class.
The reference's closest ancestor is its async-PS *sharding of whole
variables* across PS tasks (tensorflow/python/training/device_setter.py:129
round-robins variables over /job:ps) — EP is the modern descendant: shard
whole *experts* across devices and move the **tokens** to the experts
instead of the parameters to the workers.

Design (GShard/Switch dense-dispatch, TPU-first):

* Routing produces fixed-capacity dispatch/combine tensors via one-hot
  einsums — **static shapes only**, so XLA tiles everything onto the MXU;
  no gather/scatter, no dynamic shapes, overflow tokens drop (standard
  capacity-factor semantics).
* Token exchange is one ``all_to_all`` each way over the ``expert`` ICI
  ring (collectives/collectives.py all_to_all → lax.all_to_all), exactly
  the NCCL-alltoall pattern GPU MoE stacks use, but compiler-scheduled.
* Expert FFNs run as one batched einsum over the local expert shard —
  E_local weight matrices multiply in a single MXU-friendly contraction.

Aux outputs follow Switch Transformer: load-balance loss
``E * Σ_e f_e·p_e`` and router z-loss.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

import distributed_tensorflow_guide_tpu.collectives as cc
from distributed_tensorflow_guide_tpu.core.mesh import axis_sizes


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int
    num_experts: int          # global expert count, divisible by axis size
    top_k: int = 2
    capacity_factor: float = 1.25
    # "switch": fixed-capacity GShard/Switch dispatch — tokens past
    # ``capacity_factor`` headroom drop. "dropless": capacity-factor-free —
    # capacity widens to the per-shard token count, which provably admits
    # every token (a token picks each expert at most once, so no expert can
    # receive more than T_local rows), at the price of an E×-larger dispatch
    # buffer. Same one-hot algebra, same all_to_all census, zero drops.
    router: str = "switch"
    axis: str = "expert"
    # mesh axes (besides `axis`) that also shard the token dimension; aux
    # statistics are averaged over all of them so every device reports the
    # same global value. None for pure-EP shard_maps with no data axis bound.
    data_axis: str | None = "data"
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.router not in ("switch", "dropless"):
            raise ValueError(
                f"router must be 'switch' or 'dropless', got {self.router!r}")

    @property
    def token_axes(self) -> tuple[str, ...]:
        return (self.data_axis, self.axis) if self.data_axis else (self.axis,)


def init_moe_params(cfg: MoEConfig, rng) -> dict:
    """Router replicated; expert stacks laid out (E, d, ff)/(E, ff, d) so the
    leading axis shards over the ``expert`` mesh axis."""
    kr, ki, ko = jax.random.split(rng, 3)
    scale_in = 1.0 / np.sqrt(cfg.d_model)
    scale_out = 1.0 / np.sqrt(cfg.d_ff)
    return {
        "router": (jax.random.normal(kr, (cfg.d_model, cfg.num_experts))
                   * scale_in).astype(cfg.dtype),
        "w_in": (jax.random.normal(
            ki, (cfg.num_experts, cfg.d_model, cfg.d_ff)) * scale_in
        ).astype(cfg.dtype),
        "w_out": (jax.random.normal(
            ko, (cfg.num_experts, cfg.d_ff, cfg.d_model)) * scale_out
        ).astype(cfg.dtype),
    }


def _topk_dispatch(gates: jax.Array, top_k: int, capacity: int):
    """Fixed-capacity top-k assignment, entirely as one-hot algebra.

    Returns ``dispatch`` (T, E, C) in {0,1} and ``combine`` (T, E, C)
    gate-weighted. Slot s of each token goes to its s-th-choice expert at
    the next free capacity slot; tokens past capacity are dropped (their
    dispatch row is zero). No sorting, no dynamic shapes.
    """
    t, e = gates.shape
    dispatch = jnp.zeros((t, e, capacity), gates.dtype)
    combine = jnp.zeros((t, e, capacity), gates.dtype)
    fill = jnp.zeros((e,), jnp.int32)   # capacity slots already used
    g = gates
    for _ in range(top_k):
        idx = jnp.argmax(g, axis=1)                      # (T,)
        onehot = jax.nn.one_hot(idx, e, dtype=gates.dtype)
        # position of each token within its chosen expert's buffer
        pos = (jnp.cumsum(onehot, axis=0) - onehot) + fill[None, :]
        pos_i = pos.astype(jnp.int32)
        keep = onehot * (pos_i < capacity)
        slot = keep[:, :, None] * jax.nn.one_hot(
            pos_i, capacity, dtype=gates.dtype)           # (T, E, C)
        gate_val = jnp.sum(gates * onehot, axis=1)        # (T,)
        dispatch = dispatch + slot
        combine = combine + slot * gate_val[:, None, None]
        fill = fill + jnp.sum(keep, axis=0).astype(jnp.int32)
        g = g * (1.0 - onehot)                            # mask chosen expert
    return dispatch, combine


def moe_ffn(params: dict, x: jax.Array, cfg: MoEConfig):
    """One MoE FFN layer. Must run inside shard_map with ``x`` token-sharded
    and expert stacks sharded over ``cfg.axis``.

    Per-device shapes: x (T_local, d); w_in (E_local, d, ff).
    Returns (y (T_local, d), aux dict with load_balance/z losses).
    """
    n_dev = cc.axis_size(cfg.axis)
    e_global = cfg.num_experts
    e_local = params["w_in"].shape[0]
    if e_local * n_dev != e_global:
        raise ValueError(
            f"{e_global} experts over {n_dev} devices needs "
            f"{e_global // n_dev} local, got {e_local}")
    t_local = x.shape[0]
    if cfg.router == "dropless":
        # Each token selects an expert at most once across the top_k rounds
        # (the chosen column is masked between rounds), so no expert is ever
        # assigned more than t_local rows: capacity == t_local admits every
        # token and _topk_dispatch's ``pos < capacity`` guard never fires.
        capacity = t_local
    else:
        capacity = max(1, int(np.ceil(
            cfg.top_k * t_local * cfg.capacity_factor / e_global)))

    # router always in fp32: routing decisions are precision-sensitive
    logits = x.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    dispatch, combine = _topk_dispatch(gates, cfg.top_k, capacity)

    # Switch aux losses, averaged over every token-sharding axis so the
    # returned values are truly replicated (out_specs P() honest)
    frac_tokens = cc.pmean(jnp.mean(dispatch.sum(-1), axis=0), cfg.token_axes)
    frac_probs = cc.pmean(jnp.mean(gates, axis=0), cfg.token_axes)
    load_balance = e_global * jnp.sum(frac_tokens * frac_probs) / cfg.top_k
    z_loss = cc.pmean(jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
                      cfg.token_axes)

    xd = x.astype(cfg.dtype)
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(cfg.dtype), xd)
    # (E_global, C, d) -> (E_local, n_dev*C, d): rows for MY experts from all
    # devices land here
    expert_in = cc.all_to_all(expert_in, cfg.axis, split_axis=0, concat_axis=1)
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", expert_in, params["w_in"]))
    out = jnp.einsum("ecf,efd->ecd", h, params["w_out"])
    # route results back: (E_local, n_dev*C, d) -> (E_global, C, d)
    out = cc.all_to_all(out, cfg.axis, split_axis=1, concat_axis=0)
    y = jnp.einsum("tec,ecd->td", combine.astype(cfg.dtype), out)
    return y.astype(x.dtype), {"load_balance": load_balance, "z_loss": z_loss}


class ExpertParallel:
    """Harness: shard params/tokens over the ``expert`` axis and build a
    jitted training step for a standalone MoE layer (the transformer wiring
    lives in models/; this class is the EP sibling of parallel/tensor.py's
    TensorParallel)."""

    def __init__(self, mesh: Mesh, cfg: MoEConfig):
        if cfg.axis not in axis_sizes(mesh):
            raise ValueError(
                f"mesh axes {tuple(axis_sizes(mesh))} lack {cfg.axis!r}")
        if cfg.num_experts % axis_sizes(mesh)[cfg.axis]:
            raise ValueError(
                f"num_experts {cfg.num_experts} not divisible by "
                f"{cfg.axis} axis size {axis_sizes(mesh)[cfg.axis]}")
        self.mesh = mesh
        self.cfg = cfg
        self.param_spec = {
            "router": P(),
            "w_in": P(cfg.axis),
            "w_out": P(cfg.axis),
        }
        # tokens sharded over data AND expert axes jointly: every device in
        # the (data x expert) grid holds a distinct token shard
        self.token_spec = P(cfg.token_axes)

    def shard_params(self, params: dict) -> dict:
        return jax.device_put(
            params,
            {k: NamedSharding(self.mesh, s)
             for k, s in self.param_spec.items()},
        )

    def apply(self, params: dict, x: jax.Array):
        """Jitted sharded forward: x (T_global, d) -> (y, aux). The jitted
        function is built once (per instance) so repeated calls hit the
        trace cache instead of recompiling."""
        if not hasattr(self, "_apply_jit"):
            cfg = self.cfg

            @functools.partial(
                jax.jit,
                in_shardings=(
                    {k: NamedSharding(self.mesh, s)
                     for k, s in self.param_spec.items()},
                    NamedSharding(self.mesh, self.token_spec),
                ),
            )
            def run(params, x):
                fn = functools.partial(moe_ffn, cfg=cfg)
                return shard_map(
                    fn, mesh=self.mesh,
                    in_specs=(self.param_spec, self.token_spec),
                    out_specs=(self.token_spec, P()),
                    check_vma=False,
                )(params, x)

            self._apply_jit = run
        return self._apply_jit(params, x)

    def make_train_step(self, lr: float = 0.1, *, aux_weight: float = 1e-2):
        """Jitted SGD step on an MSE toy objective — exercises the full EP
        path (routing, both all_to_alls, expert einsums, grads, reductions).
        Real models plug :func:`moe_ffn` into their blocks instead."""
        cfg = self.cfg
        p_specs = {k: NamedSharding(self.mesh, s)
                   for k, s in self.param_spec.items()}

        def step(params, x, y_target):
            def loss_fn(p):
                y, aux = moe_ffn(p, x, cfg)
                se = jnp.sum((y - y_target) ** 2)
                n = jnp.array(y.size, jnp.float32)
                loss = (cc.psum(se, cfg.token_axes)
                        / cc.psum(n, cfg.token_axes)
                        + aux_weight * (aux["load_balance"] + aux["z_loss"]))
                return loss, aux

            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params)
            # the loss is already the GLOBAL mean, so each device's grad is a
            # partial contribution and the reduction is psum (a pmean here
            # would under-scale by the axis size). Replicated router: sum
            # over every token-shard axis; expert stacks: contributions from
            # the expert axis already arrived via the backward all_to_all,
            # so sum over data only.
            grads["router"] = cc.psum(grads["router"], cfg.token_axes)
            if cfg.data_axis:
                grads["w_in"] = cc.psum(grads["w_in"], cfg.data_axis)
                grads["w_out"] = cc.psum(grads["w_out"], cfg.data_axis)
            params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype),
                                  params, grads)
            return params, {"loss": loss, **aux}

        sm = shard_map(
            step, mesh=self.mesh,
            in_specs=(self.param_spec, self.token_spec, self.token_spec),
            out_specs=(self.param_spec, P()),
            check_vma=False,
        )
        return jax.jit(
            sm,
            in_shardings=(p_specs,
                          NamedSharding(self.mesh, self.token_spec),
                          NamedSharding(self.mesh, self.token_spec)),
        )
