"""Switch-Transformer LM — the MoE model family, wired end to end.

``parallel/expert.py`` provides the EP machinery (static-shape top-k
dispatch, dual ``all_to_all`` token exchange, Switch aux losses) as a
standalone layer; this module is the model that USES it: a causal LM whose
every block replaces the dense FFN with the routed MoE FFN (Switch
Transformer, Fedus et al. 2021), trained over a ``data × expert`` mesh.

No reference equivalent (the guide predates MoE; SURVEY.md §2c lists EP as
a stretch goal). Structure mirrors :class:`~..parallel.pipeline.PipelinedLM`:
a strategy-owning class whose flax submodules (embedder, attention blocks,
head) carry replicated params while the expert stacks are raw arrays
sharded over the ``expert`` axis — tokens travel, parameters stay.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

import distributed_tensorflow_guide_tpu.collectives as cc
from distributed_tensorflow_guide_tpu.core.mesh import axis_sizes
from distributed_tensorflow_guide_tpu.models.transformer import (
    MultiHeadAttention,
    TransformerConfig,
)
from distributed_tensorflow_guide_tpu.parallel.expert import (
    MoEConfig,
    init_moe_params,
    moe_ffn,
)
from distributed_tensorflow_guide_tpu.utils.spec_utils import (
    assign_by_shape,
    expand_prefix,
)


class _AttnBlock(nn.Module):
    """Pre-LN attention half of a block: x + attn(LN(x)). The FFN half is
    the routed MoE layer, applied outside flax."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        return x + MultiHeadAttention(self.cfg, name="attn")(
            nn.LayerNorm(dtype=self.cfg.dtype, name="ln1")(x)
        )


class _Embedder(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                     name="tok_emb")(tokens)
        pos = nn.Embed(cfg.max_len, cfg.d_model, dtype=cfg.dtype,
                       name="pos_emb")(jnp.arange(tokens.shape[1])[None, :])
        return x + pos


class _Head(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        x = nn.LayerNorm(dtype=self.cfg.dtype, name="ln_f")(x)
        return nn.Dense(self.cfg.vocab_size, dtype=jnp.float32,
                        use_bias=False, name="lm_head")(x)


class SwitchLM:
    """Causal Switch-MoE LM over the ``data × expert`` mesh axes.

    Batch rows are sharded jointly over both axes (every device in the
    grid holds a distinct slice); expert stacks are sharded over
    ``expert``; everything else is replicated. Aux losses (load balance +
    router z) are added to the LM loss with ``aux_weight``.
    """

    def __init__(self, mesh: Mesh, cfg: TransformerConfig,
                 num_experts: int, *, top_k: int = 1,
                 capacity_factor: float = 2.0, router: str = "switch",
                 aux_weight: float = 1e-2,
                 fused_ce="auto", ce_chunk: int | None = None,
                 precision=None):
        if precision is not None:
            from distributed_tensorflow_guide_tpu.core import (
                precision as precision_mod,
            )

            cfg = precision_mod.resolve(precision).apply_to_transformer(cfg)
        sizes = axis_sizes(mesh)
        if num_experts % sizes["expert"]:
            raise ValueError(
                f"num_experts {num_experts} not divisible by expert axis "
                f"size {sizes['expert']}"
            )
        self.mesh = mesh
        self.cfg = cfg
        self.n_data = sizes["data"]
        self.n_expert = sizes["expert"]
        self.aux_weight = aux_weight
        self.moe_cfg = MoEConfig(
            d_model=cfg.d_model, d_ff=cfg.d_ff, num_experts=num_experts,
            top_k=top_k, capacity_factor=capacity_factor, router=router,
            dtype=cfg.dtype,
        )
        self.embedder = _Embedder(cfg)
        self.attn_block = _AttnBlock(cfg)
        self.ln2 = nn.LayerNorm(dtype=cfg.dtype)
        self.head = _Head(cfg)
        # chunked fused CE (ops/fused_ce.py): loss + grad-of-logits per
        # vocab chunk, no (B, S, V) logits live — same knob/resolution as
        # PipelinedLM; the raw LN applies ln_f with explicit params on the
        # fused path (the _Head module would materialize full logits)
        from distributed_tensorflow_guide_tpu.ops.fused_ce import (
            resolve_fused_ce,
        )

        self.fused_ce = resolve_fused_ce(fused_ce,
                                         vocab_size=cfg.vocab_size)
        self.ce_chunk = ce_chunk
        self._head_ln = nn.LayerNorm(dtype=cfg.dtype)

    # -- params ---------------------------------------------------------------
    def init_params(self, rng) -> dict:
        cfg = self.cfg
        r_emb, r_attn, r_ln, r_moe, r_head = jax.random.split(rng, 5)
        dummy_tok = jnp.zeros((1, cfg.max_len), jnp.int32)
        dummy_x = jnp.zeros((1, cfg.max_len, cfg.d_model), cfg.dtype)

        attn = jax.vmap(
            lambda k: self.attn_block.init(k, dummy_x)["params"]
        )(jax.random.split(r_attn, cfg.num_layers))
        ln2 = jax.vmap(
            lambda k: self.ln2.init(k, dummy_x)["params"]
        )(jax.random.split(r_ln, cfg.num_layers))
        moe = jax.vmap(
            lambda k: init_moe_params(self.moe_cfg, k)
        )(jax.random.split(r_moe, cfg.num_layers))
        params = {
            "embed": self.embedder.init(r_emb, dummy_tok)["params"],
            "attn": attn,
            "ln2": ln2,
            "moe": moe,
            "head": self.head.init(r_head, dummy_x)["params"],
        }
        return jax.device_put(params, self.param_shardings())

    def param_specs(self) -> dict:
        return {
            "embed": P(), "attn": P(), "ln2": P(),
            "moe": {
                "router": P(),
                # (L, E, d, ff): expert dim sharded over the expert axis
                "w_in": P(None, "expert"),
                "w_out": P(None, "expert"),
            },
            "head": P(),
        }

    def param_shardings(self):
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self.param_specs(),
            is_leaf=lambda x: isinstance(x, P),
        )

    # -- forward --------------------------------------------------------------
    def _forward(self, params, tokens, *, return_hidden: bool = False):
        """Per-device forward: tokens (B_local, S) -> (logits, aux) — or
        (pre-head hidden states, aux) with ``return_hidden`` (the fused-CE
        entry point, which must never see full-vocab logits)."""
        cfg = self.cfg
        x = self.embedder.apply({"params": params["embed"]}, tokens)
        b, s, d = x.shape

        def layer(h, lp):
            h = self.attn_block.apply({"params": lp["attn"]}, h)
            pre = self.ln2.apply({"params": lp["ln2"]}, h)
            y, aux = moe_ffn(lp["moe"], pre.reshape(b * s, d), self.moe_cfg)
            return h + y.reshape(b, s, d), aux

        x, auxs = lax.scan(
            layer, x, {"attn": params["attn"], "ln2": params["ln2"],
                       "moe": params["moe"]}
        )
        aux = jax.tree.map(jnp.mean, auxs)  # mean over layers
        if return_hidden:
            return x, aux
        logits = self.head.apply({"params": params["head"]}, x)
        return logits, aux

    def _local_loss(self, params, tokens):
        """Global-mean LM loss + aux, computed from this device's shard.

        Both paths produce the identical (sum-of-NLL, count) pair so the
        global mean stays the same psum/psum assembly; the fused path just
        never materializes the (B, S, V) logits it sums over.
        """
        n = jnp.array(tokens.shape[0] * (tokens.shape[1] - 1), jnp.float32)
        if self.fused_ce:
            from distributed_tensorflow_guide_tpu.ops.fused_ce import (
                fused_next_token_loss,
            )

            x, aux = self._forward(params, tokens, return_hidden=True)
            xh = self._head_ln.apply(
                {"params": params["head"]["ln_f"]}, x)
            se = fused_next_token_loss(
                xh, params["head"]["lm_head"]["kernel"], tokens,
                chunk=self.ce_chunk, reduction="sum")
        else:
            logits, aux = self._forward(params, tokens)
            logp = jax.nn.log_softmax(logits[:, :-1])
            ll = jnp.take_along_axis(
                logp, tokens[:, 1:][..., None], axis=-1
            )[..., 0]
            se = -jnp.sum(ll)
        axes = self.moe_cfg.token_axes
        lm = cc.psum(se, axes) / cc.psum(n, axes)
        loss = lm + self.aux_weight * (aux["load_balance"] + aux["z_loss"])
        return loss, {"lm_loss": lm, **aux}

    # -- compiled step --------------------------------------------------------
    def opt_state_specs(self, tx: optax.GradientTransformation, params):
        """Optimizer moments inherit their param's spec (matched by
        shape+dtype); scalars/counts replicate."""
        return assign_by_shape(
            params, expand_prefix(self.param_specs(), params),
            jax.eval_shape(tx.init, params), P(),
        )

    def make_train_step(self, tx: optax.GradientTransformation, params,
                        *, donate: bool = True):
        """``(opt_state, params, tokens (B, S)) -> (opt_state, params,
        metrics)``; B divisible by n_data * n_expert."""
        specs = self.param_specs()
        opt_specs = self.opt_state_specs(tx, params)
        axes = self.moe_cfg.token_axes

        def sm_step(opt_state, params, tokens):
            (loss, mets), grads = jax.value_and_grad(
                self._local_loss, has_aux=True
            )(params, tokens)
            # loss is the GLOBAL mean -> per-device grads are partial
            # contributions. Replicated leaves (embed/attn/ln/head/router):
            # psum over both token axes. Expert-sharded stacks: the expert
            # axis contributions already arrived through the backward
            # all_to_all, so psum over data only.
            grads = {
                "embed": cc.psum(grads["embed"], axes),
                "attn": cc.psum(grads["attn"], axes),
                "ln2": cc.psum(grads["ln2"], axes),
                "moe": {
                    "router": cc.psum(grads["moe"]["router"], axes),
                    "w_in": cc.psum(grads["moe"]["w_in"], "data"),
                    "w_out": cc.psum(grads["moe"]["w_out"], "data"),
                },
                "head": cc.psum(grads["head"], axes),
            }
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return opt_state, params, {"loss": loss, **mets}

        sharded = shard_map(
            sm_step,
            mesh=self.mesh,
            in_specs=(opt_specs, specs, P(self.moe_cfg.token_axes)),
            out_specs=(opt_specs, specs, P()),
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=(0, 1) if donate else ())

    def init_opt_state(self, tx, params):
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s),
            self.opt_state_specs(tx, params),
            is_leaf=lambda x: isinstance(x, P),
        )
        with self.mesh:
            return jax.jit(tx.init, out_shardings=shardings)(params)


# ---- program contracts (analysis/) ------------------------------------------


def lint_contracts():
    """Contract for the Switch train step over the data x expert mesh.
    The defining expectation is the all_to_all census: exactly 4 eqns on
    the expert axis — dispatch + return in the forward scan body, their
    transposes in the backward — and NOTHING else crossing expert as raw
    token traffic. The cost pin holds the byte side of the same promise:
    derived all_to_all traffic must equal the comm_bytes_model's
    4·L·B·(e−1)/e with B the fixed-capacity dispatch buffer."""
    from distributed_tensorflow_guide_tpu.analysis.contracts import (
        CostPin,
        CostSpec,
        DonationSpec,
        ProgramContract,
    )
    from distributed_tensorflow_guide_tpu.analysis.cost import closed_forms

    # 8-device fixture: data=2 x expert=4, E=4 experts, top_k=1.
    # t_local = (8 tokens / 8 devices) * max_len 8 = 8 rows per device;
    # capacity = ceil(1 * 8 * 2.0 / 4) = 4 -> dispatch buffer
    # (E=4, C=4, d=16) f32 = 1024 B per device (the return buffer
    # (e_local=1, E*C=16, d=16) is the same 1024 B by construction)
    n_expert, n_layers, top_k, cap_factor = 4, 2, 1, 2.0

    def _make_build(router):
        def _build():
            import jax
            import optax

            from distributed_tensorflow_guide_tpu.analysis.fixtures import (
                tiny_lm_cfg,
            )
            from distributed_tensorflow_guide_tpu.core.mesh import (
                MeshSpec,
                build_mesh,
            )

            cfg = tiny_lm_cfg()
            mesh = build_mesh(MeshSpec(data=2, expert=n_expert))
            lm = SwitchLM(mesh, cfg, num_experts=n_expert, top_k=top_k,
                          capacity_factor=cap_factor, router=router,
                          fused_ce=False)
            params = jax.eval_shape(lm.init_params, jax.random.PRNGKey(0))
            tx = optax.sgd(0.1)
            opt_state = jax.eval_shape(tx.init, params)
            step = lm.make_train_step(tx, params, donate=True)
            tokens = jax.ShapeDtypeStruct((8, 8), "int32")
            return step, (opt_state, params, tokens)

        return _build

    _build = _make_build("switch")

    def _a2a_expect(router="switch"):
        t_local, d_model = 8, 16
        if router == "dropless":
            capacity = t_local
        else:
            capacity = max(1,
                           -(-top_k * t_local * int(cap_factor) // n_expert))
        dispatch_bytes = n_expert * capacity * d_model * 4
        return closed_forms().moe_all_to_all_bytes(
            dispatch_bytes, n_expert, n_layers=n_layers)

    # Same census as the switch row (dropless changes the CAPACITY, not the
    # collective structure), but the byte pin doubles: C = t_local = 8 vs
    # the fixed-capacity 4 — the price of zero drops, stated exactly.
    _moe_census = {
        # dispatch + return per scan body, forward and backward
        "all_to_all[expert]": 4,
        # replicated-leaf grad psums (embed/attn/ln2/router/head
        # trees) + the loss/aux metric pmeans over both token axes
        "psum[data,expert]": 13,
        # the two expert-sharded stacks (w_in, w_out) reduce over
        # data ONLY — their expert contributions arrived through
        # the backward all_to_all; a psum[data,expert] here would
        # double-count across experts
        "psum[data]": 2,
    }

    return [
        ProgramContract(
            name="moe_train_step",
            build=_build,
            policy="f32",
            collectives=dict(_moe_census),
            donation=DonationSpec(argnums=(0, 1)),
            sources=(
                "distributed_tensorflow_guide_tpu.models.moe_lm",
                "distributed_tensorflow_guide_tpu.parallel.expert",
                "distributed_tensorflow_guide_tpu.collectives.collectives",
            ),
            cost=CostSpec(
                pins=(
                    CostPin("collective_bytes[all_to_all[expert]]",
                            _a2a_expect,
                            note="4·L·B·(e-1)/e expert-routing traffic "
                                 "at the fixed-capacity dispatch buffer"),
                ),
                max_peak_live_bytes=262144),
            notes="Switch-MoE step: tokens travel, expert params stay"),
        ProgramContract(
            name="moe_dropless_train_step",
            build=_make_build("dropless"),
            policy="f32",
            collectives=dict(_moe_census),
            donation=DonationSpec(argnums=(0, 1)),
            sources=(
                "distributed_tensorflow_guide_tpu.models.moe_lm",
                "distributed_tensorflow_guide_tpu.parallel.expert",
                "distributed_tensorflow_guide_tpu.collectives.collectives",
            ),
            cost=CostSpec(
                pins=(
                    CostPin("collective_bytes[all_to_all[expert]]",
                            lambda: _a2a_expect("dropless"),
                            note="same 4-crossing census, C widened to "
                                 "t_local — the exact byte price of "
                                 "dropless routing"),
                ),
                max_peak_live_bytes=262144),
            notes="dropless Switch step: capacity = t_local, zero drops"),
    ]
