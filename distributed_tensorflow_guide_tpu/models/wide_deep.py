"""Wide&Deep recommender — judged config 4: "Wide&Deep recommender, async PS
→ synchronous ICI allreduce" (BASELINE.json).

Reference context: recommender training is the canonical
ParameterServerStrategy workload
(tensorflow/python/distribute/parameter_server_strategy_v2.py:77) — huge
embedding tables live on PS shards, workers push sparse gradient rows
asynchronously. The TPU inversion: embedding tables are dense on-device
arrays (HBM is the parameter server), lookups are gathers that XLA fuses,
and gradient exchange is the same sync pmean as every other parameter —
see docs/async_ps_semantics.md for what that changes.

Model (Cheng et al. 2016): a *wide* linear path over categorical fields
(memorization) + a *deep* embeddings→MLP path (generalization), summed into
one logit, trained jointly.
"""

from __future__ import annotations

from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp


class WideDeep(nn.Module):
    vocab_sizes: Sequence[int]  # one per categorical field
    num_dense: int = 8
    embed_dim: int = 16
    mlp_dims: Sequence[int] = (128, 64)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, cat: jax.Array, dense: jax.Array) -> jax.Array:
        """cat: (B, n_fields) int32; dense: (B, num_dense) float. → (B,) logit."""
        # wide: per-field scalar weight per id — the linear one-hot path
        wide_logit = jnp.zeros(cat.shape[0], self.dtype)
        for i, vocab in enumerate(self.vocab_sizes):
            w = nn.Embed(vocab, 1, name=f"wide_{i}", dtype=self.dtype)(cat[:, i])
            wide_logit = wide_logit + w[:, 0]
        wide_logit = wide_logit + nn.Dense(1, name="wide_dense",
                                           dtype=self.dtype)(dense)[:, 0]

        # deep: embeddings + dense features → MLP
        embs = [
            nn.Embed(vocab, self.embed_dim, name=f"emb_{i}", dtype=self.dtype)(
                cat[:, i]
            )
            for i, vocab in enumerate(self.vocab_sizes)
        ]
        x = jnp.concatenate(embs + [dense.astype(self.dtype)], axis=-1)
        for j, d in enumerate(self.mlp_dims):
            x = nn.Dense(d, name=f"mlp_{j}", dtype=self.dtype)(x)
            x = nn.relu(x)
        deep_logit = nn.Dense(1, name="deep_out", dtype=jnp.float32)(x)[:, 0]
        return wide_logit.astype(jnp.float32) + deep_logit


def make_loss_fn(model: WideDeep):
    """``(params, batch) -> (loss, metrics)`` — binary cross-entropy (CTR)."""

    def loss_fn(params, batch):
        logit = model.apply({"params": params}, batch["cat"], batch["dense"])
        label = batch["label"].astype(jnp.float32)
        loss = jnp.mean(
            jnp.maximum(logit, 0) - logit * label + jnp.log1p(jnp.exp(-jnp.abs(logit)))
        )
        auc_proxy = jnp.mean((logit > 0) == (label > 0.5))
        return loss, {"accuracy": auc_proxy}

    return loss_fn
