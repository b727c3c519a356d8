"""ResNet — judged config 2: "ResNet-50 ImageNet MultiWorkerMirroredStrategy
(NCCL allreduce → lax.psum)" (BASELINE.json), the north-star throughput model.

Reference context: the guide's multi-GPU tower example (⚠
Multiple-GPUs-Single-Machine/) replicates a model per GPU and averages tower
gradients on the CPU — the hand-rolled MirroredStrategy
(tensorflow/python/distribute/mirrored_strategy.py:200). Here the replication
is SPMD over the data mesh axis and the average is one ICI psum
(parallel/data_parallel.py).

TPU-first choices:
  * NHWC layout, bf16 activations/f32 params (MXU-native mixed precision)
  * BatchNorm stats are *local* per step and cross-replica pmean-ed along
    with gradients (sync running stats — the MultiWorkerMirrored behavior)
  * stride-2 3x3 center conv in the bottleneck (the "v1.5" variant every
    modern benchmark uses)
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

ModuleDef = Any


class FusedBatchNormAct(nn.Module):
    """BatchNorm (+ optional ReLU) as ONE folded normalize-activate pass.

    The round-3 trace put the ResNet-50 backward at 88–96% of HBM
    bandwidth with BN+ReLU re-reading activations the convs just wrote —
    this module is the XLA-level restructure that attacks it:

    * **bf16 batch-stats reduction**: the mean / mean-of-squares reductions
      read the bf16 activations ONCE, with the f32 cast/square fused into
      the reduction (XLA keeps it elementwise-in-registers) — no separate
      upcast copy of the (N, H, W, C) tensor feeds the stats, and the
      squaring stays f32 so the E[x²]−E[x]² identity cannot go negative
      from bf16 rounding.
    * **single fused normalize-activate**: the affine fold
      ``k = scale·rsqrt(var+eps); b = bias − mean·k`` turns
      normalize+scale+shift(+ReLU) into one FMA + max over x — one read,
      one write, and a backward that re-derives everything from the same
      single expression instead of flax's separate subtract/multiply/add
      chain.

    Param/variable layout is IDENTICAL to ``nn.BatchNorm`` (params
    ``scale``/``bias``, batch_stats ``mean``/``var``, same init, same
    running-average update), so fused and plain models share checkpoints
    and the DataParallel cross-replica ``pmean`` of batch_stats is
    unchanged — pinned in tests/test_resnet.py.
    """

    use_running_average: bool = False
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16
    act: bool = False
    scale_init: Callable = nn.initializers.ones_init()

    @nn.compact
    def __call__(self, x):
        feat = x.shape[-1]
        f32 = jnp.float32
        scale = self.param("scale", self.scale_init, (feat,), f32)
        bias = self.param("bias", nn.initializers.zeros_init(), (feat,), f32)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda s: jnp.zeros(s, f32), (feat,))
        ra_var = self.variable("batch_stats", "var",
                               lambda s: jnp.ones(s, f32), (feat,))
        if self.use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            axes = tuple(range(x.ndim - 1))
            # reduce the activations AS STORED: the bf16 tensor is read
            # once and the f32 cast/square fuse INTO the reductions (no
            # materialized upcast copy — the traffic diet is the bf16
            # read). The square must happen in f32: squaring in bf16 puts
            # ~0.4% relative error on E[x²], enough to drive the
            # E[x²]−E[x]² identity negative for high-mean/low-variance
            # channels and NaN the rsqrt. The residual clamp guards the
            # same cancellation at f32 precision.
            x32 = x.astype(f32)
            mean = jnp.mean(x32, axes)
            mean2 = jnp.mean(jnp.square(x32), axes)
            var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
            if not self.is_initializing():
                ra_mean.value = (self.momentum * ra_mean.value
                                 + (1 - self.momentum) * mean)
                ra_var.value = (self.momentum * ra_var.value
                                + (1 - self.momentum) * var)
        k = (scale * lax.rsqrt(var + self.epsilon)).astype(self.dtype)
        b = (bias - mean * scale * lax.rsqrt(var + self.epsilon)).astype(
            self.dtype)
        y = x.astype(self.dtype) * k + b
        return nn.relu(y) if self.act else y


class BottleneckBlock(nn.Module):
    filters: int
    strides: tuple[int, int] = (1, 1)
    conv: ModuleDef = nn.Conv
    norm: ModuleDef = nn.BatchNorm
    act: Callable = nn.relu
    # norm is FusedBatchNormAct: norm+ReLU collapse into its single fused
    # pass wherever the pair occurs (the BN names are pinned to the
    # historical auto-names so both paths share one parameter layout)
    fused_bn: bool = False

    @nn.compact
    def __call__(self, x):
        def norm_act(y, name):
            if self.fused_bn:
                return self.norm(act=True, name=name)(y)
            return self.act(self.norm(name=name)(y))

        residual = x
        y = self.conv(self.filters, (1, 1), use_bias=False)(x)
        y = norm_act(y, "BatchNorm_0")
        y = self.conv(self.filters, (3, 3), self.strides, use_bias=False)(y)
        y = norm_act(y, "BatchNorm_1")
        y = self.conv(self.filters * 4, (1, 1), use_bias=False)(y)
        y = self.norm(scale_init=nn.initializers.zeros_init(),
                      name="BatchNorm_2")(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters * 4, (1, 1), self.strides, use_bias=False,
                name="conv_proj",
            )(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    small_inputs: bool = False  # skip the stride-4 stem for <=64px images
    # Rematerialize each bottleneck block in the backward pass: stores only
    # block-boundary activations, trading conv re-FLOPs (cheap — the step is
    # HBM-bound, docs/performance.md roofline) for resident HBM, to admit
    # larger per-chip batches without spilling. Numerically identical.
    remat: bool = False
    # Fused BN+ReLU path (FusedBatchNormAct): bf16 batch-stats reduction +
    # the normalize-activate pair folded into one FMA/max pass — the A/B
    # knob against the measured backward-conv/BN HBM re-reads (bench.py
    # --fused-bn). Parameter and batch_stats layout is unchanged.
    fused_bn: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = functools.partial(nn.Conv, dtype=self.dtype)
        norm_cls = FusedBatchNormAct if self.fused_bn else nn.BatchNorm
        norm = functools.partial(
            norm_cls,
            use_running_average=not train,
            momentum=0.9,
            epsilon=1e-5,
            dtype=self.dtype,
        )
        x = x.astype(self.dtype)
        if self.small_inputs:
            x = conv(self.num_filters, (3, 3), use_bias=False, name="conv_init")(x)
        else:
            x = conv(
                self.num_filters, (7, 7), (2, 2),
                padding=[(3, 3), (3, 3)], use_bias=False, name="conv_init",
            )(x)
        if self.fused_bn:
            x = norm(name="bn_init", act=True)(x)
        else:
            x = norm(name="bn_init")(x)
            x = nn.relu(x)
        if not self.small_inputs:
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        block_cls = nn.remat(BottleneckBlock) if self.remat else BottleneckBlock
        k = 0
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                # explicit name pins the param-tree path to the historical
                # auto-name, so remat=True/False share one parameter layout
                x = block_cls(
                    self.num_filters * 2**i,
                    strides=strides,
                    conv=conv,
                    norm=norm,
                    fused_bn=self.fused_bn,
                    name=f"BottleneckBlock_{k}",
                )(x)
                k += 1
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)
        return x


ResNet50 = functools.partial(ResNet, stage_sizes=(3, 4, 6, 3))
ResNet18ish = functools.partial(ResNet, stage_sizes=(1, 1, 1, 1))  # test-sized


def _ce_and_accuracy(logits, labels):
    """Softmax cross-entropy + top-1 accuracy — ONE definition shared by
    the train loss and the eval metrics, so they cannot diverge."""
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    acc = jnp.mean(jnp.argmax(logits, -1) == labels)
    return loss, acc


def make_loss_fn(model: ResNet, weight_decay: float = 0.0):
    """``(params, model_state, batch) -> (loss, (metrics, new_model_state))``
    for :meth:`DataParallel.make_train_step_with_stats`."""

    def loss_fn(params, model_state, batch):
        logits, new_model_state = model.apply(
            {"params": params, **model_state},
            batch["image"],
            train=True,
            mutable=["batch_stats"],
        )
        loss, acc = _ce_and_accuracy(logits, batch["label"])
        if weight_decay:
            loss = loss + 0.5 * weight_decay * sum(
                jnp.sum(p.astype(jnp.float32) ** 2)
                for p in jax.tree.leaves(params)
                if p.ndim > 1  # skip BN scales/biases
            )
        return loss, ({"accuracy": acc}, new_model_state)

    return loss_fn


def make_metric_fn(model: ResNet):
    """``(params, model_state, batch) -> metrics`` for
    :meth:`DataParallel.make_eval_step_with_stats`: BatchNorm inference
    mode (running stats, not batch stats), nothing written back."""

    def metric_fn(params, model_state, batch):
        logits = model.apply(
            {"params": params, **model_state}, batch["image"], train=False
        )
        loss, acc = _ce_and_accuracy(logits, batch["label"])
        return {"loss": loss, "accuracy": acc}

    return metric_fn
