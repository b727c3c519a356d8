"""Transformer family — shared backbone for judged configs 3 (BERT-base TP)
and 5 (GPT-2 124M PP), with Megatron-style tensor-parallel annotations.

No transformer exists in the reference (its largest model is a small CNN);
these configs come from BASELINE.json. The tensor-parallel design follows
the Megatron factorization (Shoeybi et al. 2019) expressed the JAX way:
parameters carry *logical* axis names via ``nn.with_logical_partitioning``,
``parallel/tensor.py`` maps logical names → mesh axes
(vocab/mlp/heads → "model"), and XLA inserts the collectives that Megatron
hand-writes as NCCL calls (the north-star mapping: NCCL allreduce →
``lax.psum``, here implicit through ``pjit`` shardings).

Logical axes: "batch", "seq", "embed" (d_model), "mlp" (d_ff),
"heads", "kv" (head_dim), "vocab".

TPU-first: bf16 activations / f32 params; d_ff and head counts MXU-friendly;
optional ``jax.checkpoint`` rematerialization per block (HBM ↔ FLOPs trade);
static shapes throughout (fixed seq_len — no dynamic padding).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_guide_tpu.collectives import (
    tp_allreduce,
    tp_identity,
)
from distributed_tensorflow_guide_tpu.utils.activation_sharding import (
    activation_mesh,  # noqa: F401 - re-export (strategy API lived here first)
    constrain as _constrain,
)

Dtype = Any

MIXERS = ("attention", "short_conv", "mamba2", "mamba1", "window_attention",
          "cross_attention", "gmu")
FFNS = ("dense", "routed")
#: the mixers whose sequences carry state beside their keys and values in
#: the block pool (a window layer's keys and values are such state: they
#: live in a ring of the slot's own, not in the pool)
STATE_MIXERS = ("short_conv", "mamba2", "mamba1", "window_attention")
#: the mixers ``HybridAttention`` runs
HYBRID_ATTENTION = ("window_attention", "cross_attention")
#: a window layer's two leaves of the ``state`` collection: its slots' rings
WINDOW_LEAVES = ("win_key", "win_value")
POSITIONS = ("table", "rotary", "none")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    d_ff: int = 3072
    max_len: int = 1024
    causal: bool = True
    dtype: Dtype = jnp.bfloat16
    remat: bool = False
    # Selective rematerialization (core/precision.py): None derives from the
    # legacy ``remat`` bool ("block" when True); "attention" checkpoints ONLY
    # the attention sub-layer per block — recompute the high-traffic part,
    # keep the MLP activations resident; "block" is the classic full-block
    # checkpoint (what remat=True always meant); "none" stores everything.
    remat_mode: str | None = None
    num_classes: int | None = None  # set → classification head (BERT/GLUE)
    # "dense"  — XLA softmax attention (materializes (S, S) scores). GSPMD
    #            partitions it under pjit, so it composes with TP sharding.
    # "flash"  — fused Pallas kernel (ops/flash_attention.py); falls back to
    #            the pure-XLA blockwise path on unsupported shapes. Works
    #            inside shard_map strategies (DP/PP/SP — per-device local
    #            arrays) AND under pjit/TP: the kernel carries a
    #            custom_partitioning rule that shards batch/heads (heads →
    #            the "model" axis) and replicates seq/head_dim.
    # "auto"   — flash for causal long-context (max_len >= 1024), else dense.
    #            Round-5 runs on the v5 lite chip had dense ahead below ~1k
    #            tokens (XLA's fused softmax beats the kernel-dispatch
    #            overhead); the crossover has not been measured since.
    attn_impl: str = "auto"
    # Manual-SPMD tensor parallelism (TP inside shard_map, e.g. TP-sharded
    # pipeline stages): set ``tp_axis`` to the mesh axis name and build the
    # module with LOCAL head/ff counts (num_heads / tp, d_ff / tp, plus
    # ``override_head_dim`` to keep head_dim at its global value). The
    # modules then bracket each sub-layer with Megatron's f/g conjugate
    # operators (collectives.tp_identity / tp_allreduce) so both values and
    # gradients are exact. Leave None under pjit/GSPMD (TensorParallel
    # strategy), where XLA inserts the collectives itself.
    tp_axis: str | None = None
    override_head_dim: int | None = None
    # Autoregressive serving mode (models/generation.py): attention keeps a
    # (B, H, max_len, hd) KV cache in the flax "cache" collection and the
    # caller passes the write ``index``; a call processes an arbitrary
    # chunk (the whole prompt at prefill, 1 token per decode step) with
    # static shapes throughout — the lax.scan decode loop compiles once.
    # False (default) leaves the training path byte-identical.
    decode: bool = False
    # KV-cache storage dtype (decode mode only). None keeps the cache at
    # ``dtype``; "int8" stores cached_key/cached_value as int8 with
    # per-slot-per-head f32 scales (key_scale/value_scale in the cache
    # collection) and folds dequantization into _decode_attend's QK^T and
    # AV contractions — halving the dominant cache-read term of the
    # bandwidth-bound decode step (ops/decode_attention.quantize_kv).
    kv_dtype: str | None = None
    # Decode-attention implementation (decode mode only):
    # "dense"  — XLA softmax attention over the full fixed-size cache
    #            (_dense_cache_read), also what "pallas" takes where the
    #            kernel does not fit.
    # "pallas" — length-aware streaming kernel (ops/decode_attention.py):
    #            reads only written cache blocks, consumes int8 + scales
    #            natively, blocks resolved from the autotune table.
    # "auto"   — pallas on TPU, dense elsewhere (the flash/ring TPU-only
    #            convention). The cache's layout does not depend on it.
    decode_impl: str = "auto"
    # Paged KV cache (serve/paged_cache.py, decode mode only): both set →
    # the cache collection holds a POOL of ``paged_num_blocks`` blocks of
    # ``paged_block_size`` slots shared across requests instead of a
    # per-request (B, max_len, ...) buffer, and every decode call takes a
    # (B, blocks_per_seq) ``block_tables`` operand plus a per-request
    # (B,) write ``index`` vector. The serve engine is the only caller;
    # the one-shot path (both None) is untouched.
    paged_num_blocks: int | None = None
    paged_block_size: int | None = None
    # Batched multi-LoRA (serve/engine.py, PR 12): ``lora_rank`` set → every
    # projection (attention qkv/proj, MLP up/down) owns a BANK of
    # ``lora_adapters + 1`` low-rank (A, B) delta pairs in the flax
    # "adapters" collection (row 0 is all-zero = the base model), and a call
    # may pass a per-request (B,) int32 ``adapter`` id vector: the deltas
    # are GATHERED by id and applied as one batched einsum per projection,
    # so one compiled step serves many fine-tunes (no per-adapter
    # programs). ``adapter=None`` (and lora_rank=None) keep every
    # historical trace byte-identical; adapter id 0 is bitwise the base
    # model at the token-stream level (a zero delta cannot move an argmax
    # or a gumbel comparison).
    lora_rank: int | None = None
    lora_adapters: int = 0
    # Weight-only quantized serving (decode mode only): "int8" / "int4"
    # store every projection kernel (attention qkv/proj, MLP up/down,
    # lm_head) quantized per-OUTPUT-channel with f32 scales — the param
    # tree carries {qkernel, scale} where the f32 model has {kernel}
    # (ops/quant.quantize_params is the tree transform) — and the dequant
    # is FUSED into each matmul (scale on the output columns, never a
    # materialized f32 kernel copy: the int8-KV discipline applied to the
    # weights). Cuts the params term of decode_hbm_bytes_per_step ~4x
    # (int8) / ~8x (int4-packed, two nibbles per byte). Embeddings and
    # LayerNorms stay full precision (gathered, never streamed). None
    # (default) keeps every historical trace byte-identical.
    weight_dtype: str | None = None
    # AQT-style int8 TRAINING matmuls (core/precision.py PRESETS["int8"]):
    # the projection contractions run int8 x int8 -> int32 with per-tensor
    # dynamic scales and straight-through gradients (ops/quant.
    # int8_ste_dot); params stay f32 masters with the IDENTICAL tree and
    # init draws as the unquantized model (loss-parity pins). lm_head and
    # the classifier keep full-precision accumulation. Training-side only
    # — decode uses ``weight_dtype``.
    quantized_matmuls: bool = False
    # fp8 TRAINING matmuls (core/precision.py PRESETS["fp8"], round 21):
    # the projection contractions cast both operands to e4m3 with
    # per-tensor dynamic scales and accumulate in f32, backward straight-
    # through (ops/quant.fp8_ste_dot) — the same tree-transparent
    # QuantTrainDense shape as quantized_matmuls, so loss-parity pins
    # transfer. Gate with core.precision.require_fp8(): pre-fp8 TPU
    # generations emulate e4m3 at a net loss.
    fp8_matmuls: bool = False
    # Routed MoE FFN (Switch-style top-1) for the flat Transformer — the
    # serve-side sibling of models/moe_lm.py's SwitchLM. ``moe_experts``
    # set → every Block's FFN becomes MoEMLP: a per-token f32 router picks
    # one expert from a bank of (E, d, ff)/(E, ff, d) kernels and the
    # token travels through a fixed-capacity dispatch buffer (static
    # shapes, one-hot algebra — the parallel/expert.py discipline on a
    # single device). ``moe_capacity`` bounds the per-expert buffer for
    # SINGLE-TOKEN (decode) calls: a token past capacity is NOT dropped —
    # its FFN output is zeroed and its per-token overflow flag is sown
    # into the "moe_stats" collection so the serve engine can stall the
    # slot and retry (degrade-to-overflow semantics; serve/engine.py).
    # Multi-token calls (prefill chunks, one-shot, training) widen the
    # buffer to the token count, which provably admits every token.
    # ``moe_capacity=None`` is the always-dropless oracle. None/None
    # (default) keeps every historical trace byte-identical.
    moe_experts: int | None = None
    moe_capacity: int | None = None
    # The model as a PATTERN of layers (PR 28). ``layers`` set → layer ``i``
    # is ``(mixer, ffn)``: a mixer kind (one of ``MIXERS``: ``"attention"``
    # | ``"short_conv"`` | ``"mamba2"`` | ``"mamba1"`` |
    # ``"window_attention"`` | ``"cross_attention"``, which reads the cache
    # of the last ``"attention"`` layer before it | ``"gmu"``, which gates
    # by the scan output of the last ``"mamba1"`` layer before it) over a
    # feed-forward kind (``"dense"`` | ``"routed"``),
    # either of which may be None: the layer is then the other half alone,
    # ``x + f(norm(x))`` (not both). The sizes below are the model's own;
    # ``num_layers`` must equal its length. None (default) is GPT-2's block
    # everywhere and keeps every historical trace byte-identical: the sizes
    # below are then refused, not ignored.
    layers: tuple | None = None
    # "layernorm" | "rmsnorm"; ``norm_eps`` None is flax's default (1e-6)
    norm: str = "layernorm"
    norm_eps: float | None = None
    # the feed-forward's form, a routed layer's experts and shared expert
    # too. None: down(gelu(up)) with the historical bias; "silu": the gated
    # form down(silu(gate) * up); "relu2": down(relu(up)^2); the last two
    # without biases
    ffn_gate: str | None = None
    # What tells the model a token's position, said once (``position_kind``
    # is what the code reads): "table", a learned table of ``max_len``
    # positions added to the embedding; "rotary", a rotation inside
    # attention over the whole head at ``rope_theta``; "none", nothing (a
    # model whose state-space mixers order the sequence). None is what
    # configurations written before the field mean: rotary where
    # ``rope_theta`` is given, else the table. Without a table ``max_len``
    # is only the cache's length.
    positions: str | None = None
    rope_theta: float | None = None
    # fewer key/value heads than query heads (None: one each); the paged
    # pool's leaves hold this many heads
    num_kv_heads: int | None = None
    # RMSNorm over the head size on every query and key head, before the
    # rotation
    qk_norm: bool = False
    # short_conv, mamba2 and mamba1 mixers: taps of the depthwise causal
    # convolution; a sequence carries ``conv_kernel - 1`` positions of state
    conv_kernel: int = 3
    # mamba2 mixer (ops/ssm_scan.py): ``ssm_heads`` heads of ``ssm_head_dim``
    # (their product is the mixer's inner width, whatever ``d_model`` is),
    # ``ssm_groups`` groups sharing B and C of ``ssm_state`` numbers each; a
    # sequence carries a float32 (heads, head_dim, state) matrix beside the
    # convolution's positions. Training-view runs scan chunks of
    # ``ssm_chunk`` positions.
    ssm_heads: int | None = None
    ssm_head_dim: int | None = None
    ssm_groups: int = 1
    ssm_state: int | None = None
    ssm_chunk: int = 128
    # routed feed-forward (ops/routed_ffn.py): ``routed_experts`` in all,
    # ``routed_top_k`` a token, each of ``ffn_gate``'s form at
    # ``routed_d_ff``, the chosen scores normalised (over their sum plus
    # ``routed_norm_eps``) and multiplied by ``routed_scale``; this program
    # holds experts ``[routed_first, routed_first + routed_count)`` (None:
    # all) and assignments to the others add nothing. ``shared_d_ff``: one
    # more expert of that width that every token goes through (None: none),
    # held whole by every program that shares the layer.
    # ``routed_d_ff_stored`` (None: ``routed_d_ff``) is the width the banks
    # are STORED at, the caller's tree holding zeros past ``routed_d_ff``
    # (``relu(0)^2`` and ``silu(0) * 0`` are 0, so the layer is the same):
    # a TPU keeps an array whose last axis is not whole 128-lane groups
    # transposed, and the grouped product, which takes its banks
    # row-major, would copy the up bank back in every launch.
    routed_experts: int | None = None
    routed_top_k: int = 1
    routed_d_ff: int | None = None
    routed_first: int = 0
    routed_count: int | None = None
    routed_scale: float = 1.0
    routed_norm_eps: float = 1e-6
    shared_d_ff: int | None = None
    routed_d_ff_stored: int | None = None
    # mamba1 mixer (ops/ssm_scan.py, the end of the file): ``ssm_inner``
    # channels each with ``ssm_state`` numbers of state and a decay of its
    # own for each, the step size a channel through a projection of rank
    # ``ssm_dt_rank``; ``conv_kernel`` taps. A sequence carries a float32
    # (ssm_inner, ssm_state) matrix beside the convolution's positions. A
    # ``gmu`` mixer (no state, no cache) gates by the scan output of the
    # last mamba1 layer before it, in the same forward pass.
    ssm_inner: int | None = None
    ssm_dt_rank: int | None = None
    # ``window_attention``: a query sees its own position and the ``window
    # - 1`` before it. Serving keeps a slot's last ``window_ring``
    # positions of keys and values in blocks of the slot's own (the
    # ``state`` collection), whatever the sequence's length; the engine
    # sets it (``serve/engine.py paged_config``): whole blocks and whole
    # prefill chunks, at least ``window - 1`` positions and a chunk.
    window: int | None = None
    window_ring: int | None = None
    # differential attention (Ye et al., 2024): query heads in pairs ``(2p,
    # 2p + 1)``, key heads likewise, the pair's value both value heads side
    # by side; ``(softmax(q1 k1) - lambda softmax(q2 k2)) V``, a learned
    # lambda a layer, an RMSNorm over the pair's output. The cache holds a
    # pair as ONE head twice as wide, each key and value once.
    differential: bool = False
    # biases on attention's two projections
    attn_bias: bool = False
    # the head is the embedding, transposed: no ``lm_head`` leaf
    tie_embeddings: bool = False

    def __post_init__(self):
        self._check_pattern()
        if self.attn_impl not in ("auto", "dense", "flash"):
            raise ValueError(
                "attn_impl must be 'auto', 'dense' or 'flash', "
                f"got {self.attn_impl!r}"
            )
        if self.decode_impl not in ("auto", "dense", "pallas"):
            raise ValueError(
                "decode_impl must be 'auto', 'dense' or 'pallas', "
                f"got {self.decode_impl!r}"
            )
        if self.kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None or 'int8', got {self.kv_dtype!r}"
            )
        if self.remat_mode not in (None, "none", "attention", "block"):
            raise ValueError(
                "remat_mode must be None, 'none', 'attention' or 'block', "
                f"got {self.remat_mode!r}"
            )
        if (self.paged_num_blocks is None) != (self.paged_block_size is None):
            raise ValueError(
                "paged_num_blocks and paged_block_size must be set together"
            )
        if self.paged_block_size is not None:
            bad = (self.paged_block_size < 1
                   or self.max_len % self.paged_block_size)
            if bad:
                raise ValueError(
                    f"paged_block_size {self.paged_block_size} must divide "
                    f"max_len {self.max_len}"
                )
            if self.paged_num_blocks < 2:
                raise ValueError(
                    "paged_num_blocks must be >= 2 (one is the trash block)"
                )
        if self.lora_rank is not None:
            if self.lora_rank < 1:
                raise ValueError(
                    f"lora_rank must be >= 1, got {self.lora_rank}")
            if self.lora_adapters < 1:
                raise ValueError(
                    "lora_rank set requires lora_adapters >= 1 "
                    f"(got {self.lora_adapters})")
        elif self.lora_adapters:
            raise ValueError("lora_adapters requires lora_rank")
        if self.weight_dtype not in (None, "int8", "int4", "fp8"):
            raise ValueError(
                "weight_dtype must be None, 'int8', 'int4' or 'fp8', "
                f"got {self.weight_dtype!r}"
            )
        if self.weight_dtype is not None:
            # NOT decode-gated: the serving flow attaches weight_dtype to
            # the training-view config and decode_config() flips decode
            # later; the training-side exclusion is quantized_matmuls.
            if self.quantized_matmuls:
                raise ValueError(
                    "weight_dtype (decode-side) and quantized_matmuls "
                    "(training-side) are mutually exclusive"
                )
            if self.fp8_matmuls:
                raise ValueError(
                    "weight_dtype (decode-side) and fp8_matmuls "
                    "(training-side) are mutually exclusive"
                )
            if self.lora_rank is not None:
                raise ValueError(
                    "weight_dtype and lora_rank are mutually exclusive "
                    "(the quantized projections have no f32 kernel for "
                    "the deltas to ride on)"
                )
        if self.moe_capacity is not None and self.moe_experts is None:
            raise ValueError("moe_capacity requires moe_experts")
        if self.moe_experts is not None:
            if self.moe_experts < 2:
                raise ValueError(
                    f"moe_experts must be >= 2, got {self.moe_experts}")
            if self.moe_capacity is not None and self.moe_capacity < 1:
                raise ValueError(
                    f"moe_capacity must be >= 1, got {self.moe_capacity}")
            if self.lora_rank is not None:
                raise ValueError(
                    "moe_experts and lora_rank are mutually exclusive "
                    "(no delta bank wiring on the routed FFN)")
            if self.quantized_matmuls or self.fp8_matmuls:
                raise ValueError(
                    "moe_experts and the training quant levers are "
                    "mutually exclusive (SwitchLM owns MoE training)")
            if self.tp_axis:
                raise ValueError(
                    "moe_experts and tp_axis are mutually exclusive "
                    "(expert parallelism is the MoE sharding story)")
        if self.quantized_matmuls or self.fp8_matmuls:
            lever = ("quantized_matmuls" if self.quantized_matmuls
                     else "fp8_matmuls")
            if self.quantized_matmuls and self.fp8_matmuls:
                raise ValueError(
                    "quantized_matmuls and fp8_matmuls are mutually "
                    "exclusive — one quantized representation per model"
                )
            if self.decode:
                raise ValueError(
                    f"{lever} is the training lever; decode-side "
                    "quantization is weight_dtype"
                )
            if self.lora_rank is not None:
                raise ValueError(
                    f"{lever} and lora_rank are mutually exclusive"
                )

    def _check_pattern(self) -> None:
        sizes = dict(norm=self.norm != "layernorm",
                     norm_eps=self.norm_eps is not None,
                     ffn_gate=self.ffn_gate is not None,
                     positions=self.positions is not None,
                     rope_theta=self.rope_theta is not None,
                     ssm_heads=self.ssm_heads is not None,
                     shared_d_ff=self.shared_d_ff is not None,
                     routed_d_ff_stored=self.routed_d_ff_stored is not None,
                     num_kv_heads=self.num_kv_heads is not None,
                     qk_norm=self.qk_norm,
                     routed_experts=self.routed_experts is not None,
                     ssm_inner=self.ssm_inner is not None,
                     window=self.window is not None,
                     differential=self.differential,
                     attn_bias=self.attn_bias,
                     tie_embeddings=self.tie_embeddings)
        if self.layers is None:
            given = sorted(k for k, v in sizes.items() if v)
            if given:
                raise ValueError(
                    f"{given} are sizes of a patterned model: give "
                    "``layers`` too (None keeps GPT-2's block as it was)")
            return
        if len(self.layers) != self.num_layers:
            raise ValueError(
                f"layers has {len(self.layers)} entries, num_layers is "
                f"{self.num_layers}")
        for kinds in self.layers:
            if (len(kinds) != 2 or kinds[0] not in MIXERS + (None,)
                    or kinds[1] not in FFNS + (None,)
                    or kinds == (None, None)):
                raise ValueError(
                    f"a layer is (mixer in {MIXERS}, ffn in {FFNS}), either "
                    f"of them None but not both, got {kinds!r}")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"norm {self.norm!r}: layernorm or rmsnorm")
        if self.ffn_gate not in (None, "silu", "relu2"):
            raise ValueError(
                f"ffn_gate {self.ffn_gate!r}: None, 'silu' or 'relu2'")
        kv = self.kv_heads
        if kv < 1 or self.num_heads % kv:
            raise ValueError(
                f"num_kv_heads {kv} must divide num_heads {self.num_heads}")
        if self.positions not in (None,) + POSITIONS:
            raise ValueError(
                f"positions {self.positions!r}: one of {POSITIONS}")
        if (self.position_kind == "rotary") != (self.rope_theta is not None):
            raise ValueError(
                f"positions {self.positions!r} with rope_theta "
                f"{self.rope_theta!r}: rotary positions, and they alone, "
                "take a rope_theta")
        if self.position_kind == "rotary" and self.head_dim % 2:
            raise ValueError("rotary positions need an even head size")
        if self.conv_kernel < 2:
            raise ValueError("conv_kernel must be >= 2")
        if any(m == "mamba2" for m, _ in self.layers):
            needed = (self.ssm_heads, self.ssm_head_dim, self.ssm_state)
            if any(v is None or v < 1 for v in needed):
                raise ValueError("a mamba2 mixer needs ssm_heads, "
                                 "ssm_head_dim and ssm_state")
            g = self.ssm_groups
            if g < 1 or self.ssm_heads % g:
                raise ValueError(
                    f"ssm_groups {g} must divide ssm_heads {self.ssm_heads}")
            if self.ssm_chunk < 1:
                raise ValueError("ssm_chunk must be >= 1")
        if any(f == "routed" for _, f in self.layers):
            e, k = self.routed_experts, self.routed_top_k
            if e is None or self.routed_d_ff is None:
                raise ValueError(
                    "a routed layer needs routed_experts and routed_d_ff")
            if not 1 <= k <= e:
                raise ValueError(f"routed_top_k {k} not in [1, {e}]")
            first, count = self.routed_first, self.routed_held
            if first < 0 or count < 1 or first + count > e:
                raise ValueError(
                    f"held experts [{first}, {first + count}) lie outside "
                    f"[0, {e})")
            if self.ffn_gate is None:
                raise ValueError("the routed experts have no biases: "
                                 "ffn_gate must be 'silu' or 'relu2'")
            stored = self.routed_d_ff_stored
            if stored is not None and stored < self.routed_d_ff:
                raise ValueError(
                    f"routed_d_ff_stored {stored} is under routed_d_ff "
                    f"{self.routed_d_ff}")
        elif self.shared_d_ff is not None:
            raise ValueError("shared_d_ff is a routed layer's shared "
                             "expert: no layer here is routed")
        mixers = [m for m, _ in self.layers]
        if "mamba1" in mixers:
            needed = (self.ssm_inner, self.ssm_state, self.ssm_dt_rank)
            if any(v is None or v < 1 for v in needed):
                raise ValueError("a mamba1 mixer needs ssm_inner, ssm_state "
                                 "and ssm_dt_rank")
        for kind, source in (("gmu", "mamba1"),
                             ("cross_attention", "attention")):
            if kind in mixers and source not in mixers[:mixers.index(kind)]:
                raise ValueError(
                    f"a {kind} layer reads the last {source} layer before "
                    "it: there is none")
        if ("window_attention" in mixers) != (self.window is not None):
            raise ValueError("window_attention layers, and they alone, take "
                             "a window")
        if self.window is not None and self.window < 1:
            raise ValueError("window must be >= 1")
        if self.window_ring is not None:
            bs = self.paged_block_size
            if (self.window is None or bs is None or self.window_ring % bs
                    or self.window_ring < self.window):
                raise ValueError(
                    f"window_ring {self.window_ring}: whole blocks of the "
                    "paged cache, at least the window")
        if (any(m in HYBRID_ATTENTION for m in mixers)
                and (self.position_kind == "rotary" or self.qk_norm)):
            raise ValueError("window and cross attention have no wiring for "
                             "rotary positions or qk_norm")
        if self.differential and (self.num_heads % 2 or self.kv_heads % 2):
            raise ValueError("differential attention pairs heads: "
                             "num_heads and num_kv_heads must be even")
        # what the patterned path has no wiring for is refused by name
        unwired = dict(lora_rank=self.lora_rank, weight_dtype=self.weight_dtype,
                       moe_experts=self.moe_experts, tp_axis=self.tp_axis,
                       kv_dtype=self.kv_dtype,
                       quantized_matmuls=self.quantized_matmuls or None,
                       fp8_matmuls=self.fp8_matmuls or None,
                       num_classes=self.num_classes)
        bad = sorted(k for k, v in unwired.items() if v is not None)
        if bad:
            raise ValueError(
                f"a patterned model (layers=...) has no wiring for {bad}")

    @property
    def kv_heads(self) -> int:
        return (self.num_heads if self.num_kv_heads is None
                else self.num_kv_heads)

    @property
    def routed_held(self) -> int:
        """How many experts this program holds."""
        return (self.routed_experts - self.routed_first
                if self.routed_count is None else self.routed_count)

    @property
    def position_kind(self) -> str:
        """``positions`` with its default filled in: one of ``POSITIONS``."""
        if self.positions is not None:
            return self.positions
        return "table" if self.rope_theta is None else "rotary"

    @property
    def state_mixers(self) -> tuple:
        """The kinds of mixer in the pattern whose sequences carry state
        beside their keys and values (a short_conv mixer's last positions,
        a mamba2 mixer's those and its state matrix), in ``STATE_MIXERS``'
        order."""
        return tuple(k for k in STATE_MIXERS if any(
            m == k for m, _ in self.layers or ()))

    @property
    def stateful(self) -> bool:
        """Whether a sequence carries such state: what moves blocks of keys
        and values alone cannot move such a sequence."""
        return bool(self.state_mixers)

    @property
    def paged(self) -> bool:
        return self.paged_num_blocks is not None

    @property
    def moe(self) -> bool:
        return self.moe_experts is not None

    @property
    def lora(self) -> bool:
        return self.lora_rank is not None

    @property
    def resolved_remat_mode(self) -> str:
        """The effective remat mode: explicit ``remat_mode`` wins, else the
        legacy bool maps True -> "block"."""
        if self.remat_mode is not None:
            return self.remat_mode
        return "block" if self.remat else "none"

    def resolve_decode_impl(self) -> str:
        """Resolve the decode-attention impl: 'auto' is pallas on TPU and
        dense everywhere else (same backend-resolution convention as the
        ring kernel and the KV-cache donation gate)."""
        if self.decode_impl != "auto":
            return self.decode_impl
        import jax

        return "pallas" if jax.default_backend() == "tpu" else "dense"

    def resolve_attn_impl(self, seq_len: int | None = None) -> str:
        """Resolve 'auto' against the actual (trace-time) sequence length;
        falls back to ``max_len`` when none is given (the config-level upper
        bound, used by e.g. the TensorParallel flash guard)."""
        if self.attn_impl != "auto":
            return self.attn_impl
        s = self.max_len if seq_len is None else seq_len
        return "flash" if (self.causal and s >= 1024) else "dense"

    @property
    def resolved_attn_impl(self) -> str:
        return self.resolve_attn_impl()

    @property
    def head_dim(self) -> int:
        if self.override_head_dim is not None:
            return self.override_head_dim
        assert self.d_model % self.num_heads == 0
        return self.d_model // self.num_heads

    def tp_local(self, tp: int, axis: str = "model") -> "TransformerConfig":
        """The per-shard view of this config under ``tp``-way manual tensor
        parallelism: local head/ff counts, global head_dim pinned, f/g
        operators enabled on ``axis``."""
        if self.num_heads % tp or self.d_ff % tp:
            raise ValueError(
                f"num_heads={self.num_heads} and d_ff={self.d_ff} must both "
                f"divide by tp={tp}"
            )
        return dataclasses.replace(
            self,
            num_heads=self.num_heads // tp,
            d_ff=self.d_ff // tp,
            override_head_dim=self.head_dim,
            tp_axis=axis,
        )


def gpt2_124m(**kw) -> TransformerConfig:
    """GPT-2 small (124M): 12L, 768d, 12h, causal. Vocab 50257 padded to
    50304 (multiple of 128) so the vocab dim shards evenly over any model
    axis and tiles the MXU — the standard Megatron-style padding."""
    return TransformerConfig(
        vocab_size=50304, num_layers=12, num_heads=12, d_model=768,
        d_ff=3072, max_len=1024, causal=True, **kw,
    )


def bert_base(num_classes: int = 2, **kw) -> TransformerConfig:
    """BERT-base (110M): 12L, 768d, 12h, bidirectional. Vocab 30522 padded
    to 30592 (multiple of 128) for even vocab sharding / MXU tiling."""
    return TransformerConfig(
        vocab_size=30592, num_layers=12, num_heads=12, d_model=768,
        d_ff=3072, max_len=512, causal=False, num_classes=num_classes, **kw,
    )


def _dense_init(*names):
    return nn.with_logical_partitioning(
        nn.initializers.normal(stddev=0.02), names
    )


# Binding activation constraints: see utils/activation_sharding.py — the
# strategy (parallel/tensor.py) enters ``activation_mesh`` at trace time
# and these modules' ``_constrain`` sites lower to real
# with_sharding_constraint ops; outside that context they stay advisory
# (shard_map paths must not emit wsc).


def _lora_bank(module: nn.Module, cfg: TransformerConfig, name: str,
               d_in: int, d_out: int):
    """The (A, B) delta bank of one projection: ``lora_adapters + 1`` rows
    (row 0 all-zero = the base model), created at init whenever
    ``cfg.lora_rank`` is set so the "adapters" collection has known shapes
    regardless of whether a call passes adapter ids."""
    n_bank = cfg.lora_adapters + 1
    a = module.variable("adapters", f"{name}_A", jnp.zeros,
                        (n_bank, d_in, cfg.lora_rank), cfg.dtype)
    b = module.variable("adapters", f"{name}_B", jnp.zeros,
                        (n_bank, cfg.lora_rank, d_out), cfg.dtype)
    return a, b


def _lora_delta(a, b, x: jax.Array, adapter: jax.Array) -> jax.Array:
    """x @ A[id] @ B[id] with per-request ids — ONE gathered batched
    einsum pair serves every adapter resident in the batch."""
    a_e = jnp.take(a.value, adapter, axis=0)  # (B, d_in, r)
    b_e = jnp.take(b.value, adapter, axis=0)  # (B, r, d_out)
    t = jnp.einsum("bcd,bdr->bcr", x, a_e)
    return jnp.einsum("bcr,bre->bce", t, b_e)


_WQ_BITS = {"int8": 8, "int4": 4, "fp8": "fp8"}


def _prod(dims) -> int:
    out = 1
    for d in dims:
        out *= int(d)
    return out


class WeightQuantDense(nn.Module):
    """Weight-only quantized projection (``cfg.weight_dtype``, decode).

    Declares the serving-side param layout directly — ``qkernel`` (int8,
    int4 packed two-per-byte into uint8, or fp8-e4m3) plus
    per-output-column f32
    ``scale`` — exactly what ``ops.quant.quantize_params`` produces from
    the f32 sibling's ``kernel``, under the SAME module name, so the
    quantized tree drops straight into ``model.apply``. The dequant is
    fused into the matmul (``ops.quant.wq_matmul``): the int cast rides
    the contraction and the scale lands on the output columns, so no
    dequantized kernel copy is ever materialized (pinned by the jaxpr
    walk in tests/test_quant.py). Init values (zeros/ones) are
    placeholders — real weights always arrive via ``quantize_params``.
    """

    features: tuple
    in_axes: int = 1
    bits: Any = 8  # 8 | 4 | "fp8"
    dtype: Dtype = jnp.float32
    use_bias: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        feats = tuple(self.features)
        d_in = _prod(x.shape[-self.in_axes:])
        out_flat = _prod(feats)
        if self.bits == 4:
            if d_in % 2:
                raise ValueError(
                    f"int4 packing needs an even fan-in, got {d_in}")
            rows, store = d_in // 2, jnp.uint8
        elif self.bits == "fp8":
            rows, store = d_in, jnp.float8_e4m3fn
        else:
            rows, store = d_in, jnp.int8
        qkernel = self.param("qkernel", nn.initializers.zeros_init(),
                             (rows, out_flat), store)
        scale = self.param("scale", nn.initializers.ones_init(),
                           (out_flat,), jnp.float32)
        from distributed_tensorflow_guide_tpu.ops import quant

        xf = x.reshape(x.shape[:-self.in_axes] + (d_in,)).astype(self.dtype)
        y = quant.wq_matmul(xf, qkernel, scale, bits=self.bits,
                            dtype=self.dtype)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros_init(),
                              (out_flat,), jnp.float32)
            y = y + bias.astype(self.dtype)
        return y.reshape(x.shape[:-self.in_axes] + feats)


class QuantTrainDense(nn.Module):
    """AQT-style quantized training projection (``cfg.quantized_matmuls``
    for int8, ``cfg.fp8_matmuls`` for e4m3 via ``mode="fp8"``).

    Param-tree transparent: declares the SAME ``kernel`` (and optional
    ``bias``) — names, shapes, f32 param dtype, initializers — as the
    ``nn.Dense``/``nn.DenseGeneral`` it replaces, and flax derives init
    RNG from the param path, so the init draws are bit-identical to the
    unquantized model (the basis of the loss-parity pins). Only the
    contraction changes: ``ops.quant.int8_ste_dot`` (or ``fp8_ste_dot``)
    quantizes both operands per-tensor dynamically each step, accumulates
    int8 x int8 in int32 (e4m3 x e4m3 in f32 for fp8), rescales in f32,
    and backpropagates straight-through.
    """

    features: tuple
    in_axes: int = 1
    dtype: Dtype = jnp.float32
    kernel_init: Any = None
    use_bias: bool = False
    bias_init: Any = None
    mode: str = "int8"  # "int8" | "fp8"

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        feats = tuple(self.features)
        in_shape = tuple(x.shape[-self.in_axes:])
        d_in = _prod(in_shape)
        kernel = self.param("kernel", self.kernel_init, in_shape + feats,
                            jnp.float32)
        from distributed_tensorflow_guide_tpu.ops import quant

        dot = quant.fp8_ste_dot if self.mode == "fp8" else quant.int8_ste_dot
        xf = x.reshape(x.shape[:-self.in_axes] + (d_in,)).astype(self.dtype)
        k2d = kernel.astype(self.dtype).reshape(d_in, -1)
        y = dot(xf, k2d).astype(self.dtype)
        if self.use_bias:
            bias = self.param("bias", self.bias_init, feats, jnp.float32)
            y = y + bias.reshape(-1).astype(self.dtype)
        return y.reshape(x.shape[:-self.in_axes] + feats)


def _norm(cfg: TransformerConfig, name: str):
    """The model's normalisation: GPT-2's ``nn.LayerNorm`` at flax's
    default epsilon unless the config names another kind or size."""
    if cfg.norm == "layernorm" and cfg.norm_eps is None:
        return nn.LayerNorm(dtype=cfg.dtype, name=name)
    eps = 1e-6 if cfg.norm_eps is None else cfg.norm_eps
    cls = nn.RMSNorm if cfg.norm == "rmsnorm" else nn.LayerNorm
    return cls(epsilon=eps, dtype=cfg.dtype, name=name)


def rotate(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary positions over the whole head: ``x`` (B, S, H, hd) at
    ``positions`` (B, S) or (1, S). The halves ``[x1, x2]`` turn as ``x *
    cos + [-x2, x1] * sin`` with frequencies ``theta ** (-2i / hd)``, angles
    in float32 (a bfloat16 angle loses the position past a few hundred)."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (B, S, hd/2)
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    turned = jnp.concatenate([-x2, x1], axis=-1)
    return (x.astype(jnp.float32) * cos + turned * sin).astype(x.dtype)


class MultiHeadAttention(nn.Module):
    cfg: TransformerConfig
    layer: int = 0  # a patterned model's: differential attention's lambda

    def _grouped_qkv(self, x, index):
        """The patterned model's projection: ``h`` query heads and
        ``kv_heads`` key and value heads out of one kernel (with a bias
        where ``attn_bias``), each query and key head normalised
        (``qk_norm``) and then turned by its position (``rope_theta``)."""
        cfg = self.cfg
        h, kv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
        qkv = nn.DenseGeneral(
            (h + 2 * kv, hd), axis=-1, dtype=cfg.dtype,
            kernel_init=_dense_init("embed", "heads", "kv"),
            use_bias=cfg.attn_bias, name="qkv")(x)
        q, k, v = qkv[:, :, :h], qkv[:, :, h:h + kv], qkv[:, :, h + kv:]
        if cfg.qk_norm:
            eps = 1e-6 if cfg.norm_eps is None else cfg.norm_eps
            q = nn.RMSNorm(epsilon=eps, dtype=cfg.dtype, name="q_norm")(q)
            k = nn.RMSNorm(epsilon=eps, dtype=cfg.dtype, name="k_norm")(k)
        if cfg.position_kind == "rotary":
            positions = jnp.arange(x.shape[1])[None, :]
            if cfg.decode:
                positions = positions + jnp.reshape(index, (-1, 1))
            q = rotate(q, positions, cfg.rope_theta)
            k = rotate(k, positions, cfg.rope_theta)
        return q, k, v

    @nn.compact
    def __call__(self, x: jax.Array, index=None, *,
                 block_tables=None, adapter=None,
                 hand_kv: bool = False) -> jax.Array:  # (B, S, D)
        # ``hand_kv``: the result is ``(out, (keys, values))``, the keys and
        # values as the pool keeps them ((B, heads, hd, S), or in decode
        # mode the pool's two leaves after this chunk's write), for the
        # ``cross_attention`` layers below (``HybridAttention``)
        cfg = self.cfg
        h, hd = cfg.num_heads, cfg.head_dim
        if cfg.tp_axis:  # Megatron f: identity fwd, psum bwd (see tp_axis doc)
            x = tp_identity(x, cfg.tp_axis)
        if cfg.layers is not None:
            q, k, v = self._grouped_qkv(x, index)
        elif cfg.weight_dtype:
            qkv = WeightQuantDense(
                (3, h, hd), in_axes=1, bits=_WQ_BITS[cfg.weight_dtype],
                dtype=cfg.dtype, name="qkv",
            )(x)
        elif cfg.quantized_matmuls or cfg.fp8_matmuls:
            qkv = QuantTrainDense(
                (3, h, hd), in_axes=1, dtype=cfg.dtype,
                kernel_init=_dense_init("embed", "qkv", "heads", "kv"),
                mode="fp8" if cfg.fp8_matmuls else "int8",
                name="qkv",
            )(x)
        else:
            qkv = nn.DenseGeneral(
                (3, h, hd),
                axis=-1,
                dtype=cfg.dtype,
                kernel_init=_dense_init("embed", "qkv", "heads", "kv"),
                use_bias=False,
                name="qkv",
            )(x)
        if cfg.lora:
            qkv_a, qkv_b = _lora_bank(self, cfg, "qkv",
                                      cfg.d_model, 3 * h * hd)
            if adapter is not None:
                qkv = qkv + _lora_delta(qkv_a, qkv_b, x,
                                        adapter).reshape(qkv.shape)
        if cfg.layers is None:
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # (B,S,H,hd)
        # "seq_inner": inside a sub-layer the sequence dim is deliberately
        # a DIFFERENT logical axis from the residual stream's "seq" — under
        # Megatron-SP rules "seq" maps to the model axis (sequence-sharded
        # residual stream) while "seq_inner" stays unsharded, so attention
        # and the MLP see the full sequence on a head/ff shard and GSPMD
        # places the all-gather/reduce-scatter pair at the boundary.
        q = _constrain(q, ("batch", "seq_inner", "heads", "kv"))
        k = _constrain(k, ("batch", "seq_inner", "heads", "kv"))
        v = _constrain(v, ("batch", "seq_inner", "heads", "kv"))

        if cfg.differential:
            q, k, v = _in_pairs(q, k, v)
        handed = None
        if hand_kv and not cfg.decode:
            handed = tuple(jnp.transpose(t, (0, 2, 3, 1)) for t in (k, v))
        group = h // k.shape[2]
        if group > 1 and not (cfg.decode and cfg.paged):
            # the training view: every query head beside its own copy of
            # its group's keys and values (the paged path never copies)
            k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        if cfg.decode and cfg.paged:
            out, leaves = self._paged_decode_attend(q, k, v, index,
                                                    block_tables)
            handed = leaves if hand_kv else None
        elif cfg.decode:
            out = self._decode_attend(q, k, v, index)
        elif (cfg.resolve_attn_impl(x.shape[1]) == "flash"
              and not cfg.differential):  # whose value is wider than its key
            from distributed_tensorflow_guide_tpu.ops.flash_attention import (
                flash_attention,
            )

            out = flash_attention(q, k, v, causal=cfg.causal)
        else:
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(hd).astype(
                cfg.dtype
            )
            if cfg.causal:
                s = x.shape[1]
                mask = jnp.tril(jnp.ones((s, s), bool))
                scores = jnp.where(
                    mask[None, None], scores, jnp.finfo(cfg.dtype).min
                )
            probs = jax.nn.softmax(
                scores.astype(jnp.float32), axis=-1
            ).astype(cfg.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        if cfg.differential:
            out = _pairs_difference(self, out, self.layer)
        proj_in = out
        if cfg.weight_dtype:
            out = WeightQuantDense(
                (cfg.d_model,), in_axes=2, bits=_WQ_BITS[cfg.weight_dtype],
                dtype=cfg.dtype, name="proj",
            )(out)
        elif cfg.quantized_matmuls or cfg.fp8_matmuls:
            out = QuantTrainDense(
                (cfg.d_model,), in_axes=2, dtype=cfg.dtype,
                kernel_init=_dense_init("heads", "kv", "embed"),
                mode="fp8" if cfg.fp8_matmuls else "int8",
                name="proj",
            )(out)
        else:
            out = nn.DenseGeneral(
                cfg.d_model,
                axis=(-2, -1),
                dtype=cfg.dtype,
                kernel_init=_dense_init("heads", "kv", "embed"),
                use_bias=cfg.attn_bias,
                name="proj",
            )(out)
        if cfg.lora:
            proj_a, proj_b = _lora_bank(self, cfg, "proj",
                                        h * hd, cfg.d_model)
            if adapter is not None:
                flat = proj_in.reshape(proj_in.shape[:2] + (h * hd,))
                out = out + _lora_delta(proj_a, proj_b, flat, adapter)
        if cfg.tp_axis:  # Megatron g: psum fwd (row-parallel proj), id bwd
            out = tp_allreduce(out, cfg.tp_axis)
        return (out, handed) if hand_kv else out

    def _decode_attend(self, q, k, v, index):
        """KV-cache incremental attention over a (B, C, H, hd) chunk.

        Writes the chunk's k/v at cache positions [index, index+C) and
        attends q against the cache under the mask ``key_pos <= q_pos`` —
        which simultaneously enforces causality within the chunk AND hides
        every not-yet-written cache slot (a slot is written only once its
        position has been reached), so one code path serves prefill
        (C = prompt length) and decode (C = 1) with fully static shapes.

        The cache is ``(B, H, max_len, hd)`` whatever the levers say, the
        layout the Pallas kernel streams, so that path never pays a
        per-step cache transpose. Two bandwidth levers hang off the config
        (decode is HBM-bound — the cache read dominates the step):
        ``kv_dtype="int8"`` stores the cache quantized with
        per-slot-per-head f32 scales beside it, ``(B, H, 1, max_len)``;
        ``decode_impl`` selects the length-aware Pallas streaming kernel
        (ops/decode_attention.py) over the dense full-cache read
        (:func:`_dense_cache_read`).
        """
        cfg = self.cfg
        if index is None:
            raise ValueError("cfg.decode=True requires the write index")
        from distributed_tensorflow_guide_tpu.ops import decode_attention as DA

        B, C, h, hd = q.shape
        quantized = cfg.kv_dtype == "int8"
        cache_dtype = jnp.int8 if quantized else cfg.dtype
        ck = self.variable("cache", "cached_key", jnp.zeros,
                           (B, h, cfg.max_len, hd), cache_dtype)
        cv = self.variable("cache", "cached_value", jnp.zeros,
                           (B, h, cfg.max_len, hd), cache_dtype)
        kT = jnp.transpose(k, (0, 2, 1, 3))  # (B, H, C, hd)
        vT = jnp.transpose(v, (0, 2, 1, 3))
        if quantized:
            kT, k_sc = DA.quantize_kv(kT)
            vT, v_sc = DA.quantize_kv(vT)
        ck.value = lax.dynamic_update_slice(ck.value, kT, (0, 0, index, 0))
        cv.value = lax.dynamic_update_slice(cv.value, vT, (0, 0, index, 0))
        scales = [None, None]
        if quantized:
            scales = []
            for name, sc in (("key_scale", k_sc), ("value_scale", v_sc)):
                leaf = self.variable("cache", name, jnp.zeros,
                                     (B, h, 1, cfg.max_len), jnp.float32)
                leaf.value = lax.dynamic_update_slice(
                    leaf.value, sc[:, :, None, :], (0, 0, 0, index))
                scales.append(leaf.value)

        if cfg.resolve_decode_impl() == "pallas":
            blk_k = DA.decode_blk_k_for(b=B, h=h, s=cfg.max_len, d=hd,
                                        dtype=cache_dtype)
            if DA.supported(cfg.max_len, blk_k, C):
                return DA.decode_attention(
                    q, ck.value, cv.value, index,
                    key_scale=scales[0], value_scale=scales[1], blk_k=blk_k)
            self._note_kernel_missed(
                "decode_attention", C, blk_k,
                f"max_len {cfg.max_len} has no usable KV block")
        return _dense_cache_read(q, ck.value, cv.value, index, "bhkd",
                                 cfg.dtype, *scales)

    def _note_kernel_missed(self, origin: str, C: int, blk_k, why: str):
        """A chunk the Pallas kernel SHOULD take fell through to the dense
        read: a degradation worth the fallback registry. An over-cap
        prefill chunk routing dense is the designed split, not a
        fallback, and is not recorded."""
        from distributed_tensorflow_guide_tpu.ops import decode_attention as DA
        from distributed_tensorflow_guide_tpu.ops.flash_attention import (
            _note_fallback,
        )

        if C <= DA.DECODE_MAX_CHUNK:
            _note_fallback(
                self.cfg.max_len, self.cfg.head_dim, C, blk_k, origin=origin,
                msg=f"{origin}: {why} (resolved {blk_k}); falling back to "
                    "the dense read (slower)")

    def _paged_decode_attend(self, q, k, v, index, block_tables):
        """Paged-pool variant of :meth:`_decode_attend` — same math,
        different cache residency.

        The cache collection holds a POOL of ``cfg.paged_num_blocks``
        fixed-size blocks shared across requests (serve/paged_cache.py);
        ``block_tables`` (B, blocks_per_seq) maps each request's logical
        positions to physical blocks and ``index`` is a PER-REQUEST (B,)
        write-position vector (continuous batching: every slot sits at
        its own length). Writes go through the table into the donated
        pool in place (``write_chunk``); reads either stream the pool
        directly through the Pallas block-table kernel
        (``decode_impl="pallas"``) or gather the logical views and run
        :func:`_dense_cache_read`, the lines the one-shot path runs — the
        per-row mask zeroes whatever junk the trash block and unwritten
        slots carry, which is what keeps the engine token-identical to
        the one-shot path on CPU.

        The pool has ONE layout, ``(N, h, hd, block_size)`` (the int8
        cache's scale rows ``(N, h, 1, block_size)``): a block's slots on
        the lane axis, which is how the device keeps such an array
        whatever shape it is declared with (ops/decode_attention.py, the
        paged section's comment), so neither the write nor the kernel's
        read relays a leaf out. Returns the rows' outputs and the two
        leaves after the write (a ``cross_attention`` layer below reads
        them through the same tables).
        """
        cfg = self.cfg
        if index is None or block_tables is None:
            raise ValueError(
                "paged decode requires the per-request index vector and "
                "the block tables")
        from distributed_tensorflow_guide_tpu.ops import decode_attention as DA
        from distributed_tensorflow_guide_tpu.serve.paged_cache import (
            gather_view,
            write_chunk,
        )

        B, C, hq, hd = q.shape
        # the pool holds the key/value heads; ``hq // h`` query heads
        # share each (1 for GPT-2's block)
        h = k.shape[2]
        N, bs = cfg.paged_num_blocks, cfg.paged_block_size
        quantized = cfg.kv_dtype == "int8"
        impl = cfg.resolve_decode_impl()
        cache_dtype = jnp.int8 if quantized else cfg.dtype
        ck = self.variable("cache", "cached_key", jnp.zeros,
                           (N, h, hd, bs), cache_dtype)
        cv = self.variable("cache", "cached_value", jnp.zeros,
                           (N, h, hd, bs), cache_dtype)
        # beside the Pallas read, the Pallas write (a grid step a block,
        # not a loop step: serve/paged_cache.py write_chunk)
        write = functools.partial(
            write_chunk, tables=block_tables, index=index, block_size=bs,
            kernel=(impl == "pallas"
                    and DA.paged_write_fits((h, hd, bs), cache_dtype)))
        scale_pools = [None, None]
        if quantized:
            k, k_sc = DA.quantize_kv(k)  # (B, C, H, hd), (B, C, H)
            v, v_sc = DA.quantize_kv(v)
            scale_pools = []
            for name, sc in (("key_scale", k_sc), ("value_scale", v_sc)):
                leaf = self.variable("cache", name, jnp.zeros,
                                     (N, h, 1, bs), jnp.float32)
                leaf.value = write(
                    leaf.value, jnp.transpose(sc, (0, 2, 1))[:, :, None])
                scale_pools.append(leaf.value)
        for leaf, rows in ((ck, k), (cv, v)):
            leaf.value = write(
                leaf.value, jnp.transpose(rows, (0, 2, 3, 1)))  # (B,H,hd,C)

        lengths = index + C  # (B,) live length after the write
        # heads in pairs are twice as wide and keep their own width's scale
        scale = cfg.head_dim ** -0.5 if cfg.differential else None
        leaves = (ck.value, cv.value)
        if impl == "pallas":
            blk_k = DA.paged_decode_blk_k_for(
                b=B, h=h, s=cfg.max_len, d=hd, dtype=cache_dtype,
                block_size=bs)
            if DA.paged_supported(cfg.max_len, bs, blk_k, C):
                return DA.paged_decode_attention(
                    q, ck.value, cv.value, block_tables, lengths,
                    key_scale_pool=scale_pools[0],
                    value_scale_pool=scale_pools[1],
                    block_size=bs, blk_k=blk_k, scale=scale), leaves
            self._note_kernel_missed(
                "paged_decode_attention", C, blk_k,
                f"block_size {bs} has no usable KV edge")
        keys, vals, *scales = (
            None if leaf is None else gather_view(leaf, block_tables)
            for leaf in (*leaves, *scale_pools))
        return _dense_cache_read(q, keys, vals, index, "bhdk", cfg.dtype,
                                 *scales, scale=scale), leaves


def _dense_cache_read(q, keys, vals, index, layout: str, dtype,
                      k_scale=None, v_scale=None, *, scale=None,
                      window=None):
    """The dense read of a decode cache, the one statement of it: ``q``
    (B, C, h * group, hd) against each sequence's keys and values in
    ``layout`` — ``"bhkd"``, the one-shot cache as it lies, or ``"bhdk"``,
    the views ``gather_view`` makes of the pool — under the mask ``key_pos
    <= index + c``, ``index`` the position of the chunk's first row (one
    for the batch, or one a row). ``window``: a query sees the ``window``
    keys up to and including its own position and none before them.
    ``scale`` multiplies the scores in place of ``1 / sqrt(hd)``
    (differential attention's queries are widened with zeros and keep the
    scale of the width they had).

    Scores and softmax are float32. The int8 cache's dequantisation is
    folded into the two contractions and no dequantised copy is made: a
    scale (B, h, 1, S) is constant along the contracted ``hd`` axis of QK^T
    (``k_scale`` multiplies the score columns) and along the probability
    axis of AV (``v_scale`` multiplies the probabilities after the
    normaliser), so it factors out exactly. ``group`` query heads share a
    key/value head and none is copied; one is the degenerate case."""
    B, C, hq, hd = q.shape
    h = keys.shape[1]
    seq = keys.shape[layout.index("k")]
    qg = q.reshape(B, C, h, hq // h, hd)
    scores = jnp.einsum(f"bqhgd,{layout}->bhgqk", qg, keys.astype(dtype))
    scores = (scores / jnp.sqrt(hd).astype(dtype) if scale is None
              else scores * scale).astype(jnp.float32)
    if k_scale is not None:
        scores = scores * k_scale[:, :, None]
    q_pos = jnp.reshape(index, (-1, 1)) + jnp.arange(C)  # (B or 1, C)
    mask = jnp.arange(seq)[None, None, :] <= q_pos[:, :, None]
    if window is not None:
        mask &= jnp.arange(seq)[None, None, :] > q_pos[:, :, None] - window
    scores = jnp.where(mask[:, None, None], scores,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, -1)
    if v_scale is not None:
        probs = probs * v_scale[:, :, None]
    out = jnp.einsum(f"bhgqk,{layout}->bqhgd", probs.astype(dtype),
                     vals.astype(dtype))
    return out.reshape(B, C, hq, hd)


def _in_pairs(q, k=None, v=None):
    """Differential attention's heads in pairs (``cfg.differential``). A
    pair of key heads, and the pair's value (both value heads side by
    side), are kept as ONE head twice as wide, each key and value once:
    ``k``, ``v`` ``(B, S, kv, hd) -> (B, S, kv / 2, 2 hd)``. A query head is
    widened with zeros on the other key's side, ``(B, S, h, hd) -> (B, S,
    h, 2 hd)``: an even head ``[q | 0]`` (it reads the pair's first key
    head), an odd one ``[0 | q]`` (the second). The zeros add nothing to a
    score, each head's softmax then weighs the pair's whole value, and the
    paged kernel's grouped heads compute ``A1 V`` and ``A2 V`` as they
    compute any head (at the scale of the width a head had)."""
    zero = jnp.zeros_like(q)
    odd = (jnp.arange(q.shape[2]) % 2 == 1)[:, None]
    q = jnp.where(odd, jnp.concatenate([zero, q], -1),
                  jnp.concatenate([q, zero], -1))
    if k is None:
        return q
    wide = k.shape[:2] + (k.shape[2] // 2, 2 * k.shape[3])
    return q, k.reshape(wide), v.reshape(wide)


def _pairs_difference(module: nn.Module, out, layer: int):
    """Differential attention's epilogue on ``out`` (B, S, h, 2 hd), the
    heads' ``A V`` over :func:`_in_pairs`' keys and values: ``(A1 V -
    lambda A2 V)`` a pair, an RMSNorm over the pair's width (``subln``) and
    ``1 - lambda_init``, with ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
    lambda_init`` and ``lambda_init = 0.8 - 0.6 exp(-0.3 layer)``. The five
    leaves are ``module``'s own. Returns (B, S, h / 2, 2 hd)."""
    cfg = module.cfg
    start = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lq1, lk1, lq2, lk2 = (module.param(
        name, nn.initializers.normal(stddev=0.1), (cfg.head_dim,),
        jnp.float32)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + start
    o = out.astype(jnp.float32)
    o = o[:, :, 0::2] - lam * o[:, :, 1::2]
    eps = 1e-5 if cfg.norm_eps is None else cfg.norm_eps
    gain = module.param("subln", nn.initializers.ones_init(),
                        (o.shape[-1],), jnp.float32)
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                      + eps) * gain * (1.0 - start)
    return o.astype(cfg.dtype)


class HybridAttention(nn.Module):
    """The two kinds of attention a hybrid decoder adds beside
    ``MultiHeadAttention``'s ``"attention"`` (no positions of any kind; the
    same biases and the same heads in pairs where the model has them):

    * ``"cross_attention"``: a query projection only. Keys and values are
      ``shared``, what the last ``attention`` layer handed on (``hand_kv``:
      in decode mode the pool's two leaves, read through the same block
      table and never written);
    * ``"window_attention"``: a query sees its own position and the
      ``cfg.window - 1`` before it. In decode mode the keys and values live
      in a ring of the slot's own, ``cfg.window_ring`` positions in blocks
      of the pool's shape in the ``state`` collection (``win_key``,
      ``win_value``: ``(rows * blocks + 1, heads, hd, block_size)``, row
      ``r``'s blocks ``[r * blocks, (r + 1) * blocks)`` and a last one that
      takes idle rows' writes), position ``p`` in slot ``p % ring``. A
      chunk is written first and then read through a block table TURNED so
      that the ring's oldest block comes first: the view is ``ring``
      consecutive positions ending in the chunk's block, which the paged
      kernel (or the gathered dense read) masks by the window as it would
      any sequence. The ring is whole prefill chunks, and a chunk starts at
      a multiple of its length (the engine's do), so none straddles the
      ring's end; it is at least ``window - 1`` positions and a chunk, so a
      chunk never overwrites a key its first query still sees. Padding
      positions land where the next positions will, on keys ``ring``
      behind that no query sees any more; idle rows write the last block."""

    cfg: TransformerConfig
    kind: str
    layer: int = 0

    @nn.compact
    def __call__(self, x, index=None, *, block_tables=None, state_rows=None,
                 valid=None, shared=None):
        cfg = self.cfg
        h, kv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim

        def heads(n, name):
            return nn.DenseGeneral(
                (n, hd), axis=-1, dtype=cfg.dtype, use_bias=cfg.attn_bias,
                kernel_init=_dense_init("embed", "heads", "kv"), name=name)

        window = None
        if self.kind == "cross_attention":
            q = heads(h, "q")(x)
            keys, vals = shared
            if cfg.differential:
                q = _in_pairs(q)
        else:
            window = cfg.window
            qkv = heads(h + 2 * kv, "qkv")(x)
            q, k, v = qkv[:, :, :h], qkv[:, :, h:h + kv], qkv[:, :, h + kv:]
            if cfg.differential:
                q, k, v = _in_pairs(q, k, v)
            # as the pool keeps them: (B, heads, hd, S)
            keys, vals = (jnp.transpose(t, (0, 2, 3, 1)) for t in (k, v))
        scale = hd ** -0.5
        if not cfg.decode:
            out = _dense_cache_read(q, keys, vals, 0, "bhdk", cfg.dtype,
                                    scale=scale, window=window)
        elif index is None or block_tables is None:
            raise ValueError("attention in decode mode needs the index "
                             "vector and the block tables")
        elif window is not None:
            out = self._ring_attend(q, (keys, vals), index, state_rows,
                                    valid, scale)
        else:
            out = self._read(q, keys, vals, block_tables,
                             index + q.shape[1], scale)
        if cfg.differential:
            out = _pairs_difference(self, out, self.layer)
        return nn.DenseGeneral(
            cfg.d_model, axis=(-2, -1), dtype=cfg.dtype,
            use_bias=cfg.attn_bias,
            kernel_init=_dense_init("heads", "kv", "embed"), name="proj")(out)

    def _read(self, q, keys, vals, tables, lengths, scale, window=None):
        """``q`` (B, C, ...) at positions ``[lengths - C, lengths)`` of the
        sequences ``tables`` lays out over the pool-shaped leaves ``keys``
        and ``vals``: the paged kernel where it is the path and fits, else
        the views gathered and :func:`_dense_cache_read`."""
        cfg = self.cfg
        from distributed_tensorflow_guide_tpu.ops import decode_attention as DA
        from distributed_tensorflow_guide_tpu.serve.paged_cache import (
            gather_view,
        )

        C = q.shape[1]
        bs, span = cfg.paged_block_size, tables.shape[1] * cfg.paged_block_size
        if cfg.resolve_decode_impl() == "pallas":
            blk_k = DA.paged_decode_blk_k_for(
                b=q.shape[0], h=keys.shape[1], s=span, d=q.shape[-1],
                dtype=keys.dtype, block_size=bs)
            if DA.paged_supported(span, bs, blk_k, C):
                return DA.paged_decode_attention(
                    q, keys, vals, tables, lengths, block_size=bs,
                    blk_k=blk_k, scale=scale, window=window)
        return _dense_cache_read(
            q, gather_view(keys, tables), gather_view(vals, tables),
            lengths - C, "bhdk", cfg.dtype, scale=scale, window=window)

    def _ring_attend(self, q, new, index, state_rows, valid, scale):
        """A window layer through its slots' rings (the class's docstring
        has the layout)."""
        cfg = self.cfg
        if valid is None or cfg.window_ring is None:
            raise ValueError("window_attention in decode mode needs the "
                             "valid counts and cfg.window_ring (the engine "
                             "sets it)")
        from distributed_tensorflow_guide_tpu.ops import decode_attention as DA
        from distributed_tensorflow_guide_tpu.serve.paged_cache import (
            write_chunk,
        )

        B, C = q.shape[:2]
        bs, ring = cfg.paged_block_size, cfg.window_ring
        nb = ring // bs
        block = (B * nb + 1,) + new[0].shape[1:3] + (bs,)
        leaves = [self.variable("state", name, jnp.zeros, block, cfg.dtype)
                  for name in WINDOW_LEAVES]
        rows = jnp.arange(B) if state_rows is None else state_rows
        own = rows[:, None] * nb + jnp.arange(nb)  # (B, nb): a row's blocks
        trash = leaves[0].value.shape[0] - 1
        into = jnp.where((valid > 0)[:, None], own, trash)
        for leaf, chunk in zip(leaves, new):
            leaf.value = write_chunk(
                leaf.value, chunk, into, index % ring, block_size=bs,
                kernel=(cfg.resolve_decode_impl() == "pallas"
                        and DA.paged_write_fits(block[1:], cfg.dtype)))
        # the view: the ring's blocks oldest first, once it has wrapped
        last = index + C - 1  # the chunk's last position
        wrapped = (last >= ring)[:, None]
        order = jnp.where(
            wrapped, ((last // bs)[:, None] + 1 + jnp.arange(nb)) % nb,
            jnp.arange(nb))
        first = jnp.where(wrapped[:, 0], (last // bs - (nb - 1)) * bs, 0)
        return self._read(q, leaves[0].value, leaves[1].value,
                          jnp.take_along_axis(own, order, axis=1),
                          last + 1 - first, scale, window=cfg.window)


class MLP(nn.Module):
    cfg: TransformerConfig
    width: int | None = None  # a patterned model's only: None is ``d_ff``

    @nn.compact
    def __call__(self, x: jax.Array, *, adapter=None) -> jax.Array:
        cfg = self.cfg
        if cfg.ffn_gate is not None:
            # a patterned model's forms: no biases, ``width`` wide (a
            # routed layer's shared expert is this module at its own width)
            width = cfg.d_ff if self.width is None else self.width

            def dense(features, names, name):
                return nn.Dense(features, dtype=cfg.dtype, use_bias=False,
                                kernel_init=_dense_init(*names), name=name)

            y = dense(width, ("embed", "mlp"), "up")(x)
            if cfg.ffn_gate == "silu":
                y = nn.silu(dense(width, ("embed", "mlp"), "gate")(x)) * y
            else:  # "relu2"
                y = jnp.square(nn.relu(y))
            y = _constrain(y, ("batch", "seq_inner", "mlp"))
            return dense(cfg.d_model, ("mlp", "embed"), "down")(y)
        if cfg.tp_axis:  # Megatron f
            x = tp_identity(x, cfg.tp_axis)
        if cfg.weight_dtype:
            y = WeightQuantDense(
                (cfg.d_ff,), in_axes=1, bits=_WQ_BITS[cfg.weight_dtype],
                dtype=cfg.dtype, use_bias=True, name="up",
            )(x)
        elif cfg.quantized_matmuls or cfg.fp8_matmuls:
            y = QuantTrainDense(
                (cfg.d_ff,), in_axes=1, dtype=cfg.dtype,
                kernel_init=_dense_init("embed", "mlp"),
                use_bias=True,
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), ("mlp",)
                ),
                mode="fp8" if cfg.fp8_matmuls else "int8",
                name="up",
            )(x)
        else:
            y = nn.Dense(
                cfg.d_ff,
                dtype=cfg.dtype,
                kernel_init=_dense_init("embed", "mlp"),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), ("mlp",)
                ),
                name="up",
            )(x)
        if cfg.lora:
            up_a, up_b = _lora_bank(self, cfg, "up", cfg.d_model, cfg.d_ff)
            if adapter is not None:
                y = y + _lora_delta(up_a, up_b, x, adapter)
        y = nn.gelu(y)
        y = _constrain(y, ("batch", "seq_inner", "mlp"))
        down_in = y
        if cfg.weight_dtype:
            y = WeightQuantDense(
                (cfg.d_model,), in_axes=1, bits=_WQ_BITS[cfg.weight_dtype],
                dtype=cfg.dtype, name="down",
            )(y)
        elif cfg.quantized_matmuls or cfg.fp8_matmuls:
            y = QuantTrainDense(
                (cfg.d_model,), in_axes=1, dtype=cfg.dtype,
                kernel_init=_dense_init("mlp", "embed"),
                mode="fp8" if cfg.fp8_matmuls else "int8",
                name="down",
            )(y)
        else:
            y = nn.Dense(
                cfg.d_model,
                dtype=cfg.dtype,
                kernel_init=_dense_init("mlp", "embed"),
                use_bias=False,
                name="down",
            )(y)
        if cfg.lora:
            down_a, down_b = _lora_bank(self, cfg, "down",
                                        cfg.d_ff, cfg.d_model)
            if adapter is not None:
                y = y + _lora_delta(down_a, down_b, down_in, adapter)
        if cfg.tp_axis:  # Megatron g (row-parallel down-projection)
            y = tp_allreduce(y, cfg.tp_axis)
        return y


class _ExpertBank(nn.Module):
    """The f32 per-expert kernel stack of one MoE projection: a single
    ``kernel`` param of shape (E, d_in, d_out) under this module's name —
    the exact ``{name: {kernel}}`` layout ``ops.quant.quantize_params``
    rewrites per expert (``WQ_BANKS``)."""

    shape: tuple
    names: tuple

    @nn.compact
    def __call__(self):
        return self.param("kernel", _dense_init(*self.names), self.shape,
                          jnp.float32)


class _WeightQuantBank(nn.Module):
    """Weight-only quantized sibling of :class:`_ExpertBank`
    (``cfg.weight_dtype`` on the expert banks): declares ``qkernel``
    (E, d_in[, /2], d_out) at the storage dtype plus per-expert
    per-output-column ``scale`` (E, d_out) f32 — exactly what
    ``quantize_params`` produces from the f32 bank under the SAME module
    name. The dequant is fused after the expert gather
    (``ops.quant.wq_bank_matmul``); init values are placeholders."""

    shape: tuple  # logical (E, d_in, d_out)
    bits: Any = 8

    @nn.compact
    def __call__(self):
        e, d_in, d_out = self.shape
        if self.bits == 4:
            if d_in % 2:
                raise ValueError(
                    f"int4 packing needs an even fan-in, got {d_in}")
            rows, store = d_in // 2, jnp.uint8
        elif self.bits == "fp8":
            rows, store = d_in, jnp.float8_e4m3fn
        else:
            rows, store = d_in, jnp.int8
        qkernel = self.param("qkernel", nn.initializers.zeros_init(),
                             (e, rows, d_out), store)
        scale = self.param("scale", nn.initializers.ones_init(),
                           (e, d_out), jnp.float32)
        return qkernel, scale


class MoEMLP(nn.Module):
    """Routed top-1 MoE FFN (``cfg.moe_experts``) — the MoE sibling of
    :class:`MLP`, single-device (the serve engine's view; EP sharding is
    models/moe_lm.py's story).

    The parallel/expert.py dispatch discipline without the mesh: a f32
    router picks one expert per token, tokens are copied into a
    fixed-capacity (E, C, d) buffer by one-hot einsum (static shapes,
    MXU-friendly batched expert contraction), and the combine gathers the
    gated outputs back. ``C = cfg.moe_capacity`` for single-token
    (decode) calls; multi-token calls (prefill chunks, one-shot oracle,
    ``moe_capacity=None``) widen ``C`` to the token count, which provably
    admits every token (top-1: an expert can receive at most T rows).

    A token past capacity is never dropped silently OR routed elsewhere:
    its dispatch row is zero (the FFN contributes nothing) and its
    overflow flag is sown into the ``moe_stats`` collection —
    ``serve/engine.py`` discards the slot's sampled token and retries the
    SAME token next tick, so every emitted token was computed by its true
    expert (degrade-to-overflow semantics). Dispatch fills in token order
    (cumsum), so the lowest-indexed contending slot always wins a
    capacity seat and at least one slot advances every tick.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jax.Array, *, moe_mask=None) -> jax.Array:
        cfg = self.cfg
        e = cfg.moe_experts
        b, s, d = x.shape
        t = b * s
        xt = x.reshape(t, d)
        if cfg.moe_capacity is None or s > 1:
            capacity = t
        else:
            capacity = cfg.moe_capacity

        # router always in f32: routing decisions are precision-sensitive
        # (the parallel/expert.py rule); name "router" is NOT in
        # WQ_PROJECTIONS, so quantize_params leaves it full precision
        logits = nn.Dense(
            e, dtype=jnp.float32,
            kernel_init=_dense_init("embed", "expert"),
            use_bias=False, name="router",
        )(xt.astype(jnp.float32))
        gates = jax.nn.softmax(logits, axis=-1)

        # top-1 fixed-capacity dispatch, entirely one-hot algebra: exact
        # row copies in, exact gated gathers out — zeros added everywhere
        # else, so the per-token value is independent of C (the basis of
        # the engine-vs-oracle bitwise pin)
        idx = jnp.argmax(gates, axis=1)                       # (T,)
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)
        if moe_mask is not None:
            # serve-engine padding mask: idle decode slots / prefill pad
            # rows route NOWHERE — they consume no capacity (an idle slot
            # must never starve a live one) and contribute nothing to the
            # load/overflow census. Masking cannot change a live token's
            # value: it only ever frees capacity seats, and a row's dot
            # is independent of its buffer position.
            onehot = onehot * moe_mask.reshape(t).astype(
                jnp.float32)[:, None]
        pos = jnp.cumsum(onehot, axis=0) - onehot             # (T, E)
        pos_i = pos.astype(jnp.int32)
        keep = onehot * (pos_i < capacity)
        dispatch = keep[:, :, None] * jax.nn.one_hot(
            pos_i, capacity, dtype=jnp.float32)               # (T, E, C)
        gate_val = jnp.sum(gates * onehot, axis=1)            # (T,)
        combine = dispatch * gate_val[:, None, None]

        # per-expert load / overflow census for the obs plane; sow is a
        # no-op unless the caller passes mutable=["moe_stats"] (the serve
        # step fns do; training and the one-shot oracle don't)
        dropped = onehot - keep
        self.sow("moe_stats", "load", jnp.sum(keep, axis=0))
        self.sow("moe_stats", "overflow", jnp.sum(dropped, axis=0))
        self.sow("moe_stats", "overflow_tok", jnp.sum(dropped, axis=1))

        xb = jnp.einsum("tec,td->ecd", dispatch.astype(cfg.dtype),
                        xt.astype(cfg.dtype))
        shape_in = (e, cfg.d_model, cfg.d_ff)
        shape_out = (e, cfg.d_ff, cfg.d_model)
        from distributed_tensorflow_guide_tpu.ops import quant

        if cfg.weight_dtype:
            bits = _WQ_BITS[cfg.weight_dtype]
            q_in, s_in = _WeightQuantBank(shape_in, bits=bits,
                                          name="w_in")()
            q_out, s_out = _WeightQuantBank(shape_out, bits=bits,
                                            name="w_out")()
            h = nn.gelu(quant.wq_bank_matmul(xb, q_in, s_in, bits=bits,
                                             dtype=cfg.dtype))
            out = quant.wq_bank_matmul(h, q_out, s_out, bits=bits,
                                       dtype=cfg.dtype)
        else:
            w_in = _ExpertBank(shape_in, ("expert", "embed", "mlp"),
                               name="w_in")()
            w_out = _ExpertBank(shape_out, ("expert", "mlp", "embed"),
                                name="w_out")()
            h = nn.gelu(jnp.einsum("ecd,edf->ecf", xb,
                                   w_in.astype(cfg.dtype)))
            out = jnp.einsum("ecf,efd->ecd", h, w_out.astype(cfg.dtype))
        y = jnp.einsum("tec,ecd->td", combine.astype(cfg.dtype), out)
        return y.reshape(b, s, d).astype(x.dtype)


def _state_rows_of(leaf, rows):
    """Rows ``rows`` of a per-slot state leaf: None is every row in order
    (the decode program: batch row ``b`` is slot ``b``), which reads the
    leaf as it stands; the rows of a prefill launch (one a chunk, few and
    their number static) are a slice each."""
    if rows is None:
        return leaf
    return jnp.concatenate(
        [lax.dynamic_slice_in_dim(leaf, rows[r], 1, axis=0)
         for r in range(rows.shape[0])])


def _state_rows_set(leaf, rows, new):
    """``leaf`` with ``rows`` replaced by ``new``, so written that a
    donated leaf is updated where it lies: all rows elementwise, a prefill
    launch's rows as one slice update each (a scatter may copy the leaf
    first, and a leaf is hundreds of megabytes). The rows must differ: the
    updates run in order, so a row given twice keeps the last."""
    new = new.astype(leaf.dtype)
    if rows is None:
        return new
    for r in range(rows.shape[0]):
        leaf = lax.dynamic_update_slice_in_dim(leaf, new[r:r + 1], rows[r],
                                               axis=0)
    return leaf


def _conv_with_state(z, w, state, index, state_rows, valid, bias=None):
    """A depthwise causal convolution of ``z`` (B, S, C) with ``w`` (C, K)
    whose first ``K - 1`` inputs are a sequence's last ones: ``state`` is
    the flax variable holding them, (rows, K - 1, C), or None for the
    training view (zeros before position 0). A chunk at position 0 reads
    zeros, and a chunk of which ``valid[b]`` positions are real leaves the
    inputs of its last real positions (``valid[b] == 0``: the row's state
    as it was). Returns the convolution, (B, S, C)."""
    B, S, _ = z.shape
    taps = w.shape[1]
    if state is None:
        before = jnp.zeros((B, taps - 1, z.shape[2]), z.dtype)
    else:
        held = _state_rows_of(state.value, state_rows)  # (B, K - 1, C)
        fresh = jnp.reshape(index, (-1, 1, 1)) == 0
        before = jnp.where(fresh, jnp.zeros_like(held), held)
    ext = jnp.concatenate([before.astype(z.dtype), z], axis=1)
    c = sum(w[:, j] * ext[:, j:j + S] for j in range(taps))
    if bias is not None:
        c = c + bias
    if state is not None:
        # ext[n : n + K - 1] is z at the last K - 1 real positions
        after = jax.vmap(lambda e, n: lax.dynamic_slice_in_dim(
            e, n, taps - 1, axis=0))(ext, valid)
        after = jnp.where(jnp.reshape(valid, (-1, 1, 1)) > 0,
                          after.astype(held.dtype), held)
        state.value = _state_rows_set(state.value, state_rows, after)
    return c


class ShortConv(nn.Module):
    """The short-convolution mixer (LFM2): ``[B, C, u] = split3(W_in x)``,
    ``z = B * u``, ``c_t = sum_j w[:, j] * z_{t - (K - 1) + j}`` (depthwise,
    causal, zeros before position 0), ``out = W_out (C * c)``.

    A sequence carries ``z`` at its last ``K - 1`` positions. In decode mode
    that is one row of the ``state`` collection's ``conv`` leaf, ``(rows,
    K - 1, d)``: batch row ``b`` reads and writes row ``state_rows[b]``
    (the engine's slot; None: row ``b``), as ``_conv_with_state`` says."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jax.Array, index=None, *, state_rows=None,
                 valid=None) -> jax.Array:
        cfg = self.cfg
        d, taps = cfg.d_model, cfg.conv_kernel
        B, S, _ = x.shape
        bcu = nn.Dense(3 * d, dtype=cfg.dtype, use_bias=False,
                       kernel_init=_dense_init("embed", "mlp"),
                       name="in_proj")(x)
        gate_b, gate_c, u = jnp.split(bcu, 3, axis=-1)
        z = gate_b * u
        w = self.param("conv_w", _dense_init("embed", "conv"), (d, taps),
                       jnp.float32).astype(cfg.dtype)
        state = None
        if cfg.decode:
            if index is None or valid is None:
                raise ValueError("short_conv in decode mode needs the "
                                 "index and the valid counts")
            state = self.variable("state", "conv", jnp.zeros,
                                  (B, taps - 1, d), cfg.dtype)
        c = _conv_with_state(z, w, state, index, state_rows, valid)
        return nn.Dense(d, dtype=cfg.dtype, use_bias=False,
                        kernel_init=_dense_init("mlp", "embed"),
                        name="out_proj")(gate_c * c)


class Mamba2(nn.Module):
    """The Mamba-2 state-space mixer (``ops/ssm_scan.py`` has the
    recurrence). With ``H`` heads of ``P``, ``G`` groups and a state of
    ``N``: ``[z | xBC | dt] = W_in u`` (``HP | HP + 2GN | H``); ``xBC =
    silu(conv(xBC) + b)``, depthwise and causal over ``conv_kernel`` taps;
    ``x, B, C = split(xBC)``; ``dt = softplus(dt + dt_bias)``, ``A =
    -exp(A_log)``, both float32; ``y = scan(x, dt, A, B, C) + D x``; then
    gate and norm, ``y = RMSNorm_groups(y * silu(z)) * g`` (each of the
    ``G`` groups of ``HP / G`` channels normalised alone); ``out = W_out
    y``. No biases but the convolution's.

    A sequence carries two things, each a row of a leaf of the ``state``
    collection in decode mode: ``conv`` (rows, K - 1, HP + 2GN) in the
    activations' dtype, the convolution's last inputs, and ``ssm`` (rows,
    H, P, N) float32, the state matrices (float32 whatever the activations
    are: a sum over every position the sequence has had). Rows, fresh
    chunks and padding as in ``_conv_with_state``; for the scan a padding
    position or an idle row has ``dt = 0``, which leaves the state as it
    was. A run of one position (the decode program) takes the recurrence's
    one step, a longer one the chunked form, a chunk a call."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, u: jax.Array, index=None, *, state_rows=None,
                 valid=None) -> jax.Array:
        from distributed_tensorflow_guide_tpu.ops.ssm_scan import (
            ssm_chunked,
            ssm_step,
        )

        cfg = self.cfg
        H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                      cfg.ssm_state)
        inner, wide, taps = H * P, H * P + 2 * G * N, cfg.conv_kernel
        B, S, _ = u.shape
        zxd = nn.Dense(inner + wide + H, dtype=cfg.dtype, use_bias=False,
                       kernel_init=_dense_init("embed", "mlp"),
                       name="in_proj")(u)
        z, xbc, dt = jnp.split(zxd, [inner, inner + wide], axis=-1)
        conv_w = self.param("conv_w", _dense_init("mlp", "conv"),
                            (wide, taps), jnp.float32).astype(cfg.dtype)
        conv_b = self.param("conv_b", nn.initializers.zeros_init(), (wide,),
                            jnp.float32).astype(cfg.dtype)
        per_head = nn.with_logical_partitioning
        dt_bias = self.param("dt_bias", per_head(
            nn.initializers.zeros_init(), ("heads",)), (H,), jnp.float32)
        a_log = self.param("A_log", per_head(
            nn.initializers.zeros_init(), ("heads",)), (H,), jnp.float32)
        skip = self.param("D", per_head(
            nn.initializers.ones_init(), ("heads",)), (H,), jnp.float32)
        conv_state = ssm_state = None
        if cfg.decode:
            if index is None or valid is None:
                raise ValueError("mamba2 in decode mode needs the index "
                                 "and the valid counts")
            conv_state = self.variable("state", "conv", jnp.zeros,
                                       (B, taps - 1, wide), cfg.dtype)
            ssm_state = self.variable("state", "ssm", jnp.zeros,
                                      (B, H, P, N), jnp.float32)
        with jax.named_scope("dtg.ssm.conv"):
            xbc = nn.silu(_conv_with_state(xbc, conv_w, conv_state, index,
                                           state_rows, valid, bias=conv_b))
        x, b, c = jnp.split(xbc, [inner, inner + G * N], axis=-1)
        x = x.reshape(B, S, H, P)
        b, c = b.reshape(B, S, G, N), c.reshape(B, S, G, N)
        with jax.named_scope("dtg.ssm.scan"):
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            a = -jnp.exp(a_log)
            if ssm_state is None:
                carried = jnp.zeros((B, H, P, N), jnp.float32)
            else:
                held = _state_rows_of(ssm_state.value, state_rows)
                fresh = jnp.reshape(index, (-1, 1, 1, 1)) == 0
                carried = jnp.where(fresh, jnp.zeros_like(held), held)
                real = jnp.arange(S)[None, :] < valid[:, None]  # (B, S)
                dt = jnp.where(real[..., None], dt, 0.0)
            if S == 1:
                y, after = ssm_step(x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0],
                                    carried)
                y = y[:, None]
            else:
                y, after = ssm_chunked(x, dt, a, b, c, carried,
                                       chunk=cfg.ssm_chunk)
            if ssm_state is not None:
                # a row with no real position keeps what it held, a fresh
                # one's zeros included: nothing reads them before a chunk
                # at position 0 does
                after = jnp.where(
                    jnp.reshape(valid, (-1, 1, 1, 1)) > 0, after, held)
                ssm_state.value = _state_rows_set(ssm_state.value,
                                                  state_rows, after)
            y = y + skip[:, None] * x.astype(jnp.float32)
        with jax.named_scope("dtg.ssm.gate_norm"):
            y = y.reshape(B, S, inner) * nn.silu(z.astype(jnp.float32))
            eps = 1e-6 if cfg.norm_eps is None else cfg.norm_eps
            grouped = y.reshape(B, S, G, inner // G)
            grouped = grouped * lax.rsqrt(
                jnp.mean(jnp.square(grouped), -1, keepdims=True) + eps)
            gain = self.param("norm_g", nn.with_logical_partitioning(
                nn.initializers.ones_init(), ("mlp",)), (inner,), jnp.float32)
            y = (grouped.reshape(B, S, inner) * gain).astype(cfg.dtype)
        return nn.Dense(cfg.d_model, dtype=cfg.dtype, use_bias=False,
                        kernel_init=_dense_init("mlp", "embed"),
                        name="out_proj")(y)


class Mamba1(nn.Module):
    """The Mamba-1 state-space mixer (``ops/ssm_scan.py``'s end has the
    recurrence). With ``D = ssm_inner`` channels, a state of ``N`` a
    channel and a step projection of rank ``R``: ``[u | z] = W_in x``; ``u
    = silu(conv(u) + b)``, depthwise and causal over ``conv_kernel`` taps;
    ``[r | B | C] = W_x u`` (``R | N | N``); ``dt = softplus(W_dt r +
    b_dt)`` and ``A = -exp(A_log)`` (D, N), both float32; ``y = scan(u, dt,
    A, B, C) + D u``; ``out = W_out (y * silu(z))``. Returns ``(out, y)``:
    ``y``, before the gate, is what a later ``gmu`` layer gates by.

    A sequence carries two things, each a row of a leaf of the ``state``
    collection in decode mode, as ``Mamba2``'s: ``conv`` (rows, K - 1, D)
    in the activations' dtype and ``ssm`` (rows, D, N) float32. Rows, fresh
    chunks, padding and idle rows as there (``dt = 0`` leaves the state)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jax.Array, index=None, *, state_rows=None,
                 valid=None):
        from distributed_tensorflow_guide_tpu.ops.ssm_scan import (
            selective_scan,
            selective_step,
        )

        cfg = self.cfg
        D, N, R, taps = (cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank,
                         cfg.conv_kernel)
        B, S, _ = x.shape

        def dense(features, names, name, **kw):
            return nn.Dense(features, dtype=cfg.dtype,
                            kernel_init=_dense_init(*names), name=name, **kw)

        u, z = jnp.split(dense(2 * D, ("embed", "mlp"), "in_proj",
                               use_bias=False)(x), 2, axis=-1)
        conv_w = self.param("conv_w", _dense_init("mlp", "conv"),
                            (D, taps), jnp.float32).astype(cfg.dtype)
        conv_b = self.param("conv_b", nn.initializers.zeros_init(), (D,),
                            jnp.float32).astype(cfg.dtype)
        a_log = self.param("A_log", _dense_init("mlp", "state"), (D, N),
                           jnp.float32)
        skip = self.param("D", nn.with_logical_partitioning(
            nn.initializers.ones_init(), ("mlp",)), (D,), jnp.float32)
        conv_state = ssm_state = None
        if cfg.decode:
            if index is None or valid is None:
                raise ValueError("mamba1 in decode mode needs the index "
                                 "and the valid counts")
            conv_state = self.variable("state", "conv", jnp.zeros,
                                       (B, taps - 1, D), cfg.dtype)
            ssm_state = self.variable("state", "ssm", jnp.zeros, (B, D, N),
                                      jnp.float32)
        with jax.named_scope("dtg.ssm.conv"):
            u = nn.silu(_conv_with_state(u, conv_w, conv_state, index,
                                         state_rows, valid, bias=conv_b))
        r, b, c = jnp.split(dense(R + 2 * N, ("mlp", "state"), "x_proj",
                                  use_bias=False)(u), [R, R + N], axis=-1)
        dt = nn.Dense(D, dtype=jnp.float32,
                      kernel_init=_dense_init("state", "mlp"),
                      name="dt_proj")(r)
        with jax.named_scope("dtg.ssm.scan"):
            dt = jax.nn.softplus(dt)
            a = -jnp.exp(a_log)
            if ssm_state is None:
                carried = jnp.zeros((B, D, N), jnp.float32)
            else:
                held = _state_rows_of(ssm_state.value, state_rows)
                fresh = jnp.reshape(index, (-1, 1, 1)) == 0
                carried = jnp.where(fresh, jnp.zeros_like(held), held)
                real = jnp.arange(S)[None, :] < valid[:, None]  # (B, S)
                dt = jnp.where(real[..., None], dt, 0.0)
            if S == 1:
                y, after = selective_step(u[:, 0], dt[:, 0], a, b[:, 0],
                                          c[:, 0], carried)
                y = y[:, None]
            else:
                y, after = selective_scan(u, dt, a, b, c, carried)
            if ssm_state is not None:
                after = jnp.where(jnp.reshape(valid, (-1, 1, 1)) > 0, after,
                                  held)
                ssm_state.value = _state_rows_set(ssm_state.value,
                                                  state_rows, after)
            y = y + skip * u.astype(jnp.float32)
        gated = (y * nn.silu(z.astype(jnp.float32))).astype(cfg.dtype)
        return dense(cfg.d_model, ("mlp", "embed"), "out_proj",
                     use_bias=False)(gated), y


class GatedMemory(nn.Module):
    """The gated memory unit: ``out = W_2 (silu(W_1 x) * m)``, ``m`` (B, S,
    ssm_inner) the scan output of the last ``mamba1`` layer before this one
    at the same positions of the same forward pass: an activation of the
    launch, never kept. No state, no cache, no biases."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jax.Array, memory: jax.Array) -> jax.Array:
        cfg = self.cfg
        gate = nn.Dense(cfg.ssm_inner, dtype=cfg.dtype, use_bias=False,
                        kernel_init=_dense_init("embed", "mlp"),
                        name="in_proj")(x)
        y = (nn.silu(gate.astype(jnp.float32)) * memory).astype(cfg.dtype)
        return nn.Dense(cfg.d_model, dtype=cfg.dtype, use_bias=False,
                        kernel_init=_dense_init("mlp", "embed"),
                        name="out_proj")(y)


class RoutedMLP(nn.Module):
    """The routed feed-forward of a patterned model: ``ops/routed_ffn.py``
    over this module's router, selection bias and the banks of the experts
    the program holds, each of ``ffn_gate``'s form (the plain one has no
    ``w_gate`` bank). Sows the router's census
    (``load``, (E,)) into the ``routed_stats`` collection, a no-op unless
    the caller makes it mutable (the serve step does)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jax.Array, *, live=None) -> jax.Array:
        from distributed_tensorflow_guide_tpu.ops.routed_ffn import (
            routed_ffn,
        )

        cfg = self.cfg
        e, held = cfg.routed_experts, cfg.routed_held
        d, ff = cfg.d_model, cfg.routed_d_ff_stored or cfg.routed_d_ff
        b, s, _ = x.shape
        router = self.param("router", _dense_init("embed", "expert"),
                            (d, e), jnp.float32)
        bias = self.param("expert_bias", nn.initializers.zeros_init(),
                          (e,), jnp.float32)
        up_names, down_names = (("expert", "embed", "mlp"),
                                ("expert", "mlp", "embed"))
        gated = cfg.ffn_gate == "silu"
        y, load = routed_ffn(
            x.reshape(b * s, d).astype(cfg.dtype), router, bias,
            (_ExpertBank((held, d, ff), up_names, name="w_gate")()
             if gated else None),
            _ExpertBank((held, d, ff), up_names, name="w_up")(),
            _ExpertBank((held, ff, d), down_names, name="w_down")(),
            top_k=cfg.routed_top_k, first=cfg.routed_first,
            live=None if live is None else live.reshape(b * s),
            scale=cfg.routed_scale, norm_eps=cfg.routed_norm_eps)
        self.sow("routed_stats", "load", load)
        return y.reshape(b, s, d)


class Block(nn.Module):
    """Pre-LN transformer block: x + attn(LN(x)); x + mlp(LN(x)).
    ``kinds`` (a patterned model's layer) names the mixer and the
    feed-forward the same two lines run."""

    cfg: TransformerConfig
    kinds: tuple | None = None
    layer: int = 0  # a patterned model's: the layer's index

    def _hybrid_mixer(self, h, index, block_tables, state_rows, valid,
                      carried):
        """The mixers that read or hand on what ``carried`` holds: ``kv``,
        the keys and values of the last full-attention layer (in decode
        mode the pool's two leaves), which the ``cross_attention`` layers
        below it read (the producer and its readers under one outer scope,
        ``dtg.shared_kv``), and ``memory``, the last ``mamba1`` layer's scan
        output, which a ``gmu`` layer gates by; and ``window_attention``,
        which does neither. Returns the mixer's output and ``carried``
        after it."""
        cfg, mixer = self.cfg, self.kinds[0]
        if mixer == "gmu":
            with jax.named_scope("dtg.gmu"):
                return GatedMemory(cfg, name="gmu")(
                    h, carried["memory"]), carried
        if mixer == "mamba1":
            with jax.named_scope("dtg.ssm"):
                out, y = Mamba1(cfg, name="ssm")(
                    h, index, state_rows=state_rows, valid=valid)
            return out, {**carried, "memory": y}
        if mixer == "window_attention":
            with jax.named_scope("dtg.window_attn"):
                return HybridAttention(
                    cfg, kind=mixer, layer=self.layer, name="attn")(
                        h, index, block_tables=block_tables,
                        state_rows=state_rows, valid=valid), carried
        with jax.named_scope("dtg.shared_kv"):
            if mixer == "cross_attention":
                with jax.named_scope("dtg.cross_attn"):
                    return HybridAttention(
                        cfg, kind=mixer, layer=self.layer, name="attn")(
                            h, index, block_tables=block_tables,
                            shared=carried["kv"]), carried
            with jax.named_scope("dtg.attn"):
                out, kv = MultiHeadAttention(
                    cfg, layer=self.layer, name="attn")(
                        h, index, block_tables=block_tables, hand_kv=True)
        return out, {**carried, "kv": kv}

    def _patterned(self, x, index, block_tables, state_rows, valid,
                   carried):
        """``x + mixer(norm(x))`` then ``x + ffn(norm(x))``, each half only
        if the layer has it (and its norm, ``ln1`` / ``ln2``, with it). A
        routed layer's shared expert reads the same normalised rows and is
        a module of the block's, ``shared``, beside ``mlp``. Returns ``x``
        and what the layer hands to later ones (``_hybrid_mixer``)."""
        cfg, (mixer, ffn) = self.cfg, self.kinds
        if mixer is not None:
            h = _norm(cfg, "ln1")(x)
            mixers = [m for m, _ in cfg.layers]
            if mixer in ("mamba1", "gmu") + HYBRID_ATTENTION or (
                    mixer == "attention" and "cross_attention" in mixers):
                out, carried = self._hybrid_mixer(
                    h, index, block_tables, state_rows, valid, carried)
                x = x + out
            elif mixer == "attention":
                with jax.named_scope("dtg.attn"):
                    x = x + MultiHeadAttention(
                        cfg, layer=self.layer, name="attn")(
                            h, index, block_tables=block_tables)
            else:
                scope, module, name = {
                    "short_conv": ("dtg.short_conv", ShortConv, "conv"),
                    "mamba2": ("dtg.ssm", Mamba2, "ssm")}[mixer]
                with jax.named_scope(scope):
                    x = x + module(cfg, name=name)(
                        h, index, state_rows=state_rows, valid=valid)
        if ffn == "routed":
            h2 = _norm(cfg, "ln2")(x)
            live = None
            if valid is not None:
                live = jnp.arange(x.shape[1])[None, :] < valid[:, None]
            with jax.named_scope("dtg.routed"):
                y = RoutedMLP(cfg, name="mlp")(h2, live=live)
            if cfg.shared_d_ff is not None:
                with jax.named_scope("dtg.shared_expert"):
                    y = y + MLP(cfg, width=cfg.shared_d_ff,
                                name="shared")(h2)
            x = x + y
        elif ffn == "dense":
            x = x + MLP(cfg, name="mlp")(_norm(cfg, "ln2")(x))
        return _constrain(x, ("batch", "seq", "embed")), carried

    @nn.compact
    def __call__(self, x: jax.Array, index=None, *,
                 block_tables=None, adapter=None,
                 moe_mask=None, state_rows=None, valid=None,
                 carried=None):
        # ``carried`` (a patterned model's, from ``Transformer``): what the
        # layers before hand to this one; given, the result is ``(x,
        # carried)`` after this layer, else ``x`` alone
        cfg = self.cfg
        if self.kinds is not None:
            x, after = self._patterned(
                x, index, block_tables, state_rows, valid,
                {} if carried is None else carried)
            return x if carried is None else (x, after)
        # Attention-only selective remat (core/precision.py): checkpoint the
        # attention sub-layer here so EVERY consumer — the flat Transformer,
        # all four pipeline schedules — gets the same HBM/FLOP trade without
        # per-schedule wiring. nn.remat preserves the "attn" param path, so
        # the layout is identical across modes. prevent_cse=False as in the
        # block-level sites (scan bodies need no CSE barrier).
        attn_cls = MultiHeadAttention
        if cfg.resolved_remat_mode == "attention":
            attn_cls = nn.remat(MultiHeadAttention, prevent_cse=False)
        attn = attn_cls(cfg, name="attn")
        h = nn.LayerNorm(dtype=cfg.dtype, name="ln1")(x)
        x = x + attn(h, index, block_tables=block_tables, adapter=adapter)
        h2 = nn.LayerNorm(dtype=cfg.dtype, name="ln2")(x)
        if cfg.moe:
            x = x + MoEMLP(cfg, name="mlp")(h2, moe_mask=moe_mask)
        else:
            x = x + MLP(cfg, name="mlp")(h2, adapter=adapter)
        return _constrain(x, ("batch", "seq", "embed"))


def _positions_kept(x, last):
    """``x`` (B, S, D) at position ``last[b]`` of each row, (B, 1, D);
    ``x`` itself where ``last`` is None."""
    if last is None:
        return x
    return jnp.take_along_axis(x, jnp.reshape(last, (-1, 1, 1)), axis=1)


class Transformer(nn.Module):
    """Token-in, logits-out. ``cfg.num_classes`` set → [CLS]-pooled
    classification logits (BERT/GLUE); otherwise per-token LM logits."""

    cfg: TransformerConfig

    def _patterned(self, x, index, block_tables, state_rows, valid,
                   return_hidden, embed, last=None):
        """The forward of a model given as a pattern of layers, from the
        embedded tokens on: the learned table only if that is what the
        model has (``position_kind``), each layer its own mixer or
        feed-forward or both, the model's normalisation, the same head (or
        ``embed``'s table, transposed, where the head is tied to it)."""
        cfg = self.cfg
        if cfg.decode and not cfg.paged:
            raise ValueError(
                "a patterned model decodes through the paged engine only "
                "(serve/engine.py): it has no one-shot cache")
        if cfg.position_kind == "table":
            positions = jnp.arange(x.shape[1])[None, :]
            if cfg.decode:
                positions = positions + jnp.reshape(index, (-1, 1))
            x = x + nn.Embed(cfg.max_len, cfg.d_model, dtype=cfg.dtype,
                             embedding_init=_dense_init("seq", "embed"),
                             name="pos_emb")(positions)
        x = _constrain(x, ("batch", "seq", "embed"))
        block = Block
        if cfg.resolved_remat_mode == "block":
            block = nn.remat(Block, prevent_cse=False)
        carried = {}  # what a layer hands to later ones: Block._hybrid_mixer
        for i, kinds in enumerate(cfg.layers):
            x, carried = block(cfg, kinds=tuple(kinds), layer=i,
                               name=f"block_{i}")(
                x, index, block_tables=block_tables,
                state_rows=state_rows, valid=valid, carried=carried)
        x = _norm(cfg, "ln_f")(_positions_kept(x, last))
        if return_hidden:
            return x
        if cfg.tie_embeddings:
            # float32 logits, as the head below gives, out of the table as
            # it is stored: no float32 copy of it is made
            return jnp.einsum("bsd,vd->bsv", x,
                              embed.embedding.astype(cfg.dtype),
                              preferred_element_type=jnp.float32)
        return nn.Dense(cfg.vocab_size, dtype=jnp.float32, use_bias=False,
                        kernel_init=_dense_init("embed", "vocab"),
                        name="lm_head")(x)

    @nn.compact
    def __call__(self, tokens: jax.Array, index=None, *,
                 block_tables=None, adapter=None, moe_mask=None,
                 state_rows=None, valid=None, last=None,
                 return_hidden: bool = False) -> jax.Array:
        # tokens (B, S) int32; ``index`` only in cfg.decode mode: the
        # absolute position of tokens[:, 0] (prefill passes 0, the decode
        # loop passes the running length). ``return_hidden`` stops after the
        # final LayerNorm and returns the (B, S, D) hidden states WITHOUT
        # applying the LM head — the entry point of the fused
        # cross-entropy loss path (ops/fused_ce.py), which must never see
        # full-vocab logits. Param layout is unchanged (init runs the
        # default call, so lm_head still materializes). ``last`` (B,) keeps
        # one position a row for the final norm and the head, (B, 1, V)
        # logits: a prefill launch samples one position a row, and the
        # head over a whole chunk is 100 MB of float32 a row at a 200k
        # vocabulary.
        cfg = self.cfg
        if cfg.decode and index is None:
            raise ValueError("cfg.decode=True requires the position index")
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.d_model,
            dtype=cfg.dtype,
            embedding_init=_dense_init("vocab", "embed"),
            name="tok_emb",
        )
        x = embed(tokens)
        if cfg.layers is not None:
            return self._patterned(x, index, block_tables, state_rows,
                                   valid, return_hidden, embed, last)
        positions = jnp.arange(tokens.shape[1])[None, :]
        if cfg.decode:
            # the serve engine passes a PER-REQUEST (B,) index vector
            # (continuous batching: each slot sits at its own length),
            # the one-shot path one position for the batch
            if getattr(index, "ndim", 0):
                positions = positions + index[:, None]
            else:
                positions = positions + index
        pos = nn.Embed(
            cfg.max_len,
            cfg.d_model,
            dtype=cfg.dtype,
            embedding_init=_dense_init("seq", "embed"),
            name="pos_emb",
        )(positions)
        x = x + pos
        x = _constrain(x, ("batch", "seq", "embed"))

        block = Block
        if cfg.resolved_remat_mode == "block":
            block = nn.remat(Block, prevent_cse=False)
        for i in range(cfg.num_layers):
            x = block(cfg, name=f"block_{i}")(
                x, index, block_tables=block_tables, adapter=adapter,
                moe_mask=moe_mask)
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_f")(
            _positions_kept(x, last))
        if return_hidden:
            return x

        if cfg.num_classes is not None:
            cls = x[:, 0]  # [CLS] pooling
            return nn.Dense(
                cfg.num_classes, dtype=jnp.float32, name="classifier"
            )(cls)
        if cfg.weight_dtype:
            # quantized head: logits still f32 (the scale multiply IS the
            # f32 promotion); quantized_matmuls deliberately leaves the
            # head at full precision (accumulation/loss contract)
            logits = WeightQuantDense(
                (cfg.vocab_size,), in_axes=1,
                bits=_WQ_BITS[cfg.weight_dtype],
                dtype=jnp.float32, name="lm_head",
            )(x)
        else:
            logits = nn.Dense(
                cfg.vocab_size,
                dtype=jnp.float32,
                use_bias=False,
                kernel_init=_dense_init("embed", "vocab"),
                name="lm_head",
            )(x)
        return logits


def make_lm_loss_fn(model: Transformer, *, fused_ce="auto",
                    ce_chunk: int | None = None):
    """Next-token LM loss: ``(params, batch{tokens}) -> (loss, metrics)``.

    ``fused_ce`` ("auto"|True|False, resolved by
    ``ops.fused_ce.resolve_fused_ce``) routes the head through the chunked
    fused cross-entropy: the trunk stops at the final LayerNorm
    (``return_hidden``) and loss + grad-of-logits run per vocab chunk, so
    no ``(B, S, V)`` tensor is ever live — the HBM diet for every DP/FSDP
    LM call site. The naive path is byte-identical to the historical one.
    """
    from distributed_tensorflow_guide_tpu.ops.fused_ce import (
        fused_next_token_loss,
        resolve_fused_ce,
    )

    use_fused = resolve_fused_ce(fused_ce, vocab_size=model.cfg.vocab_size)

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        if use_fused:
            hidden = model.apply({"params": params}, tokens,
                                 return_hidden=True)
            # params may carry flax partitioning boxes (logical-axis
            # metadata); the kernel itself is the boxed value
            kernel = nn.meta.unbox(params["lm_head"]["kernel"])
            loss = fused_next_token_loss(hidden, kernel, tokens,
                                         chunk=ce_chunk)
            return loss, {"perplexity": jnp.exp(loss)}
        logits = model.apply({"params": params}, tokens)  # (B, S, V)
        targets = tokens[:, 1:]
        logp = jax.nn.log_softmax(logits[:, :-1])
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        loss = -jnp.mean(ll)
        return loss, {"perplexity": jnp.exp(loss)}

    return loss_fn


def make_cls_loss_fn(model: Transformer):
    """Sequence classification (GLUE-style): batch {tokens, label}."""

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["tokens"])
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(
            jnp.take_along_axis(logp, batch["label"][:, None], axis=1)
        )
        acc = jnp.mean(jnp.argmax(logits, -1) == batch["label"])
        return loss, {"accuracy": acc}

    return loss_fn
