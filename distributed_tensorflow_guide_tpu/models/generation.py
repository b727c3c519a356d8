"""Autoregressive generation — the serving half of the LM family.

The reference is a training tutorial and has no inference path at all;
this is capability the TPU build adds on top of parity. TPU-first shape:

* **Static shapes everywhere.** The KV cache is a fixed (B, H, max_len,
  hd) buffer per layer (flax "cache" collection, written with
  ``lax.dynamic_update_slice``); the decode loop is ONE ``lax.scan`` whose
  body processes exactly one token — the whole generate call compiles to
  a single XLA program, no per-token dispatch, no retraces as the
  sequence grows.
* **One attention code path for prefill and decode**: a chunk of C tokens
  attends to the full cache under ``key_pos <= q_pos`` (masking both
  causality and not-yet-written slots), so the prompt is ingested in one
  forward pass (C = prompt length) and decode steps reuse the same module
  with C = 1 (models/transformer.py ``_decode_attend``).
* Sampling: greedy (``temperature=0``), temperature, and top-k — all
  branchless (top-k via ``lax.top_k`` threshold masking) so the scan body
  stays a single fused program. Keys derive from the absolute position
  (``fold_in(rng, position)``), which is what lets speculative decoding
  reproduce the vanilla stream token-for-token.
* Decode bandwidth levers (round 11): ``cfg.kv_dtype="int8"`` (quantized
  cache, fused dequant), ``cfg.decode_impl`` (length-aware Pallas
  decode-attention — ops/decode_attention.py), and
  ``spec_draft_layers``/``spec_lookahead`` (self-speculative decoding —
  see :func:`make_generate_fn`). docs/serving.md "Decode levers" covers
  when each pays.

Decode-mode parity with the training forward is pinned by
tests/test_generation.py (prefill logits == full-forward logits; greedy
decode == argmax-rescoring the growing prefix with the training model).
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_guide_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
)


def decode_config(cfg: TransformerConfig) -> TransformerConfig:
    """The serving view of a training config: KV-cache attention (dense —
    flash is a long-context *training* kernel; decode chunks are 1 token),
    no remat (nothing to rematerialize without a backward pass)."""
    # remat cleared at BOTH spellings: the precision-policy remat_mode wins
    # over the legacy bool in resolved_remat_mode, so leaving it set would
    # silently keep checkpointing in the serving forward
    return dataclasses.replace(cfg, decode=True, attn_impl="dense",
                               remat=False, remat_mode=None)


def cache_shapes(cfg: TransformerConfig, batch_size: int):
    """Abstract (shape/dtype) tree of the decode KV cache — the SINGLE
    derivation :func:`init_cache` and :func:`make_generate_fn` share, so
    the allocated cache can never drift from what generate traces."""
    model = Transformer(decode_config(cfg))
    variables = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((batch_size, 1), jnp.int32), 0)
    return variables["cache"]


def init_cache(cfg: TransformerConfig, params, batch_size: int):
    """Allocate the fixed-size KV cache for ``batch_size`` sequences."""
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         cache_shapes(cfg, batch_size))
    del params  # shape/dtype only — kept in the signature for call-site symmetry
    return cache


def decode_cache_bytes_per_step(cfg: TransformerConfig, batch_size: int, *,
                                effective_len: int | None = None) -> float:
    """KV-cache HBM traffic of ONE decode step: ``effective_len`` slots
    read (K and V, at the CACHE dtype — 1 byte under ``kv_dtype="int8"`` —
    plus the two per-slot f32 scales when quantized) and one slot written.

    ``effective_len=None`` models the dense static-shape path, which
    attends against all ``max_len`` slots every step. The length-aware
    Pallas kernel (``decode_impl="pallas"``) reads only written blocks, so
    its caller passes the block-rounded live length — charging it the full
    cache would overstate its achieved bandwidth and flatter the roofline
    fraction the ≥0.4 gate judges."""
    import jax.numpy as _jnp

    from distributed_tensorflow_guide_tpu.ops.decode_attention import (
        cache_slot_bytes,
    )

    length = cfg.max_len if effective_len is None else min(
        int(effective_len), cfg.max_len)
    kv_dtype = _jnp.int8 if cfg.kv_dtype == "int8" else cfg.dtype
    # bytes per (batch, slot): K + V vectors across heads (+ scales when
    # quantized) — the shared per-(slot, head) definition, so this model
    # and the kernel-only bench's can never disagree on the same cache
    per_slot = cfg.num_heads * cache_slot_bytes(cfg.head_dim, kv_dtype)
    read = cfg.num_layers * batch_size * length * per_slot
    write = cfg.num_layers * batch_size * per_slot  # one slot
    return float(read + write)


def paged_decode_cache_bytes_per_step(cfg: TransformerConfig, *,
                                      block_size: int, live_blocks: int,
                                      active_slots: int) -> float:
    """KV-cache HBM traffic of ONE paged decode step: the pool's LIVE
    blocks read (continuous batching reads what resident requests have
    written, not ``batch * max_len``) and one slot written per active
    decode slot. Built on the same per-(slot, head)
    ``ops.decode_attention.cache_slot_bytes`` definition as the dense
    model above — the serve engine and ``bench_generate.py`` share one
    byte model, so the serving roofline rows cannot silently reuse the
    dense ``max_len`` charge (the whole point of paging)."""
    import jax.numpy as _jnp

    from distributed_tensorflow_guide_tpu.ops.decode_attention import (
        cache_slot_bytes,
    )

    kv_dtype = _jnp.int8 if cfg.kv_dtype == "int8" else cfg.dtype
    per_slot = cfg.num_heads * cache_slot_bytes(cfg.head_dim, kv_dtype)
    read = cfg.num_layers * live_blocks * block_size * per_slot
    write = cfg.num_layers * active_slots * per_slot
    return float(read + write)


def decode_hbm_bytes_per_step(cfg: TransformerConfig, params,
                              batch_size: int, *,
                              effective_len: int | None = None) -> float:
    """Minimal algorithmic HBM traffic of ONE decode step: every
    NON-EMBEDDING parameter read once (the embedding tables are gathered,
    not streamed — a step touches B rows of the token table and one
    position row, not the ~154 MB table; counting it whole would inflate
    the roofline fraction the ≥0.4 acceptance gate judges), plus the
    cache-dtype-aware KV traffic of :func:`decode_cache_bytes_per_step`
    (full ``max_len`` read for the dense path; pass ``effective_len`` for
    the length-aware kernel so the denominator stays honest either way).
    Decode is bandwidth-bound — this is the roofline denominator
    ``benchmarks/bench_generate.py`` reports ``hbm_gb_per_s`` against.
    ``params`` may be arrays or the eval_shape tree (sizes/dtypes only).
    Leaf-driven by construction, so weight-only quantization needs no
    special case: hand it the ``ops.quant.quantize_params`` tree and the
    params term shrinks with the stored bytes — ~4x for int8 qkernels,
    ~8x for int4 packed two-per-byte (scales are d_out-sized noise)."""
    import numpy as np

    p_bytes = sum(
        leaf.size * np.dtype(leaf.dtype).itemsize
        for leaf in jax.tree.leaves(params)
    )
    from collections.abc import Mapping

    emb_bytes = gathered = 0.0
    if isinstance(params, Mapping):  # plain dict or flax FrozenDict alike
        for name, rows in (("tok_emb", batch_size), ("pos_emb", 1)):
            for leaf in jax.tree.leaves(params.get(name, {})):
                it = np.dtype(leaf.dtype).itemsize
                emb_bytes += leaf.size * it
                gathered += rows * leaf.shape[-1] * it
    cache = decode_cache_bytes_per_step(cfg, batch_size,
                                        effective_len=effective_len)
    return float(p_bytes - emb_bytes + gathered + cache)


def _sample(logits, key, temperature: float, top_k: int | None):
    """(B, V) logits -> (B,) int32 token ids. Branchless; greedy when
    temperature == 0 (exact argmax, not a limit).

    ``key`` is the POSITION-derived key ``fold_in(rng, position)`` — not a
    split chain. Deriving the key from the absolute sequence position makes
    the sampled stream a pure function of (rng, position, logits), which is
    what lets speculative decoding reproduce the vanilla stream exactly:
    the draft and the verifier sample position p with the SAME key, so a
    draft whose logits agree with the full model yields the same token
    (the Gumbel coupling behind the accept test), and every accepted token
    is bitwise the one vanilla decoding would have emitted."""
    if temperature == 0.0:
        return jnp.argmax(logits, -1).astype(jnp.int32)
    logits = logits / temperature
    if top_k is not None:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def sample_rows(logits, keys, temperature: float, top_k: int | None):
    """Per-row sampling: (B, V) logits + (B,) per-row position-derived
    keys -> (B,) int32 tokens, row b bitwise what a B=1 :func:`_sample`
    call would emit. This is the serve engine's sampler: continuous
    batching puts every slot at its own position with its own request
    rng, and ``vmap`` over the B=1 call is what makes each slot's stream
    identical to that request's one-shot ``make_generate_fn`` run — the
    engine-parity acceptance pin."""
    return jax.vmap(
        lambda row, key: _sample(row[None], key, temperature, top_k)[0]
    )(logits, keys)


def make_generate_fn(cfg: TransformerConfig, *, max_new_tokens: int,
                     temperature: float = 1.0, top_k: int | None = None,
                     donate_cache: bool = True, unroll: int = 1,
                     spec_draft_layers: int = 0, spec_lookahead: int = 4,
                     adapters=None, adapter_id: int = 0):
    """Build a jitted ``(params, prompt (B, P) int32, rng) -> (B, P + N)``
    generator. Compiles once per (B, P) shape; P + max_new_tokens (+ the
    speculative lookahead, when on) must fit ``cfg.max_len`` (checked
    eagerly per call).

    Decode-path knobs (the HBM-roofline levers — decode is bandwidth-bound:
    every step re-reads the params and the KV cache; ``cfg.kv_dtype`` and
    ``cfg.decode_impl`` attack the cache bytes, the knobs here attack the
    steps):

    * ``donate_cache`` (default True): the cache is allocated OUTSIDE the
      compiled program and donated into it, so XLA aliases the buffers and
      the per-step ``dynamic_update_slice`` writes land in place — no
      second live copy of ``layers x (B, H, max_len, hd) x 2`` in HBM.
      Safe by construction: each call allocates a fresh cache and nothing
      re-reads it after the call (donation-safety pinned in
      tests/test_generation.py, the buffer-reuse oracle pattern of
      tests/test_prefetch.py).
    * ``unroll``: ``lax.scan`` unroll factor for the decode loop — trades
      program size for per-token loop/dispatch overhead; parity is pinned
      (the unrolled loop is the same program repeated).
    * ``spec_draft_layers`` (K > 0 turns speculative decoding on): draft
      with the K-layer PREFIX of the same model — shared params (flax
      ignores the unused deeper blocks), its own small K-layer cache —
      then verify all ``spec_lookahead`` drafted tokens in ONE full-model
      forward (a (G+1)-token chunk through the same ``_decode_attend``
      path) and accept the longest matching prefix, batch-lockstep (the
      accept count is the min over rows, which keeps the cache write index
      a scalar and every shape static). Sampling keys derive from the
      absolute position (see ``_sample``), so the emitted stream is the
      vanilla stream exactly: every accepted token is the verifier's own
      token for that position, and on the first mismatch the verifier's
      token is emitted instead — greedy speculative output is pinned
      BITWISE-identical to vanilla greedy (it is a reordering of the same
      argmaxes; tests/test_generation.py pins the sampled mode too). The
      outer accept loop is a ``lax.while_loop`` (static shapes, dynamic
      trip count — no wasted verify passes after the budget is met);
      rejected slots hold stale k/v but are ALWAYS rewritten by the next
      draft/verify chunk before any later query can attend to them.
      Per-call acceptance stats land in ``generate.last_stats``.
    """
    dcfg = decode_config(cfg)
    model = Transformer(dcfg)
    sample = partial(_sample, temperature=temperature, top_k=top_k)
    spec = spec_draft_layers > 0
    if spec and not 0 < spec_draft_layers < cfg.num_layers:
        raise ValueError(
            f"spec_draft_layers {spec_draft_layers} must lie strictly "
            f"between 0 and num_layers {cfg.num_layers} (the draft is a "
            "proper prefix of the same model)")
    if spec and spec_lookahead < 1:
        raise ValueError(f"spec_lookahead {spec_lookahead} must be >= 1")
    if spec:
        draft_cfg = dataclasses.replace(cfg, num_layers=spec_draft_layers)
        draft_model = Transformer(decode_config(draft_cfg))
    # Multi-LoRA one-shot path (the serve engine's per-adapter oracle):
    # ``adapters`` is the bank tree ("adapters" collection) and
    # ``adapter_id`` selects one row for the whole batch. The bank is
    # closed over (a jit constant — the oracle serves parity tests, not
    # production traffic).
    lora = dcfg.lora_rank is not None
    if lora and adapters is None:
        raise ValueError(
            "cfg.lora_rank set: pass the adapters bank "
            "(serve.init_adapter_bank)")
    if not lora and adapters is not None:
        raise ValueError("adapters given but cfg.lora_rank is None")
    if lora and not 0 <= adapter_id <= cfg.lora_adapters:
        raise ValueError(
            f"adapter_id {adapter_id} out of range "
            f"[0, {cfg.lora_adapters}]")
    if lora and spec:
        raise ValueError("speculative decoding + LoRA is not supported")

    def _apply(params, cache, toks, idx):
        variables, ids = {"params": params, "cache": cache}, None
        if lora:
            variables["adapters"] = adapters
            ids = jnp.full((toks.shape[0],), adapter_id, jnp.int32)
        return model.apply(variables, toks, idx, adapter=ids,
                           mutable=["cache"])

    def _generate(params, prompt, cache, rng):
        B, P = prompt.shape
        # prefill: the whole prompt in one forward pass, cache filled
        logits, vs = _apply(params, cache, prompt, 0)
        tok = sample(logits[:, -1], jax.random.fold_in(rng, P))

        def body(carry, _):
            cache, tok, idx = carry
            logits, vs = _apply(params, cache, tok[:, None], idx)
            nxt = sample(logits[:, -1], jax.random.fold_in(rng, idx + 1))
            return (vs["cache"], nxt, idx + 1), tok

        (_, last, _), toks = lax.scan(
            body, (vs["cache"], tok, jnp.int32(P)), None,
            length=max_new_tokens - 1, unroll=unroll)
        new = jnp.concatenate([toks.T, last[:, None]], axis=1)  # (B, N)
        return jnp.concatenate([prompt, new], axis=1)

    def _generate_spec(params, prompt, cache, draft_cache, rng):
        B, P = prompt.shape
        G = spec_lookahead
        # prefill BOTH caches with the prompt; the first token comes from
        # the full model, exactly as in the vanilla path
        logits, vs = model.apply({"params": params, "cache": cache},
                                 prompt, 0, mutable=["cache"])
        cache = vs["cache"]
        _, dvs = draft_model.apply(
            {"params": params, "cache": draft_cache}, prompt, 0,
            mutable=["cache"])
        draft_cache = dvs["cache"]
        t0 = sample(logits[:, -1], jax.random.fold_in(rng, P))
        # emitted-token buffer, G slots of slack: one verify chunk may
        # emit up to G+1 tokens and the loop exits as soon as the budget
        # is met — overshoot is sliced off below
        buf = jnp.zeros((B, max_new_tokens + G), jnp.int32)
        buf = lax.dynamic_update_slice(buf, t0[:, None], (0, 0))

        def cond(carry):
            return carry[4] < max_new_tokens

        def body(carry):
            cache, draft_cache, buf, last, produced, steps, accepted = carry
            idx0 = P + produced - 1  # position of `last` (k/v unwritten)

            def draft_body(dc, _):
                draft_cache, tok, idx = dc
                dl, dvs = draft_model.apply(
                    {"params": params, "cache": draft_cache}, tok[:, None],
                    idx, mutable=["cache"])
                nxt = sample(dl[:, -1], jax.random.fold_in(rng, idx + 1))
                return (dvs["cache"], nxt, idx + 1), nxt

            # G+1 steps, last output discarded: the extra step exists to
            # WRITE the draft-cache slot of the final draft (position
            # idx0+G). Without it a fully-accepted round (m == G) jumps
            # past that slot forever and every later draft attends a
            # zero-initialized k/v hole — output would stay correct (the
            # verifier is authoritative) but the draft stream would drift
            # from the true K-layer model and acceptance would decay in
            # exactly the high-acceptance regime the lever exists for.
            (draft_cache, _, _), drafts = lax.scan(
                draft_body, (draft_cache, last, idx0), None, length=G + 1,
                unroll=unroll)
            drafts = jnp.moveaxis(drafts[:G], 0, 1)  # (B, G)
            # verify: one (G+1)-token chunk through the FULL model — its
            # row j scores position idx0+j+1; the same position-derived
            # key as the draft makes the accept test a pure token match
            chunk = jnp.concatenate([last[:, None], drafts], axis=1)
            vl, vvs = model.apply({"params": params, "cache": cache},
                                  chunk, idx0, mutable=["cache"])
            cache = vvs["cache"]
            verified = jnp.stack(
                [sample(vl[:, j], jax.random.fold_in(rng, idx0 + 1 + j))
                 for j in range(G + 1)], axis=1)  # (B, G+1)
            matches = (verified[:, :G] == drafts).astype(jnp.int32)
            # longest accepted prefix per row, then batch-lockstep min so
            # the cache index stays a scalar
            m = jnp.min(jnp.sum(jnp.cumprod(matches, axis=1), axis=1))
            # emit the verifier's tokens 0..m: positions j < m equal the
            # drafts (that is what accepted means) and position m is the
            # verifier's correction/bonus — all are exactly what vanilla
            # decode would emit. Columns past m are garbage conditioned on
            # rejected drafts; they are overwritten by the next chunk
            # before the slice below can see them.
            buf = lax.dynamic_update_slice(buf, verified, (0, produced))
            last = lax.dynamic_index_in_dim(verified, m, axis=1,
                                            keepdims=False)
            return (cache, draft_cache, buf, last, produced + m + 1,
                    steps + 1, accepted + m)

        init = (cache, draft_cache, buf, t0, jnp.int32(1), jnp.int32(0),
                jnp.int32(0))
        _, _, buf, _, produced, steps, accepted = lax.while_loop(
            cond, body, init)
        out = jnp.concatenate([prompt, buf[:, :max_new_tokens]], axis=1)
        return out, steps, accepted

    # Donation is a no-op the CPU backend additionally WARNS about
    # ("donated buffers were not usable"), so the knob is gated off there
    # — the fresh-cache-per-call safety contract is backend-independent
    # and stays tested either way.
    donate = donate_cache and jax.default_backend() != "cpu"
    if spec:
        jitted = jax.jit(_generate_spec,
                         donate_argnums=(2, 3) if donate else ())
    else:
        jitted = jax.jit(_generate, donate_argnums=(2,) if donate else ())

    # The cache SHAPE tree is a full Flax module trace — far too expensive
    # to re-derive inside the per-call serving path (it would sit in every
    # bench's timed loop); memoize it per batch size and only the zeros
    # allocation happens per call (fresh buffers are what donation safety
    # rests on).
    @lru_cache(maxsize=8)
    def _cache_shapes(batch_size: int):
        return cache_shapes(cfg, batch_size)

    @lru_cache(maxsize=8)
    def _draft_cache_shapes(batch_size: int):
        return cache_shapes(draft_cfg, batch_size)

    def _fresh(shapes):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

    def generate(params, prompt, rng):
        B, P = prompt.shape
        budget = max_new_tokens + (spec_lookahead if spec else 0)
        if P + budget > dcfg.max_len:
            raise ValueError(
                f"prompt {P} + max_new_tokens {max_new_tokens}"
                + (f" + spec_lookahead {spec_lookahead}" if spec else "")
                + f" exceeds max_len {dcfg.max_len}")
        cache = _fresh(_cache_shapes(B))
        if not spec:
            return jitted(params, prompt, cache, rng)
        draft_cache = _fresh(_draft_cache_shapes(B))
        out, steps, accepted = jitted(params, prompt, cache, draft_cache,
                                      rng)
        # raw device scalars — reading them synchronizes, so benches fetch
        # AFTER the timed region
        generate.last_stats = {"verify_steps": steps,
                               "accepted_drafts": accepted}
        return out

    # introspection for tests/benches: whether the compiled program
    # actually aliases the cache argument (False on the CPU backend)
    generate.donates_cache = donate
    generate.last_stats = None
    # static-analysis hooks (analysis/): the compiled entry itself and the
    # donation INTENT — what a TPU run donates, even where the cpu gate
    # turned actual donation off (the lint audits the intent's soundness)
    generate.jitted = jitted
    generate.declared_donate_argnums = (2, 3) if spec else (2,)
    return generate


# ---- program contracts (analysis/) ------------------------------------------


def lint_contracts():
    """Contracts for the decode entry programs (vanilla + speculative).

    The serve path must be collective-free (it runs single-device or
    replicated; a stray psum here would deadlock a sharded server),
    host-callback-free (determinism + no per-token host round-trips),
    and its declared cache donation must be *scratch*-sound: the program
    returns only tokens, so the cache can never alias an output — the
    donation exists to let XLA reuse the buffer in place — and the lint
    checks the cache is read exactly once at top level instead."""
    from distributed_tensorflow_guide_tpu.analysis.contracts import (
        CostSpec,
        DonationSpec,
        ProgramContract,
    )

    def build(spec_layers):
        def _build():
            import jax

            from distributed_tensorflow_guide_tpu.analysis.fixtures import (
                tiny_lm_cfg,
            )

            cfg = tiny_lm_cfg(max_len=32)
            gen = make_generate_fn(
                cfg, max_new_tokens=4, spec_draft_layers=spec_layers,
                spec_lookahead=2 if spec_layers else 4)
            B, P = 2, 8
            prompt = jax.ShapeDtypeStruct((B, P), "int32")
            model = Transformer(decode_config(cfg))
            params = jax.eval_shape(
                lambda p: model.init(jax.random.PRNGKey(0), p, 0),
                prompt)["params"]
            cache = _jax_sds_tree(cache_shapes(cfg, B))
            rng = jax.random.PRNGKey(0)
            args = [params, prompt, cache, rng]
            if spec_layers:
                dcfg = dataclasses.replace(cfg, num_layers=spec_layers)
                args.insert(3, _jax_sds_tree(cache_shapes(dcfg, B)))
            return gen.jitted, tuple(args)

        return _build

    def _jax_sds_tree(tree):
        import jax

        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), tree)

    common = dict(
        policy="f32",
        collectives={},  # strict: the serve path is collective-free
        sources=("distributed_tensorflow_guide_tpu.models.generation",
                 "distributed_tensorflow_guide_tpu.models.transformer"),
    )
    return [
        ProgramContract(
            name="decode_step",
            build=build(0),
            donation=DonationSpec(argnums=(2,), mode="scratch"),
            # 123,596 observed: params + donated KV cache dominate; a
            # regression that holds a second cache copy live doubles this
            cost=CostSpec(max_peak_live_bytes=131072),
            notes="vanilla scan decode: cache donated as scratch",
            **common),
        ProgramContract(
            name="decode_spec_step",
            build=build(1),
            donation=DonationSpec(argnums=(2, 3), mode="scratch"),
            cost=CostSpec(max_peak_live_bytes=196608),
            notes="self-speculative decode (while_loop body audited too)",
            **common),
    ]
