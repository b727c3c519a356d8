#!/usr/bin/env python
"""Chip smoke: the trainer and the serving engine, once, on the TPU.

    python chip_smoke.py            # no arguments, one process

Drives the library surface the examples use (examples/gpt2_serve.py is the
pattern: train with ``DataParallel`` through ``TrainLoop``, hand the params
to ``ServeEngine``) at the full width and depth of
``gpt2_124m(dtype=bfloat16)`` with weights made from a seed, and checks what
comes out by the repo's own means:

* device:  a TPU, alone on it (a bf16 matmul chain lands near the table's
           peak), and ``block_until_ready`` fences;
* kernels: every Pallas kernel, compiled, against its XLA reference;
* patterned: the routed feed-forward, the short convolution through its
           state and the paged kernel under grouped heads, at LFM2's
           published widths, against float32 XLA;
* train:   1 + 5 ``TrainLoop`` steps, loss finite and falling, with the
           flash kernels, the fused cross-entropy and donation compiled in;
* serve:   a dozen staggered requests through ``ServeEngine``, all ``ok``,
           nothing retried, nothing leaked, nothing fell back, and greedy
           streams that agree with the one-shot dense decoder.

On a four-chip host the same phases use all four: data-parallel training
over four devices and a four-replica ``FleetScheduler``, one per chip.

Exit 0 means all of it ran on a TPU; the last line of stdout is then
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
No phase is wrapped in ``try``: the first exception ends the run. Without a
TPU it exits non-zero at once and prints no result.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import json
import sys
import time

# max |got - ref| over max |ref|, reference in f32 at full matmul precision
# from the same bf16/int8 operands. bf16 carries 8 bits of mantissa and the
# kernels round their output to it, so 2e-2 of full scale is ~5 ulp of room.
KERNEL_TOL = 2e-2

# A greedy stream may leave the one-shot decoder's only where the
# reference's two best logits are closer than this: bf16 activations put
# ~1e-2 of noise on a logit, and a wider gap must not flip.
TIE_MARGIN = 0.05


def say(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


class CompileLog:
    """Seconds spent compiling and persistent-cache traffic, from JAX's own
    monitoring events (a cache hit still passes through backend_compile)."""

    def __init__(self) -> None:
        import jax

        self.secs: dict[str, float] = collections.defaultdict(float)
        self.events: collections.Counter = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._secs)
        jax.monitoring.register_event_listener(self._event)

    def _secs(self, name: str, secs: float, **_) -> None:
        self.secs[name.rsplit("/", 1)[-1]] += secs

    def _event(self, name: str, **_) -> None:
        self.events[name.rsplit("/", 1)[-1]] += 1

    def facts(self) -> dict:
        s, e = self.secs, self.events
        return {
            "trace_lower_s": round(s["jaxpr_trace_duration"]
                                   + s["jaxpr_to_mlir_module_duration"], 1),
            "backend_compile_s": round(s["backend_compile_duration"], 1),
            "cache_hits": e["cache_hits"],
            "cache_misses": e["cache_misses"],
            "cache_retrieval_s": round(s["cache_retrieval_time_sec"], 1),
        }


def rel_err(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    assert np.isfinite(got).all(), "kernel output not finite"
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def has_pallas_call(lowered_text: str) -> bool:
    return "tpu_custom_call" in lowered_text


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def phase_device(devices, *, n: int = 8192, chain: int = 16) -> dict:
    import importlib.metadata as md

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_guide_tpu.core.device import (
        device_fields,
        peaks_for,
    )

    dev = devices[0]
    peak = peaks_for(dev.device_kind).bf16_flops
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (n, n), jnp.float32).astype(jnp.bfloat16)
    w = (jax.random.normal(kw, (n, n), jnp.float32) / n ** 0.5).astype(
        jnp.bfloat16)

    @jax.jit
    def matmuls(x, w):
        for _ in range(chain):
            x = x @ w
        return x

    matmuls(x, w).block_until_ready()  # compile + warm

    def best_of(close) -> float:
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            close(matmuls(x, w))
            best = min(best, time.perf_counter() - t0)
        return best

    t_bur = best_of(lambda y: y.block_until_ready())
    # the same work closed by fetching a value that depends on all of it
    t_fetch = best_of(lambda y: np.asarray(y[:1, :1]))
    flops = 2.0 * n ** 3 * chain
    rate = flops / t_bur
    facts = {
        **device_fields(),
        "jax": jax.__version__, "jaxlib": md.version("jaxlib"),
        "libtpu": md.version("libtpu"),
        "matmul": f"{chain} x bf16 {n}^3",
        "block_until_ready_s": round(t_bur, 4),
        "value_fetch_s": round(t_fetch, 4),
        "tflops_block_until_ready": round(rate / 1e12, 1),
        "peak_tflops": round(peak / 1e12, 1),
        "share_of_peak": round(rate / peak, 3),
    }
    say("device", **facts)
    # Above the peak, block_until_ready returned before the work was done;
    # far below it, something else is on the chip.
    assert 0.6 * peak < rate <= peak, facts
    assert t_bur > 0.9 * t_fetch, (
        "block_until_ready returned well before a dependent value fetch", facts)
    return facts


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def decode_reference(q, keys, vals, q_pos, k_scale=None, v_scale=None):
    """The dense decode read of models/transformer.py
    (``_dense_cache_read``), stated again in f32: q (B, C, H, hd),
    keys/vals (B, H, S, hd), q_pos (B, C) absolute positions, scales
    (B, H, 1, S) when the cache is int8."""
    import jax
    import jax.numpy as jnp

    hd = q.shape[-1]
    scores = jnp.einsum("bqhd,bhkd->bhqk", q.astype(jnp.float32),
                        keys.astype(jnp.float32)) / hd ** 0.5
    if k_scale is not None:
        scores = scores * k_scale
    mask = jnp.arange(keys.shape[2])[None, None, :] <= q_pos[:, :, None]
    scores = jnp.where(mask[:, None], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, -1)
    if v_scale is not None:
        probs = probs * v_scale
    return jnp.einsum("bhqk,bhkd->bqhd", probs, vals.astype(jnp.float32))


def phase_kernels(*, heads: int = 12, head_dim: int = 64, seq: int = 1024,
                  flash_batch: int = 2, decode_batch: int = 8,
                  block_size: int = 16, prefill_chunk: int = 128) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_guide_tpu.ops import decode_attention as DA
    from distributed_tensorflow_guide_tpu.ops import flash_attention as FA
    from distributed_tensorflow_guide_tpu.ops.attention import dense_attention
    from distributed_tensorflow_guide_tpu.serve.paged_cache import (
        gather_view,
        write_chunk,
    )

    assert not FA._interpret(), "Pallas kernels would run in interpret mode"
    errs: dict[str, float] = {}
    f32 = jnp.float32

    def reference(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    # -- flash forward and gradients against ops/attention.py --------------
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(1), 4)
    shape = (flash_batch, seq, heads, head_dim)
    q, k, v, g = (jax.random.normal(key, shape, f32).astype(jnp.bfloat16)
                  for key in (kq, kk, kv, kg))

    def flash(q, k, v):
        return FA.flash_attention(q, k, v, causal=True)

    def dense(q, k, v):
        return dense_attention(q.astype(f32), k.astype(f32), v.astype(f32),
                               causal=True)

    def vjp(fn):
        return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v).astype(f32)
                                                 * g.astype(f32)),
                        argnums=(0, 1, 2))

    flash_jit = jax.jit(flash)
    assert has_pallas_call(flash_jit.lower(q, k, v).as_text())
    errs["flash_fwd"] = rel_err(flash_jit(q, k, v), reference(dense, q, k, v))
    got = jax.jit(vjp(flash))(q, k, v)
    ref = reference(vjp(dense), q, k, v)
    for name, a, b in zip(("flash_dq", "flash_dk", "flash_dv"), got, ref):
        errs[name] = rel_err(a, b)

    # -- decode attention, contiguous cache --------------------------------
    B, H, hd, S = decode_batch, heads, head_dim, seq
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
    q1 = jax.random.normal(kq, (B, 1, H, hd), f32).astype(jnp.bfloat16)
    kf = jax.random.normal(kk, (B, H, S, hd), f32)
    vf = jax.random.normal(kv, (B, H, S, hd), f32)
    index = S * 2 // 3  # a partly written cache: dead blocks are skipped
    q_pos = jnp.full((B, 1), index, jnp.int32)

    kb, vb = kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16)
    fn = jax.jit(lambda q, k, v: DA.decode_attention(q, k, v, index))
    assert has_pallas_call(fn.lower(q1, kb, vb).as_text())
    errs["decode_bf16"] = rel_err(
        fn(q1, kb, vb), reference(decode_reference, q1, kb, vb, q_pos))

    k8, ks = DA.quantize_kv(kf)
    v8, vs = DA.quantize_kv(vf)
    ks, vs = ks[:, :, None, :], vs[:, :, None, :]
    fn = jax.jit(lambda q, k, v, ks, vs: DA.decode_attention(
        q, k, v, index, key_scale=ks, value_scale=vs))
    errs["decode_int8"] = rel_err(
        fn(q1, k8, v8, ks, vs),
        reference(decode_reference, q1, k8, v8, q_pos, ks, vs))

    # -- decode attention, paged pool: a decode step and a prefill chunk ---
    # The pool has one layout, (num_blocks, H, hd, block_size): a block's
    # slots on the lane axis. With a head dim under 128 the device keeps
    # such an array that way whatever shape it is declared with (a minor
    # axis of 64 fills half of an (8, 128) tile), so a pool declared
    # (.., block_size, hd) was relaid out, whole, for every write and every
    # kernel call (PERF.md, PR 29). Drawn as rows of hd values, which is
    # what quantize_kv scales, and turned once.
    def as_pool(x):
        return jnp.swapaxes(x, 2, 3)

    n_blk = S // block_size
    num_blocks = B * n_blk + 1  # + the trash block
    kk, kv, kq, kc = jax.random.split(jax.random.PRNGKey(3), 4)
    kpool = jax.random.normal(kk, (num_blocks, H, block_size, hd), f32)
    vpool = jax.random.normal(kv, (num_blocks, H, block_size, hd), f32)
    tables = jnp.asarray(np.random.RandomState(0).permutation(
        B * n_blk).reshape(B, n_blk).astype(np.int32))
    # every request at its own length, block edges and both ends included
    lengths = jnp.asarray(np.linspace(1, S, B).astype(np.int32))
    qc = jax.random.normal(kc, (1, prefill_chunk, H, hd), f32).astype(
        jnp.bfloat16)
    chunk_len = jnp.asarray([S // 2 + prefill_chunk], jnp.int32)
    k8p, ksp = DA.quantize_kv(kpool)
    v8p, vsp = DA.quantize_kv(vpool)
    ksp, vsp = ksp[:, :, None, :], vsp[:, :, None, :]

    def paged(q, kp, vp, tab, lens, ksp=None, vsp=None):
        return DA.paged_decode_attention(
            q, kp, vp, tab, lens, key_scale_pool=ksp, value_scale_pool=vsp,
            block_size=block_size)

    def paged_ref(q, kp, vp, tab, lens, ksp=None, vsp=None):
        C = q.shape[1]
        q_pos = (lens - C)[:, None] + jnp.arange(C)[None, :]
        scales = () if ksp is None else (gather_view(ksp, tab),
                                         gather_view(vsp, tab))
        return decode_reference(
            q, as_pool(gather_view(kp, tab)),
            as_pool(gather_view(vp, tab)), q_pos, *scales)

    paged_jit = jax.jit(paged)
    pools = {"bf16": (as_pool(kpool.astype(jnp.bfloat16)),
                      as_pool(vpool.astype(jnp.bfloat16))),
             "int8": (as_pool(k8p), as_pool(v8p), ksp, vsp)}
    for dtype, pool in pools.items():
        kp, vp, *sc = pool
        for name, (qq, tab, lens) in {
                "decode": (q1, tables, lengths),
                "chunk": (qc, tables[:1], chunk_len)}.items():
            args = (qq, kp, vp, tab, lens, *sc)
            assert has_pallas_call(paged_jit.lower(*args).as_text())
            errs[f"paged_{name}_{dtype}"] = rel_err(
                paged_jit(*args), reference(paged_ref, *args))

    # -- the write into that pool: the Pallas form against the loop --------
    # a decode step's one slot a row, and a chunk that starts inside a
    # block; every block but the trash block the same to the bit
    for name, rows, starts in (
            ("decode", B, lengths - 1),
            ("chunk", 1, jnp.asarray([S // 2 + 3], jnp.int32))):
        new = jax.random.normal(
            jax.random.PRNGKey(4), (rows, H, hd, 1 if name == "decode"
                                    else prefill_chunk), f32
        ).astype(jnp.bfloat16)
        loop, kernel = (jax.jit(functools.partial(
            write_chunk, block_size=block_size, kernel=k))(
                pools["bf16"][0], new, tables[:rows], starts)
            for k in (False, True))
        errs[f"paged_write_{name}"] = float(
            jnp.any(loop[:-1] != kernel[:-1]))
        assert bool(jnp.any(loop != pools["bf16"][0]))

    facts = {"interpret": False, "tolerance": KERNEL_TOL,
             "rel_err": {k: round(v, 5) for k, v in errs.items()}}
    say("kernels", **facts)
    bad = {k: v for k, v in errs.items() if not v <= KERNEL_TOL}
    assert not bad, f"kernels off their XLA reference: {bad}"
    return facts


def phase_patterned(*, d: int = 2048, ff: int = 1536, experts: int = 64,
                    top_k: int = 4, rows: int = 256, heads: int = 32,
                    kv_heads: int = 8, head_dim: int = 64, seq: int = 1024,
                    block_size: int = 128, chunk: int = 128) -> dict:
    """What a patterned model adds (PR 28), each in bfloat16 against float32
    XLA at full product precision from the same bfloat16 numbers, at LFM2's
    published widths: the routed feed-forward (sorted grouped products
    against every expert computed for every row and masked), the
    short-convolution mixer in two chunks through its state leaf against
    one pass over the whole sequence, and the paged kernel with 4 query
    heads a pool head against the pool's heads copied."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from distributed_tensorflow_guide_tpu.models.transformer import (
        ShortConv,
        TransformerConfig,
    )
    from distributed_tensorflow_guide_tpu.ops import decode_attention as DA
    from distributed_tensorflow_guide_tpu.ops.routed_ffn import (
        route,
        routed_ffn,
    )
    from distributed_tensorflow_guide_tpu.serve.paged_cache import gather_view

    errs: dict[str, float] = {}
    f32, bf16 = jnp.float32, jnp.bfloat16

    def reference(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    # -- the routed layer ----------------------------------------------------
    keys = jax.random.split(jax.random.PRNGKey(4), 7)
    x = jax.random.normal(keys[0], (rows, d), f32).astype(bf16)
    router = 0.02 * jax.random.normal(keys[1], (d, experts), f32)
    bias = 0.01 * jax.random.normal(keys[2], (experts,), f32)
    w_gate, w_up = (0.02 * jax.random.normal(k, (experts, d, ff), f32).astype(
        bf16) for k in keys[3:5])
    w_down = 0.02 * jax.random.normal(keys[5], (experts, ff, d), f32).astype(
        bf16)

    def every_expert(x, router, bias, w_gate, w_up, w_down):
        chosen, weights = route(x, router, bias, top_k=top_k)
        mask = jnp.zeros((rows, experts), f32).at[
            jnp.arange(rows)[:, None], chosen].set(weights)
        xf = x.astype(f32)

        def one(y, expert):
            g, u, dn, w = expert
            h = jax.nn.silu(xf @ g.astype(f32)) * (xf @ u.astype(f32))
            return y + w[:, None] * (h @ dn.astype(f32)), None

        return lax.scan(one, jnp.zeros((rows, d), f32),
                        (w_gate, w_up, w_down, mask.T))[0]

    routed = jax.jit(lambda *a: routed_ffn(*a, top_k=top_k))
    args = (x, router, bias, w_gate, w_up, w_down)
    if jax.default_backend() == "tpu":  # one native grouped product each
        assert "ragged-dot" in routed.lower(*args).compile().as_text()
    y, load = routed(*args)
    assert int(load.sum()) == rows * top_k  # dropless
    errs["routed_ffn"] = rel_err(y, reference(every_expert, *args))

    # -- the short convolution through its state -----------------------------
    cfg = TransformerConfig(
        vocab_size=128, num_layers=1, num_heads=heads, d_model=d, d_ff=ff,
        max_len=seq, dtype=bf16, layers=(("short_conv", "dense"),),
        norm="rmsnorm", ffn_gate="silu", rope_theta=1e6,
        num_kv_heads=kv_heads)
    whole = ShortConv(dataclasses.replace(cfg, dtype=f32))
    paged = ShortConv(dataclasses.replace(
        cfg, decode=True, paged_num_blocks=2, paged_block_size=block_size))
    xs = jax.random.normal(keys[6], (1, 2 * chunk, d), f32).astype(bf16)
    params = jax.jit(whole.init)(jax.random.PRNGKey(5), xs)["params"]
    params = jax.tree.map(lambda p: p.astype(bf16).astype(f32), params)
    slot = jnp.asarray([2], jnp.int32)
    state = {"conv": jnp.ones((4, cfg.conv_kernel - 1, d), bf16)}

    @jax.jit
    def in_chunks(params, xs, state):
        outs = []
        for i in range(2):  # the second chunk reads the first one's state
            out, mut = paged.apply(
                {"params": params, "state": state},
                xs[:, i * chunk:(i + 1) * chunk],
                jnp.asarray([i * chunk], jnp.int32), state_rows=slot,
                valid=jnp.asarray([chunk], jnp.int32), mutable=["state"])
            state = mut["state"]
            outs.append(out)
        return jnp.concatenate(outs, axis=1)

    errs["short_conv"] = rel_err(
        in_chunks(params, xs, state),
        reference(lambda p, x: whole.apply({"params": p}, x.astype(f32)),
                  params, xs))

    # -- the paged kernel, 4 query heads a pool head -------------------------
    B, n_blk = 8, seq // block_size
    kk, kv, kq = jax.random.split(jax.random.PRNGKey(6), 3)
    shape = (B * n_blk + 1, kv_heads, head_dim, block_size)  # pool layout
    kpool = jax.random.normal(kk, shape, f32).astype(bf16)
    vpool = jax.random.normal(kv, shape, f32).astype(bf16)
    q = jax.random.normal(kq, (B, 1, heads, head_dim), f32).astype(bf16)
    tables = jnp.asarray(np.random.RandomState(1).permutation(
        B * n_blk).reshape(B, n_blk).astype(np.int32))
    lengths = jnp.asarray(np.linspace(1, seq, B).astype(np.int32))
    grouped = jax.jit(lambda q, kp, vp: DA.paged_decode_attention(
        q, kp, vp, tables, lengths, block_size=block_size))
    assert has_pallas_call(grouped.lower(q, kpool, vpool).as_text())

    def copied(q, kp, vp):
        group = heads // kv_heads
        return decode_reference(q, *(
            jnp.swapaxes(gather_view(jnp.repeat(p, group, 1), tables), 2, 3)
            for p in (kp, vp)), (lengths - 1)[:, None])

    errs["paged_decode_grouped"] = rel_err(
        grouped(q, kpool, vpool), reference(copied, q, kpool, vpool))

    facts = {"tolerance": KERNEL_TOL,
             "rel_err": {k: round(v, 5) for k, v in errs.items()}}
    say("patterned", **facts)
    bad = {k: v for k, v in errs.items() if not v <= KERNEL_TOL}
    assert not bad, f"a patterned model's parts off float32 XLA: {bad}"
    return facts


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def motif_tokens(rng, rows: int, length: int, vocab: int, motif: int = 32):
    """Seeded low-entropy text: each row tiles its own random motif, so a
    few steps of training have something to learn and greedy decoding has
    something to say."""
    import numpy as np

    motifs = rng.randint(0, vocab, (rows, motif))
    return np.tile(motifs, (1, -(-length // motif)))[:, :length].astype(
        np.int32)


def phase_train(cfg, devices, *, per_chip_batch: int = 8, steps: int = 5,
                lr: float = 6e-4):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax.training import train_state

    from distributed_tensorflow_guide_tpu.core.mesh import (
        MeshSpec,
        build_mesh,
    )
    from distributed_tensorflow_guide_tpu.models.transformer import (
        Transformer,
        make_lm_loss_fn,
    )
    from distributed_tensorflow_guide_tpu.ops.fused_ce import resolve_fused_ce
    from distributed_tensorflow_guide_tpu.parallel.data_parallel import (
        DataParallel,
    )
    from distributed_tensorflow_guide_tpu.train.hooks import (
        BaseHook,
        StopAtStepHook,
    )
    from distributed_tensorflow_guide_tpu.train.loop import TrainLoop

    mesh = build_mesh(MeshSpec(data=-1))
    dp = DataParallel(mesh)
    model = Transformer(cfg)
    tokens = motif_tokens(np.random.RandomState(0),
                          per_chip_batch * len(devices), cfg.max_len,
                          cfg.vocab_size)

    @jax.jit
    def init(key):
        params = model.init(
            key, jnp.zeros((1, cfg.max_len), jnp.int32))["params"]
        return train_state.TrainState.create(
            apply_fn=model.apply, params=params, tx=optax.adamw(lr))

    state = dp.replicate(init(jax.random.PRNGKey(0)))
    batch = dp.shard_batch({"tokens": tokens})
    step = dp.make_train_step(make_lm_loss_fn(model))
    lowered = step.lower(state, batch).as_text()
    compiled_in = {
        "flash": (cfg.resolve_attn_impl(cfg.max_len) == "flash"
                  and has_pallas_call(lowered)),
        # the fused loss never builds the (tokens, vocab) logits
        "fused_ce": (resolve_fused_ce("auto", vocab_size=cfg.vocab_size)
                     and f"x{cfg.vocab_size}xf32>" not in lowered.replace(
                         f"{cfg.d_model}x{cfg.vocab_size}xf32>", "")),
        "donation": ("jax.buffer_donor" in lowered
                     or "tf.aliasing_output" in lowered),
    }

    class Losses(BaseHook):
        def __init__(self):
            self.values: list[float] = []

        def after_step(self, step, metrics):
            self.values.append(float(metrics["loss"]))

    losses = Losses()
    donor = jax.tree.leaves(state.params)[0]
    state = TrainLoop(step, state, itertools.repeat(batch),
                      hooks=[losses, StopAtStepHook(1 + steps)]).run()
    facts = {
        "model": f"gpt2 {cfg.num_layers}L d{cfg.d_model} h{cfg.num_heads} "
                 f"v{cfg.vocab_size} {jnp.dtype(cfg.dtype).name}",
        "global_batch": list(tokens.shape),
        "losses": [round(x, 4) for x in losses.values],
        "compiled_in": compiled_in,
        "donated_input_deleted": donor.is_deleted(),
        "params_devices": sorted(
            d.id for d in jax.tree.leaves(state.params)[0].devices()),
        "batch_devices": sorted(d.id for d in batch["tokens"].devices()),
    }
    say("train", **facts)
    assert len(losses.values) == 1 + steps
    assert all(np.isfinite(losses.values)), facts
    assert losses.values[-1] < losses.values[0], facts
    assert all(compiled_in.values()) and donor.is_deleted(), facts
    assert len(facts["params_devices"]) == len(devices), facts
    assert len(facts["batch_devices"]) == len(devices), facts
    return state, tokens


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def make_requests(tokens, n: int, max_len: int):
    """Seeded requests cut from the training text: prompts of 32-512
    tokens, 32-64 new tokens, arrivals staggered so that later ones are
    admitted while earlier ones decode. Requests 0 and 1 share a shape (one
    oracle compile checks both)."""
    import jax
    import numpy as np

    from distributed_tensorflow_guide_tpu.serve.engine import Request

    rng = np.random.RandomState(1)
    reqs = []
    for rid in range(n):
        plen, new = ((128, 32) if rid < 2 else
                     (int(rng.randint(32, 513)), int(rng.randint(32, 65))))
        plen = min(plen, max_len - new)
        reqs.append(Request(
            rid=rid, prompt=tokens[rid % len(tokens), :plen],
            max_new_tokens=new, rng=jax.random.PRNGKey(100 + rid),
            arrival=0.1 * rid))
    return reqs


def drive(eng, fleet: bool) -> list:
    """The serving loop of examples/gpt2_serve.py: one tick per launch on a
    demo clock, idle ticks skip to the next arrival."""
    events, now = [], 0.0
    while (eng._has_work() if fleet
           else eng.sched.has_queued or eng.sched.has_resident):
        evs, kind = eng.step(now)
        if fleet and eng.first_fault is not None:
            raise eng.first_fault  # the breaker would recover and go on
        events.extend(evs)
        if kind == "idle":
            nxt = eng.next_arrival() if fleet else eng.sched.next_arrival()
            if nxt is None:
                raise RuntimeError("serving deadlock: work left, none due")
            now = max(now, nxt)
        else:
            now += 0.01
    return events


def first_divergence(cfg, params, prompt, got, want) -> dict | None:
    """Where two greedy streams part, with the reference's top-2 logit
    margin there (a plain dense forward over the common prefix)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_guide_tpu.models.transformer import (
        Transformer,
    )

    at = next((i for i, (a, b) in enumerate(zip(got, want)) if a != b), None)
    if at is None:
        return None
    prefix = np.concatenate([prompt, np.asarray(want[:at], np.int32)])
    padded = np.zeros((1, cfg.max_len), np.int32)
    padded[0, :len(prefix)] = prefix
    model = Transformer(dataclasses.replace(cfg, attn_impl="dense"))
    logits = jax.jit(model.apply)({"params": params}, jnp.asarray(padded))
    top2 = np.sort(np.asarray(logits[0, len(prefix) - 1], np.float32))[-2:]
    return {"at": at, "engine": int(got[at]), "one_shot": int(want[at]),
            "top2_margin": round(float(top2[1] - top2[0]), 4)}


def phase_serve(cfg, state, tokens, devices, *, slots: int = 8,
                block_size: int = 16, prefill_chunk: int = 128,
                requests: int = 12) -> dict:
    import jax
    import numpy as np

    from distributed_tensorflow_guide_tpu.models.generation import (
        make_generate_fn,
    )
    from distributed_tensorflow_guide_tpu.ops.flash_attention import (
        fallback_stats,
    )
    from distributed_tensorflow_guide_tpu.serve.engine import ServeEngine
    from distributed_tensorflow_guide_tpu.serve.fleet import FleetScheduler

    fleet = len(devices) > 1
    # every slot can hold a full-length sequence, plus the trash block
    num_blocks = slots * (cfg.max_len // block_size) + 1
    geometry = dict(slots=slots, num_blocks=num_blocks,
                    block_size=block_size, prefill_chunk=prefill_chunk,
                    temperature=0.0, top_k=None)
    if fleet:  # built the way `dtg-serve --fleet N` builds it
        eng = FleetScheduler(cfg, state.params, replicas=len(devices),
                             **geometry)
        engines = eng.engines
        requests *= 2  # enough arrivals for every replica to get some
    else:
        eng = ServeEngine(cfg, state.params, **geometry)
        engines = [eng]
    reqs = make_requests(tokens, requests, cfg.max_len)
    for r in reqs:
        eng.submit(r)
    events = drive(eng, fleet)

    # the decode program, as the engine built it, holds the Pallas kernel
    e0 = engines[0]
    S, n_blk = slots, e0.fns.n_blk
    decode_text = e0.fns.decode.lower(
        e0.params, e0.pool, np.zeros((S, n_blk), np.int32),
        np.zeros((S,), np.int32), np.zeros((S,), np.int32),
        np.zeros((S, 2), np.uint32)).as_text()

    done = {e.rid for e in events if e.done and e.status == "ok"}
    completions = eng.completions()
    health = eng.health()
    # requests whose first token came while another stream was mid-decode
    span = {r.rid: [None, None] for r in reqs}
    for e in events:
        if e.status == "ok":
            span[e.rid][0] = e.time if e.first else span[e.rid][0]
            span[e.rid][1] = e.time if e.done else span[e.rid][1]
    mid_flight = sum(
        any(a < span[r][0] < b for o, (a, b) in span.items() if o != r)
        for r in span)
    facts = {
        "engine": f"fleet x{len(engines)}" if fleet else "single",
        "geometry": {k: geometry[k] for k in
                     ("slots", "num_blocks", "block_size", "prefill_chunk")},
        "requests": len(reqs), "ok": len(done),
        "tokens": sum(len(t) for t in completions.values()),
        "admitted_mid_flight": mid_flight,
        "launch_failures": health["launch_failures"],
        "decode_has_pallas_call": has_pallas_call(decode_text),
        "donates_pool": e0.fns.donates_pool,
        "fallbacks": {str(k): v for k, v in fallback_stats().items()},
        "pool_devices": [sorted(d.id for d in jax.tree.leaves(
            e.pool)[0].devices()) for e in engines],
        "completed_per_replica": [e.health()["completed"] for e in engines],
        "bytes_in_use": [d.memory_stats()["bytes_in_use"] for d in devices],
    }
    if fleet:
        facts["fleet_counters"] = {k: health[k] for k in (
            "replica_faults", "replica_crashes", "replica_stalls",
            "breaker_ejections", "breaker_probes", "shed")}
    else:
        facts["steps"] = dict(eng.steps)

    # two streams against the one-shot decoder on the dense XLA path
    oracle_params = jax.device_put(state.params, devices[0])
    gen = make_generate_fn(dataclasses.replace(cfg, decode_impl="dense"),
                           max_new_tokens=reqs[0].max_new_tokens,
                           temperature=0.0, top_k=None)
    facts["vs_one_shot_dense"] = []
    for r in reqs[:2]:
        prompt = np.asarray(r.prompt, np.int32)
        want = np.asarray(gen(oracle_params, prompt[None],
                              jax.random.PRNGKey(0)))[0, len(prompt):]
        facts["vs_one_shot_dense"].append(
            first_divergence(cfg, oracle_params, prompt,
                             completions[r.rid], want.tolist())
            or "identical")
    say("serve", **facts)

    assert done == {r.rid for r in reqs}, facts
    assert all(len(completions[r.rid]) == r.max_new_tokens for r in reqs)
    assert mid_flight > 0, facts
    assert facts["launch_failures"] == 0 and not facts["fallbacks"], facts
    assert facts["decode_has_pallas_call"] and facts["donates_pool"], facts
    for e in engines:
        e.sched.pool.check_leaks()
    assert len({tuple(p) for p in facts["pool_devices"]}) == len(devices)
    assert all(n > 0 for n in facts["completed_per_replica"]), facts
    assert all(b > 0 for b in facts["bytes_in_use"]), facts
    assert not any(facts.get("fleet_counters", {}).values()), facts
    for d in facts["vs_one_shot_dense"]:
        assert d == "identical" or d["top2_margin"] < TIE_MARGIN, facts
    eng.close()
    return facts


# ---------------------------------------------------------------------------


def main() -> int:
    t0 = time.perf_counter()
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_guide_tpu.core.device import (
        require_tpu,
        setup_compile_cache,
    )
    from distributed_tensorflow_guide_tpu.models.transformer import gpt2_124m
    from distributed_tensorflow_guide_tpu.ops import autotune

    cache_dir = setup_compile_cache()
    devices = require_tpu()  # exits non-zero here when there is no chip
    compiles = CompileLog()
    cfg = gpt2_124m(dtype=jnp.bfloat16)

    phase_device(devices)
    phase_kernels(heads=cfg.num_heads, head_dim=cfg.head_dim,
                  seq=cfg.max_len)
    phase_patterned()
    state, tokens = phase_train(cfg, devices)
    phase_serve(cfg, state, tokens, devices)
    table = autotune.table_path()
    say("cache", dir=cache_dir,
        autotune_table=str(table) if table.exists() else "absent: defaults",
        **compiles.facts(), wall_s=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
