"""End-to-end LM story: corpus -> BPE tokenizer -> token records -> sync-DP
training -> KV-cache generation -> decoded text.

The serving-side companion to examples/gpt2_pipeline.py (training-side).
No reference equivalent: the guide stops at training loss. The generate
call is ONE compiled XLA program (prefill forward + lax.scan decode loop,
static shapes, per-layer KV cache) — see models/generation.py.

    python examples/gpt2_generate.py --fake-devices 8 --steps 300 \\
        --prompt "the quick brown"
"""

import argparse
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

# A tiny deterministic corpus the model can memorize in a few hundred
# steps — the point is exercising the full loop, not language modeling.
DEMO_CORPUS = (
    "the quick brown fox jumps over the lazy dog. "
    "pack my box with five dozen liquor jugs. "
    "how vexingly quick daft zebras jump. "
) * 120


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--data", default=None, metavar="CORPUS",
                    help="text file (default: built-in demo corpus)")
    ap.add_argument("--bpe-vocab", type=int, default=384)
    ap.add_argument("--prompt", default="the quick brown")
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--fused-ce", choices=["auto", "on", "off"],
                    default="auto",
                    help="chunked fused cross-entropy for the training "
                         "loss (ops/fused_ce.py): no (B, S, V) logits "
                         "live; 'auto' = on for TPU + chunkable vocab")
    ap.add_argument("--precision", default="auto",
                    choices=["auto", "f32", "bf16", "bf16_remat",
                             "bf16_remat_attn"],
                    help="mixed-precision policy (core/precision.py); "
                         "'auto' keeps this demo's f32")
    ap.add_argument("--kv-dtype", choices=["model", "int8"],
                    default="model",
                    help="serving KV-cache dtype; 'int8' quantizes the "
                         "cache (docs/serving.md decode levers)")
    ap.add_argument("--decode-impl", choices=["auto", "dense", "pallas"],
                    default="auto",
                    help="decode-attention impl ('auto' = the Pallas "
                         "length-aware kernel on TPU, dense elsewhere)")
    ap.add_argument("--spec-draft-layers", type=int, default=0,
                    help="self-speculative decoding with this many draft "
                         "prefix layers (0 = off; output is identical "
                         "either way — the knob only changes the "
                         "schedule)")
    ap.add_argument("--fake-devices", type=int, default=0)
    args = ap.parse_args()

    if args.fake_devices:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if args.fake_devices:
        jax.config.update("jax_num_cpu_devices", args.fake_devices)

    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax.training import train_state

    from distributed_tensorflow_guide_tpu.core.dist import initialize
    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh
    from distributed_tensorflow_guide_tpu.data.native_loader import (
        open_record_loader,
    )
    from distributed_tensorflow_guide_tpu.data.tokenizer import (
        ByteBPETokenizer,
        import_text,
        padded_vocab,
        text_fields,
    )
    from distributed_tensorflow_guide_tpu.models.generation import (
        make_generate_fn,
    )
    from distributed_tensorflow_guide_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
        make_lm_loss_fn,
    )
    from distributed_tensorflow_guide_tpu.parallel.data_parallel import (
        DataParallel,
    )

    initialize()
    mesh = build_mesh(MeshSpec(data=-1))
    dp = DataParallel(mesh)

    # corpus -> tokenizer -> records -> native loader. Records go to a
    # private temp dir (concurrent runs must not clobber each other); a
    # --data corpus is imported straight from its own path.
    import tempfile

    workdir = Path(tempfile.mkdtemp(prefix="gpt2_generate_"))
    if args.data:
        corpus = Path(args.data)
    else:
        corpus = workdir / "demo.txt"
        corpus.write_text(DEMO_CORPUS)
    corpus_bytes = corpus.read_bytes()
    tokenizer = ByteBPETokenizer.train(corpus_bytes,
                                       vocab_size=args.bpe_vocab)
    rec = workdir / "corpus.records"
    n = import_text(corpus, rec, tokenizer, args.seq_len)
    loader = open_record_loader(rec, text_fields(args.seq_len),
                                args.global_batch, seed=0)
    print(f"corpus: {len(corpus_bytes)} bytes -> {n} records, "
          f"vocab {tokenizer.vocab_size}")

    cfg = TransformerConfig(
        vocab_size=padded_vocab(tokenizer.vocab_size),
        num_layers=args.layers, num_heads=args.heads,
        d_model=args.d_model, d_ff=4 * args.d_model,
        max_len=args.seq_len, causal=True, dtype=jnp.float32)
    if args.precision != "auto":
        from distributed_tensorflow_guide_tpu.core import precision as prec

        cfg = prec.resolve(args.precision).apply_to_transformer(cfg)
    model = Transformer(cfg)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0),
        jnp.zeros((1, cfg.max_len), jnp.int32))["params"]
    state = dp.replicate(train_state.TrainState.create(
        apply_fn=model.apply, params=params, tx=optax.adam(args.lr)))
    step = dp.make_train_step(make_lm_loss_fn(model,
                                              fused_ce=args.fused_ce))

    for i in range(args.steps):
        batch = dp.shard_batch(loader.next_batch())
        state, m = step(state, batch)
        if i % 50 == 0 or i == args.steps - 1:
            print(f"step {i}: loss={float(m['loss']):.4f} "
                  f"ppl={float(m['perplexity']):.1f}")

    # generate: one compiled program; params already replicated on-mesh.
    # The serving config may differ from the training config by the
    # decode levers only (cache dtype / attend impl are serving-side
    # state, invisible to the trained params).
    import dataclasses

    gen_cfg = dataclasses.replace(
        cfg, kv_dtype="int8" if args.kv_dtype == "int8" else None,
        decode_impl=args.decode_impl)
    gen = make_generate_fn(gen_cfg, max_new_tokens=args.max_new,
                           temperature=args.temperature, top_k=args.top_k,
                           spec_draft_layers=args.spec_draft_layers)
    prompt_ids = np.asarray([tokenizer.encode(args.prompt.encode())],
                            np.int32)
    out = np.asarray(gen(state.params, prompt_ids, jax.random.PRNGKey(0)))
    text = tokenizer.decode(out[0].tolist())
    print(f"prompt : {args.prompt!r}")
    print(f"output : {text!r}")
    if gen.last_stats is not None:
        stats = {k: int(v) for k, v in gen.last_stats.items()}
        print(f"speculative: {stats}")
    print("generate ok")


if __name__ == "__main__":
    main()
