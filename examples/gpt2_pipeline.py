"""Judged config 5: GPT-2 pipeline-parallel LM training (GPipe microbatch
schedule over the ``pipe`` mesh axis, composed with data parallelism).

No reference equivalent exists (the guide's only composition mechanism is
PS/worker processes); see parallel/pipeline.py for the design.

    # 4-stage pipeline x 2-way data parallel on 8 fake devices:
    python examples/gpt2_pipeline.py --fake-devices 8 --pipe 4 --layers 12

    # full GPT-2 124M geometry (for a real v5e-16: --pipe 4, data fills rest)
    python examples/gpt2_pipeline.py --full-gpt2 --pipe 4
"""

import argparse
import logging
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--pipe", type=int, default=4)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=1024)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--microbatch-size", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--full-gpt2", action="store_true",
                    help="use the real GPT-2 124M geometry")
    ap.add_argument("--schedule", choices=["gpipe", "1f1b"], default="gpipe")
    ap.add_argument("--virtual-chunks", type=int, default=1,
                    help="interleaved pipelining: layer chunks per device "
                         "(bubble shrinks ~v-fold; with --schedule 1f1b "
                         "this is Megatron's combined schedule)")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="TP degree inside each stage (Megatron f/g; the "
                         "LM head goes vocab-parallel) — 3D dp x tp x pp")
    ap.add_argument("--fused-ce", choices=["auto", "on", "off"],
                    default="auto",
                    help="chunked fused cross-entropy (ops/fused_ce.py): "
                         "loss + grad-of-logits per vocab chunk, no "
                         "(B, S, V) logits live. 'auto' resolves on for "
                         "TPU + chunkable vocab, off on CPU (the resolved "
                         "setting is printed)")
    ap.add_argument("--precision", default="auto",
                    choices=["auto", "f32", "bf16", "bf16_remat",
                             "bf16_remat_attn"],
                    help="mixed-precision policy (core/precision.py): "
                         "params f32 / activations per policy / loss+accum "
                         "f32, incl. the selective-remat knob "
                         "(bf16_remat_attn checkpoints attention only). "
                         "'auto' keeps this script's per-config dtypes")
    ap.add_argument("--data", default=None, metavar="CORPUS",
                    help="text file to train on: byte-level BPE is trained "
                         "(or loaded from CORPUS.vocab.json), the corpus is "
                         "packed into fixed-length token records, and the "
                         "native mmap/shuffle/prefetch loader streams "
                         "batches. Default: random tokens.")
    ap.add_argument("--bpe-vocab", type=int, default=1024,
                    help="target BPE vocab size when training a tokenizer")
    ap.add_argument("--generate", default=None, metavar="PROMPT",
                    help="after training, convert the pipeline params to "
                         "the serving layout and greedily decode from "
                         "PROMPT (needs --data)")
    ap.add_argument("--max-new", type=int, default=24)
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

    from distributed_tensorflow_guide_tpu.core.dist import initialize
    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, axis_sizes, build_mesh
    from distributed_tensorflow_guide_tpu.models.transformer import (
        TransformerConfig,
        gpt2_124m,
    )
    from distributed_tensorflow_guide_tpu.parallel.pipeline import PipelinedLM

    logging.basicConfig(level=logging.INFO, format="%(message)s", force=True)
    initialize()

    mesh = build_mesh(MeshSpec(data=-1, pipe=args.pipe,
                               model=args.model_parallel))
    sizes = axis_sizes(mesh)

    tokenizer = None
    if args.data:
        # real text: one-time host-side import — train/load the byte-level
        # BPE, pack the corpus into seq_len token records, stream via the
        # native loader. Tokenization never touches the training hot path.
        from distributed_tensorflow_guide_tpu.data.tokenizer import (
            ByteBPETokenizer,
            padded_vocab,
        )

        vocab_file = Path(args.data).with_suffix(".vocab.json")
        if vocab_file.exists():
            tokenizer = ByteBPETokenizer.load(vocab_file)
            print(f"loaded BPE vocab: {vocab_file} "
                  f"({tokenizer.vocab_size} tokens)")
        else:
            tokenizer = ByteBPETokenizer.train(
                Path(args.data).read_bytes(), vocab_size=args.bpe_vocab)
            tokenizer.save(vocab_file)
            print(f"trained BPE vocab: {len(tokenizer.merges)} merges -> "
                  f"{vocab_file}")
        # model vocab: tokenizer's, padded up to a lane multiple (MXU
        # tiling + vocab-parallel divisibility under --model-parallel);
        # an explicit larger --vocab is respected (headroom keeps later
        # checkpoints shape-compatible with a regrown vocab)
        padded = padded_vocab(tokenizer.vocab_size)
        if args.vocab > padded:
            print(f"vocab: keeping --vocab {args.vocab} "
                  f"(tokenizer needs {padded})")
        else:
            if args.vocab != ap.get_default("vocab"):
                print(f"vocab: --vocab {args.vocab} too small for the "
                      f"tokenizer; using {padded}")
            args.vocab = padded

    if args.full_gpt2:
        cfg = gpt2_124m(remat=True)
        if tokenizer is not None and tokenizer.vocab_size > cfg.vocab_size:
            raise SystemExit(
                f"--full-gpt2 pins vocab {cfg.vocab_size}; the trained "
                f"tokenizer needs {tokenizer.vocab_size} — lower --bpe-vocab")
    else:
        cfg = TransformerConfig(
            vocab_size=args.vocab, num_layers=args.layers,
            num_heads=args.heads, d_model=args.d_model,
            d_ff=4 * args.d_model, max_len=args.seq_len, causal=True,
            dtype=jnp.float32,
        )
    pp = PipelinedLM(mesh, cfg, num_microbatches=args.microbatches,
                     schedule=args.schedule,
                     virtual_chunks=args.virtual_chunks,
                     fused_ce=args.fused_ce,
                     precision=None if args.precision == "auto"
                     else args.precision)
    cfg = pp.cfg  # precision policy may have rewritten dtype/remat
    print(f"fused_ce={pp.fused_ce} (requested {args.fused_ce!r}), "
          f"precision={args.precision}")
    params = pp.init_params(jax.random.PRNGKey(0))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    tx = optax.adam(args.lr)
    opt_state = pp.init_opt_state(tx, params)
    step = pp.make_train_step(tx, params)

    per_shard = args.microbatches * args.microbatch_size
    global_batch = per_shard * sizes["data"]
    if args.data:
        from distributed_tensorflow_guide_tpu.data.tokenizer import (
            import_text,
            text_fields,
        )
        from distributed_tensorflow_guide_tpu.data.native_loader import (
            open_record_loader,
        )

        rec = Path(args.data).with_suffix(f".s{cfg.max_len}.records")
        # one-time import, mtime-keyed like _build_lib: re-tokenize only
        # when the corpus or vocab changed since the records were packed
        src_mtime = max(Path(args.data).stat().st_mtime,
                        vocab_file.stat().st_mtime)
        if rec.exists() and rec.stat().st_mtime >= src_mtime:
            n_rec = rec.stat().st_size // (cfg.max_len * 4)
        else:
            n_rec = import_text(args.data, rec, tokenizer, cfg.max_len)
        loader = open_record_loader(rec, text_fields(cfg.max_len),
                                    global_batch)
        print(f"native loader: {n_rec} records x {cfg.max_len} tokens "
              f"from {rec} ({type(loader).__name__})")
        batches = (b["tokens"] for b in loader)
    else:
        rng = np.random.RandomState(0)
        tokens_fixed = rng.randint(
            0, cfg.vocab_size, (global_batch, cfg.max_len)
        ).astype(np.int32)
        # NOT iter(lambda: ..., None): the 2-arg iter compares each yield
        # to the sentinel with ==, which on a numpy array is elementwise
        # and raises at the first next()
        import itertools

        batches = itertools.repeat(tokens_fixed)
    if args.virtual_chunks > 1:
        # interleaved: bubble from the actual schedule, in full-stage units
        # (each tick costs 1/v of a stage)
        from distributed_tensorflow_guide_tpu.parallel.pipeline import (
            _make_interleaved_schedule,
        )

        T = _make_interleaved_schedule(
            args.microbatches, sizes["pipe"], args.virtual_chunks)["T"]
        bubble = (T - args.microbatches * args.virtual_chunks) / T
        kind = f"interleaved (v={args.virtual_chunks})"
    else:
        bubble = (sizes["pipe"] - 1) / (args.microbatches + sizes["pipe"] - 1)
        kind = args.schedule
    for i in range(args.steps):
        opt_state, params, m = step(opt_state, params, next(batches))
        if i % 5 == 0:
            print(f"step {i}: loss={float(m['loss']):.4f}")
    print(f"done: {n_params/1e6:.1f}M params over {sizes['pipe']} stages x "
          f"{sizes['data']} data shards; {kind} bubble fraction "
          f"{bubble:.2f} ({args.microbatches} microbatches)")

    if args.generate is not None:
        if tokenizer is None:
            raise SystemExit("--generate needs --data (a trained tokenizer)")
        # train-with-PP, serve-with-KV-cache: invert the stage stacking to
        # the flat Transformer layout and decode (parity pinned in
        # tests/test_pipeline.py::test_to_serving_params_logits_parity)
        import dataclasses

        from distributed_tensorflow_guide_tpu.models.generation import (
            make_generate_fn,
        )

        serving = pp.to_serving_params(jax.device_get(params))
        gen = make_generate_fn(
            dataclasses.replace(cfg, remat=False, remat_mode=None),
            max_new_tokens=args.max_new, temperature=0.0)
        ids = np.asarray([tokenizer.encode(args.generate.encode())], np.int32)
        out = np.asarray(gen(serving, ids, jax.random.PRNGKey(0)))
        print("generated:", tokenizer.decode(out[0].tolist()))


if __name__ == "__main__":
    main()
