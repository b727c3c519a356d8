"""Judged config 3: BERT-base GLUE-style classification, parameter-sharded
over the ``model`` mesh axis (pjit / NamedSharding).

Reference equivalent: ParameterServerStrategy
(tensorflow/python/distribute/parameter_server_strategy_v2.py:77) sharding
whole variables across PS tasks over gRPC; here tensors are sharded
*internally* (Megatron factorization) and never leave HBM.

    python examples/bert_tensor_parallel.py --fake-devices 8 --model-parallel 4

Real data (GLUE-style ``label<TAB>text`` file, fed through the byte-level
BPE tokenizer -> fixed-length labeled records -> the native
mmap/shuffle/prefetch loader, with a held-out split evaluated by the
distributed eval harness):

    python examples/bert_tensor_parallel.py --data sst.tsv --fake-devices 8
    # no dataset handy? generate a deterministic sentiment-style demo:
    python examples/bert_tensor_parallel.py --make-demo-data 2048 \\
        --data demo.tsv --fake-devices 8
"""

import argparse
import logging
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

# Deterministic demo corpus: label = which lexicon dominates the line. A
# real task shape (bag-of-evidence sentiment), generated locally — the
# point is exercising the REAL input path (tokenizer, records, native
# loader, eval split), not the linguistics.
_POS = ("good great fine superb solid delightful crisp warm bright "
        "honest generous").split()
_NEG = ("bad awful dull broken sour bleak cold murky shallow brittle "
        "hollow").split()
_NEUTRAL = ("the a this that movie film plot scene actor scene pacing "
            "script camera ending dialogue soundtrack").split()


def make_demo_tsv(path: Path, n: int, seed: int = 0) -> None:
    import numpy as np

    rng = np.random.RandomState(seed)
    with open(path, "w") as fh:
        for _ in range(n):
            label = int(rng.randint(2))
            lex = _POS if label else _NEG
            words = []
            for _ in range(int(rng.randint(6, 14))):
                pick = lex if rng.rand() < 0.45 else _NEUTRAL
                words.append(pick[rng.randint(len(pick))])
            fh.write(f"{label}\t{' '.join(words)}\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--global-batch", type=int, default=32)
    ap.add_argument("--model-parallel", type=int, default=4)
    ap.add_argument("--layers", type=int, default=4,
                    help="12 = full BERT-base; small default for CPU demo")
    ap.add_argument("--d-model", type=int, default=768,
                    help="width (heads must divide it AND be divisible "
                         "by --model-parallel); d_ff scales with it")
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--data", default=None, metavar="TSV",
                    help="label<TAB>text file: byte-level BPE is trained "
                         "(or loaded from TSV.vocab.json), lines are packed "
                         "into fixed-length labeled records, the native "
                         "loader streams batches, and a held-out split is "
                         "evaluated. Default: synthetic tokens.")
    ap.add_argument("--make-demo-data", type=int, default=0, metavar="N",
                    help="first write N deterministic demo lines to --data")
    ap.add_argument("--eval-every-n", type=int, default=10,
                    help="line index i % n == 0 goes to the held-out split")
    ap.add_argument("--bpe-vocab", type=int, default=512)
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
    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, axis_sizes, build_mesh
    from distributed_tensorflow_guide_tpu.models.transformer import (
        Transformer,
        bert_base,
        make_cls_loss_fn,
    )
    from distributed_tensorflow_guide_tpu.parallel.tensor import TensorParallel

    logging.basicConfig(level=logging.INFO, format="%(message)s", force=True)
    initialize()

    vocab_size = None
    train_loader = eval_loader = None
    if args.data:
        from distributed_tensorflow_guide_tpu.data.native_loader import (
            open_record_loader,
        )
        from distributed_tensorflow_guide_tpu.data.tokenizer import (
            ByteBPETokenizer,
            import_labeled_text,
            labeled_text_fields,
            padded_vocab,
        )

        tsv = Path(args.data)
        if args.make_demo_data:
            make_demo_tsv(tsv, args.make_demo_data)
            print(f"wrote {args.make_demo_data} demo lines -> {tsv}")

        # deterministic line-index split: i % n == 0 held out
        lines = [ln for ln in tsv.read_bytes().splitlines() if ln.strip()]
        train_tsv = tsv.with_suffix(".train.tsv")
        eval_tsv = tsv.with_suffix(".eval.tsv")
        train_tsv.write_bytes(b"\n".join(
            ln for i, ln in enumerate(lines) if i % args.eval_every_n) + b"\n")
        eval_tsv.write_bytes(b"\n".join(
            ln for i, ln in enumerate(lines)
            if not i % args.eval_every_n) + b"\n")

        vocab_file = tsv.with_suffix(".vocab.json")
        if vocab_file.exists():
            tokenizer = ByteBPETokenizer.load(vocab_file)
            print(f"loaded BPE vocab: {vocab_file} "
                  f"({tokenizer.vocab_size} tokens)")
        else:
            # vocab learned from the TRAIN split only — the held-out text
            # must not shape the representation it is scored with
            tokenizer = ByteBPETokenizer.train(
                train_tsv.read_bytes(), vocab_size=args.bpe_vocab)
            tokenizer.save(vocab_file)
            print(f"trained BPE vocab on train split -> {vocab_file}")

        fields = labeled_text_fields(args.seq_len)
        recs = {}
        for split, src in (("train", train_tsv), ("eval", eval_tsv)):
            out = tsv.with_suffix(f".{split}.records")
            n = import_labeled_text(src, out, tokenizer, args.seq_len)
            recs[split] = out
            print(f"{split}: {n} records -> {out}")

        train_loader = open_record_loader(
            recs["train"], fields, args.global_batch, seed=0)
        # eval batch = global batch (must divide the eval set for exact
        # mean-of-means; the loader drops the remainder)
        eval_loader = open_record_loader(
            recs["eval"], fields, args.global_batch, seed=0)
        vocab_size = padded_vocab(tokenizer.vocab_size)

    mesh = build_mesh(MeshSpec(data=-1, model=args.model_parallel))
    cfg = bert_base(num_classes=2, dtype=jnp.float32)
    cfg = type(cfg)(**{**cfg.__dict__, "num_layers": args.layers,
                       "max_len": args.seq_len,
                       "d_model": args.d_model, "num_heads": args.heads,
                       "d_ff": 4 * args.d_model,
                       **({"vocab_size": vocab_size} if vocab_size else {})})
    model = Transformer(cfg)
    tp = TensorParallel(mesh)

    sample = jnp.zeros((1, cfg.max_len), jnp.int32)
    params, shardings = tp.init_params(model, jax.random.PRNGKey(0), sample)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    state = train_state.TrainState.create(
        apply_fn=model.apply, params=params, tx=optax.adamw(args.lr)
    )
    st_shard = tp.state_shardings(state, shardings)
    state = jax.device_put(state, st_shard)
    cls_loss = make_cls_loss_fn(model)
    step = tp.make_train_step(cls_loss, st_shard)

    evaluator = None
    if eval_loader is not None:
        from distributed_tensorflow_guide_tpu.train.evaluation import Evaluator

        def metric_fn(params, batch):
            loss, mets = cls_loss(params, batch)
            return {"loss": loss, **mets}

        def make_eval_data():
            return (eval_loader.next_batch()
                    for _ in range(eval_loader.batches_per_epoch))

        evaluator = Evaluator(tp.make_eval_step(metric_fn, st_shard),
                              make_eval_data)

    rng = np.random.RandomState(0)
    for i in range(args.steps):
        if train_loader is not None:
            b = train_loader.next_batch()
            batch = {"tokens": b["tokens"], "label": b["label"]}
        else:
            tokens = rng.randint(
                0, cfg.vocab_size,
                (args.global_batch, cfg.max_len)).astype(np.int32)
            # learnable synthetic task: [CLS] drawn from 50 ids, label parity
            tokens[:, 0] = rng.randint(0, 50, args.global_batch)
            batch = {"tokens": tokens,
                     "label": (tokens[:, 0] % 2).astype(np.int32)}
        state, m = step(state, batch)
        if i % 10 == 0:
            print(f"step {i}: loss={float(m['loss']):.4f} "
                  f"acc={float(m['accuracy']):.3f}")
    if evaluator is not None:
        ev = evaluator.run(state)
        print(f"held-out: loss={ev['loss']:.4f} acc={ev['accuracy']:.3f} "
              f"({ev['eval_batches']:.0f} batches)")
    up = state.params["block_0"]["mlp"]["up"]["kernel"]
    print(f"done: {n_params/1e6:.1f}M params, mesh={axis_sizes(mesh)}, "
          f"mlp kernel sharding={up.sharding.spec}, "
          f"local shard={up.addressable_shards[0].data.shape} of {up.shape}")


if __name__ == "__main__":
    main()
