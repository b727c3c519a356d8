"""End-to-end serving story: corpus -> BPE tokenizer -> sync-DP training
-> the continuous-batching engine -> streamed completions.

The engine-side companion to examples/gpt2_generate.py (one-shot
generation): the same DP-trained checkpoint is served through
serve/engine.py — a fixed-slot decode batch over a paged KV pool, with
requests submitted at staggered arrival times so the demo visibly
admits prompts MID-FLIGHT (watch the interleaved ``req N`` lines: a
request that arrives while others are decoding starts streaming without
anything recompiling or restarting). Per-request output is bitwise what
a one-shot ``make_generate_fn`` call would produce — the demo checks
that for the first prompt.

    python examples/gpt2_serve.py --fake-devices 8 --steps 300 \\
        --prompts "the quick brown|pack my box|how vexingly"
"""

import argparse
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

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
    ap.add_argument("--data", default=None, metavar="CORPUS")
    ap.add_argument("--bpe-vocab", type=int, default=384)
    ap.add_argument("--prompts",
                    default="the quick brown|pack my box|"
                            "how vexingly|the lazy",
                    help="'|'-separated prompts, submitted with "
                         "staggered arrivals")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--kv-dtype", choices=["model", "int8"],
                    default="model")
    ap.add_argument("--decode-impl", choices=["auto", "dense", "pallas"],
                    default="auto")
    ap.add_argument("--slots", type=int, default=2,
                    help="decode batch width — fewer slots than prompts "
                         "makes mid-flight admission visible")
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--num-blocks", type=int, default=17)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--prefix-cache", action="store_true",
                    help="cache full prompt blocks in the radix prefix "
                         "index — repeated prompt prefixes skip their "
                         "prefill (watch prefill_tokens_saved in health)")
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
    from distributed_tensorflow_guide_tpu.core.mesh import (
        MeshSpec,
        build_mesh,
    )
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
    from distributed_tensorflow_guide_tpu.serve.engine import (
        Request,
        ServeEngine,
    )

    initialize()
    mesh = build_mesh(MeshSpec(data=-1))
    dp = DataParallel(mesh)

    import tempfile

    workdir = Path(tempfile.mkdtemp(prefix="gpt2_serve_"))
    if args.data:
        corpus = Path(args.data)
    else:
        corpus = workdir / "demo.txt"
        corpus.write_text(DEMO_CORPUS)
    tokenizer = ByteBPETokenizer.train(corpus.read_bytes(),
                                       vocab_size=args.bpe_vocab)
    rec = workdir / "corpus.records"
    import_text(corpus, rec, tokenizer, args.seq_len)
    loader = open_record_loader(rec, text_fields(args.seq_len),
                                args.global_batch, seed=0)

    cfg = TransformerConfig(
        vocab_size=padded_vocab(tokenizer.vocab_size),
        num_layers=args.layers, num_heads=args.heads,
        d_model=args.d_model, d_ff=4 * args.d_model,
        max_len=args.seq_len, causal=True, dtype=jnp.float32)
    model = Transformer(cfg)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0),
        jnp.zeros((1, cfg.max_len), jnp.int32))["params"]
    state = dp.replicate(train_state.TrainState.create(
        apply_fn=model.apply, params=params, tx=optax.adam(args.lr)))
    step = dp.make_train_step(make_lm_loss_fn(model))
    for i in range(args.steps):
        state, m = step(state, dp.shard_batch(loader.next_batch()))
        if i % 100 == 0 or i == args.steps - 1:
            print(f"step {i}: loss={float(m['loss']):.4f}")

    # ---- the engine: DP-trained checkpoint, serving-side levers ---------
    import dataclasses

    serve_cfg = dataclasses.replace(
        cfg, kv_dtype="int8" if args.kv_dtype == "int8" else None,
        decode_impl=args.decode_impl)
    eng = ServeEngine(serve_cfg, state.params, slots=args.slots,
                      num_blocks=args.num_blocks,
                      block_size=args.block_size,
                      prefill_chunk=args.prefill_chunk,
                      temperature=args.temperature, top_k=args.top_k,
                      prefix_cache=args.prefix_cache)
    prompts = [p.strip() for p in args.prompts.split("|") if p.strip()]
    encoded = {}
    for rid, text in enumerate(prompts):
        toks = np.asarray(tokenizer.encode(text.encode()), np.int32)
        encoded[rid] = toks
        # staggered arrivals: later prompts land while earlier ones are
        # mid-decode — with slots < len(prompts) the queue drains into
        # slots as they free, all through the same two compiled programs
        eng.submit(Request(rid=rid, prompt=toks,
                           max_new_tokens=args.max_new,
                           rng=jax.random.PRNGKey(rid),
                           arrival=0.1 * rid))
    print(f"serving {len(prompts)} prompts on {args.slots} slots")
    now = 0.0
    while eng.sched.has_queued or eng.sched.has_resident:
        evs, kind = eng.step(now)
        if kind == "idle":
            nxt = eng.sched.next_arrival()
            if nxt is None:
                break
            now = max(now, nxt)
            continue
        now += 0.01  # demo clock: one tick per launch
        for e in evs:
            piece = tokenizer.decode([e.token])
            tag = "first" if e.first else ("done" if e.done else "")
            print(f"  req {e.rid} += {piece!r} {tag}")
    print("--")
    for rid, toks in sorted(eng.completions().items()):
        full = tokenizer.decode(encoded[rid].tolist() + toks)
        print(f"req {rid}: {full!r}")

    # parity spot-check: engine stream == one-shot generate, bitwise
    gen = make_generate_fn(serve_cfg, max_new_tokens=args.max_new,
                           temperature=args.temperature,
                           top_k=args.top_k)
    one = np.asarray(gen(state.params, encoded[0][None],
                         jax.random.PRNGKey(0)))
    oracle = one[0, len(encoded[0]):].tolist()
    assert eng.completions()[0] == oracle, "engine/one-shot divergence"
    print("engine == one-shot for req 0: ok")

    # shutdown contract (PR 11): health counters and a loud block-ledger
    # audit — every pool block accounted for before the engine goes away
    print(f"health: {eng.health()}")
    eng.sched.pool.check_leaks()
    eng.close()
    print("pool.check_leaks(): clean")
    print("serve ok")


if __name__ == "__main__":
    main()
