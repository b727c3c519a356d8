"""``dtg-serve`` — run the continuous-batching engine on a demo workload.

A console-script sibling of ``dtg-lint``: builds a small randomly
initialised model (or loads nothing — this is a scheduling demo, not a
quality demo), submits a staggered mix of prompts, and streams every
token event as it is emitted, then prints the per-request completions
and the pool/scheduler counters. The point is to make the serving loop
observable from a shell one-liner:

    dtg-serve --requests 6 --slots 2 --prefill-chunk 8

For trained-checkpoint serving see examples/gpt2_serve.py; for load
numbers see benchmarks/bench_serving.py.
"""

from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser(prog="dtg-serve")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--num-blocks", type=int, default=17)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--top-k", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share a common system prompt across the demo "
                         "requests through the radix prefix cache "
                         "(watch prefill_tokens_saved in health())")
    ap.add_argument("--lora-rank", type=int, default=0,
                    help="serve the mix multi-LoRA: requests cycle "
                         "through 3 adapters (0 = base) inside the "
                         "shared decode step")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="serve through an N-replica FleetScheduler "
                         "(global admission/DRR/routing over N stock "
                         "engines, replica i on local device i mod the "
                         "device count) instead of a single engine; watch "
                         "the per-replica healths and fleet counters")
    ap.add_argument("--fleet-roles", choices=["colocated", "disagg"],
                    default="colocated",
                    help="with --fleet: 'disagg' splits prefill/decode "
                         "roles and ships KV blocks at the phase flip")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_guide_tpu.core.device import (
        setup_compile_cache,
    )
    from distributed_tensorflow_guide_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
    )
    from distributed_tensorflow_guide_tpu.serve.engine import (
        Request,
        ServeEngine,
    )

    import dataclasses

    setup_compile_cache()
    cfg = TransformerConfig(vocab_size=256, num_layers=2, num_heads=2,
                            d_model=32, d_ff=64, max_len=64, causal=True,
                            dtype=jnp.float32)
    bank = None
    if args.lora_rank:
        from distributed_tensorflow_guide_tpu.serve.engine import (
            init_adapter_bank,
        )

        cfg = dataclasses.replace(cfg, lora_rank=args.lora_rank,
                                  lora_adapters=2)
        leaves, treedef = jax.tree.flatten(init_adapter_bank(cfg))
        keys = jax.random.split(jax.random.PRNGKey(args.seed + 7),
                                len(leaves))
        bank = jax.tree.unflatten(treedef, [
            (0.05 * jax.random.normal(k, l.shape, l.dtype)).at[0].set(0.0)
            for k, l in zip(keys, leaves)])
    params = Transformer(cfg).init(
        jax.random.PRNGKey(args.seed),
        jnp.zeros((1, 8), jnp.int32))["params"]
    if args.fleet:
        from distributed_tensorflow_guide_tpu.serve.fleet import (
            FleetScheduler,
        )

        eng = FleetScheduler(cfg, params, replicas=args.fleet,
                             roles=args.fleet_roles, slots=args.slots,
                             num_blocks=args.num_blocks,
                             block_size=args.block_size,
                             prefill_chunk=args.prefill_chunk,
                             temperature=args.temperature,
                             top_k=args.top_k, adapters=bank,
                             prefix_cache=args.prefix_cache)
    else:
        eng = ServeEngine(cfg, params, slots=args.slots,
                          num_blocks=args.num_blocks,
                          block_size=args.block_size,
                          prefill_chunk=args.prefill_chunk,
                          temperature=args.temperature, top_k=args.top_k,
                          prefix_cache=args.prefix_cache, adapters=bank)
    rng = np.random.RandomState(args.seed)
    sys_prompt = (rng.randint(0, cfg.vocab_size, 16).astype(np.int32)
                  if args.prefix_cache else None)
    for rid in range(args.requests):
        plen = int(rng.choice([4, 8, 16]))
        prompt = rng.randint(0, cfg.vocab_size, plen).astype(np.int32)
        if sys_prompt is not None:
            prompt = np.concatenate([sys_prompt, prompt[:4]])
        eng.submit(Request(
            rid=rid,
            prompt=prompt,
            max_new_tokens=args.max_new,
            rng=jax.random.PRNGKey(args.seed * 1000 + rid),
            adapter=(rid % 3 if args.lora_rank else 0),
            tenant=rid % 2))
    for ev in eng.run():
        if ev.status != "ok":
            print(f"req {ev.rid:3d} ! {ev.status}")
            continue
        mark = "*" if ev.first else ("." if not ev.done else "$")
        print(f"req {ev.rid:3d} {mark} token {ev.token}")
    print("--")
    for rid, toks in sorted(eng.completions().items()):
        print(f"req {rid}: {toks}")
    if args.fleet:
        print(f"health={eng.health()}")
        # shutdown contract: every replica's ledgers clean, loudly
        eng.check_leaks()
    else:
        print(f"steps={eng.steps} health={eng.health()}")
        # shutdown contract: every block accounted for, loudly
        eng.sched.pool.check_leaks()
    eng.close()
    print("pool.check_leaks(): clean")


if __name__ == "__main__":
    main()
