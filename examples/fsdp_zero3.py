"""Fully-sharded data parallelism (FSDP / ZeRO-3) — params and optimizer
moments sharded over the SAME ``data`` axis the batch is split over.

Reference context: the guide's synchronous track (⚠ Synchronous-SGD/ via
``SyncReplicasOptimizer``, tensorflow/python/training/
sync_replicas_optimizer.py:42) replicates every variable on every worker.
FSDP is that strategy completed for models that outgrow one device: same
sync-DP numerics (the determinism gate diffs fsdp8 against the 1-device
control), ~world-fold less resident state per device. On TPU it is pure
sharding annotation — GSPMD inserts the all-gather/reduce-scatter schedule
on ICI (parallel/fsdp.py).

    python examples/fsdp_zero3.py --fake-devices 8
    python examples/fsdp_zero3.py --fake-devices 8 --layers 4 --d-model 512
"""

import argparse
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--d-ff", type=int, default=512)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=1024)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--fused-ce", choices=["auto", "on", "off"],
                    default="auto",
                    help="chunked fused cross-entropy for the LM loss "
                         "(ops/fused_ce.py; 'auto' = on for TPU + "
                         "chunkable vocab)")
    ap.add_argument("--fsdp-prefetch", choices=["auto", "on", "off"],
                    default="auto",
                    help="manual per-leaf gather/scatter schedule "
                         "(parallel/overlap.py: explicit all-gather fwd / "
                         "reduce-scatter bwd per leaf, prefetchable by the "
                         "async-collective scheduler; 'auto' = on for TPU, "
                         "off keeps GSPMD's inferred schedule)")
    ap.add_argument("--fake-devices", type=int, default=0)
    args = ap.parse_args()

    if args.fake_devices:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if args.fake_devices:
        jax.config.update("jax_num_cpu_devices", args.fake_devices)

    import flax.linen as nn
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax.training import train_state
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_guide_tpu.core.dist import initialize
    from distributed_tensorflow_guide_tpu.core.mesh import (
        MeshSpec,
        axis_sizes,
        build_mesh,
    )
    from distributed_tensorflow_guide_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
        make_lm_loss_fn,
    )
    from distributed_tensorflow_guide_tpu.parallel.fsdp import FSDP

    initialize()
    mesh = build_mesh(MeshSpec(data=-1))
    cfg = TransformerConfig(
        vocab_size=args.vocab, num_layers=args.layers, num_heads=args.heads,
        d_model=args.d_model, d_ff=args.d_ff, max_len=args.seq_len,
        causal=True, dtype=jnp.float32,
    )
    model = Transformer(cfg)
    fsdp = FSDP(mesh, min_shard_size=2 ** 10, prefetch=args.fsdp_prefetch)
    tokens0 = jnp.zeros((1, cfg.max_len), jnp.int32)

    def init_fn():
        return nn.meta.unbox(
            model.init(jax.random.PRNGKey(0), tokens0)
        )["params"]

    # each leaf materializes directly INTO its shard — no device ever holds
    # the full tree (how models ~world x larger than HBM initialize)
    params, shardings = fsdp.init_params(init_fn)
    state = train_state.TrainState.create(
        apply_fn=model.apply, params=params, tx=optax.adam(args.lr)
    )
    st_sh = fsdp.state_shardings(state, shardings)
    state = jax.device_put(state, st_sh)
    step = fsdp.make_train_step(
        make_lm_loss_fn(model, fused_ce=args.fused_ce), st_sh)

    rng = np.random.RandomState(0)
    first = last = None
    for i in range(args.steps):
        # learnable synthetic stream: next token = (token + 1) mod 16
        start = rng.randint(0, 16, (args.global_batch, 1))
        tokens = ((start + np.arange(cfg.max_len)) % 16).astype(np.int32)
        batch = {"tokens": jax.device_put(
            tokens, NamedSharding(mesh, P("data")))}
        state, m = step(state, batch)
        last = float(m["loss"])
        first = first if first is not None else last
        if i % 10 == 0:
            print(f"step {i}: loss={last:.4f}")

    emb = state.params["tok_emb"]["embedding"]
    shard_frac = emb.addressable_shards[0].data.size / emb.size
    print(f"done: loss {first:.3f} -> {last:.3f}, mesh={axis_sizes(mesh)}, "
          f"prefetch={'on' if fsdp.prefetch else 'off'}, "
          f"embedding sharding={emb.sharding.spec}, "
          f"local shard = {shard_frac:.3f} of the full table")
    if args.steps >= 20:  # short demo runs may not have converged yet
        assert last < first, "loss did not improve"


if __name__ == "__main__":
    main()
