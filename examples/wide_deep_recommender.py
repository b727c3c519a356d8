"""Judged config 4: Wide&Deep recommender — async PS replaced by synchronous
ICI allreduce.

Reference equivalent: the ParameterServerStrategy recommender workload
(tensorflow/python/distribute/parameter_server_strategy_v2.py:77): embedding
tables sharded across PS tasks, workers pushing sparse rows asynchronously.
Here the tables are dense HBM arrays updated in lockstep; the semantic delta
(what asynchrony is given up, what is kept) is docs/async_ps_semantics.md.

    python examples/wide_deep_recommender.py --steps 300 --fake-devices 8
"""

import argparse
import logging
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--global-batch", type=int, default=1024)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--log-every", type=int, default=50)
    ap.add_argument("--fake-devices", type=int, default=0)
    args = ap.parse_args()

    if args.fake_devices:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if args.fake_devices:
        jax.config.update("jax_num_cpu_devices", args.fake_devices)

    import jax.numpy as jnp
    import optax
    from flax.training import train_state

    from distributed_tensorflow_guide_tpu.core.dist import initialize
    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, axis_sizes, build_mesh
    from distributed_tensorflow_guide_tpu.data.synthetic import SyntheticCTR
    from distributed_tensorflow_guide_tpu.models.wide_deep import WideDeep, make_loss_fn
    from distributed_tensorflow_guide_tpu.parallel.data_parallel import DataParallel
    from distributed_tensorflow_guide_tpu.train import (
        LoggingHook,
        StepCounterHook,
        StopAtStepHook,
        TrainLoop,
    )

    logging.basicConfig(level=logging.INFO, format="%(message)s", force=True)
    initialize()

    vocabs = (100_000, 100_000, 10_000, 1000, 100)
    model = WideDeep(vocab_sizes=vocabs, num_dense=8, embed_dim=32,
                     mlp_dims=(256, 128))
    data = SyntheticCTR(args.global_batch, vocab_sizes=vocabs, num_dense=8)
    b0 = data.take(1)[0]
    params = model.init(
        jax.random.PRNGKey(0), jnp.asarray(b0["cat"]), jnp.asarray(b0["dense"])
    )["params"]
    n_params = sum(p.size for p in jax.tree.leaves(params))

    mesh = build_mesh(MeshSpec(data=-1))
    dp = DataParallel(mesh)
    state = dp.replicate(
        train_state.TrainState.create(
            apply_fn=model.apply, params=params, tx=optax.adam(args.lr)
        )
    )
    step = dp.make_train_step(make_loss_fn(model))
    n_dev = mesh.devices.size
    loop = TrainLoop(
        step,
        state,
        (dp.shard_batch(b) for b in data),
        hooks=[
            StopAtStepHook(args.steps),
            LoggingHook(args.log_every),
            StepCounterHook(args.log_every, batch_size=args.global_batch,
                            n_chips=n_dev),
        ],
    )
    loop.run()
    print(f"done: {loop.step} steps, {n_params/1e6:.1f}M params "
          f"(embeddings resident in HBM, no PS), mesh={axis_sizes(mesh)}")


if __name__ == "__main__":
    main()
