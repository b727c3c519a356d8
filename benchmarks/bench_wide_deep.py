#!/usr/bin/env python
"""Judged config 4: Wide&Deep CTR recommender. The reference track is async
parameter-server training; on TPU this is synchronous ICI allreduce with the
embeddings HBM-resident (semantic delta documented in
docs/async_ps_semantics.md).

Metric: examples/sec (global)."""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.common import device_setup, report, time_steps


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--global-batch", type=int, default=8192)
    ap.add_argument("--steps", type=int, default=500)
    # see bench_mnist_dp.py: the TF steps_per_run knob, echoed when set
    ap.add_argument("--steps-per-call", type=int, default=1)
    ap.add_argument("--fake-devices", type=int, default=0)
    args = ap.parse_args()

    device_setup(args.fake_devices)
    import jax
    import jax.numpy as jnp
    import optax
    from flax.training import train_state

    from distributed_tensorflow_guide_tpu.core.dist import initialize
    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh
    from distributed_tensorflow_guide_tpu.data.synthetic import SyntheticCTR
    from distributed_tensorflow_guide_tpu.models.wide_deep import (
        WideDeep,
        make_loss_fn,
    )
    from distributed_tensorflow_guide_tpu.parallel.data_parallel import (
        DataParallel,
    )

    initialize()
    vocabs = (100_000, 100_000, 10_000, 1000, 100)
    model = WideDeep(vocab_sizes=vocabs, num_dense=8, embed_dim=32,
                     mlp_dims=(256, 128))
    data = SyntheticCTR(args.global_batch, vocab_sizes=vocabs, num_dense=8)
    b0 = data.take(1)[0]
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.asarray(b0["cat"]),
        jnp.asarray(b0["dense"]))["params"]

    mesh = build_mesh(MeshSpec(data=-1))
    dp = DataParallel(mesh)
    state = dp.replicate(train_state.TrainState.create(
        apply_fn=model.apply, params=params, tx=optax.adam(1e-3)))
    step = dp.make_train_step(make_loss_fn(model),
                              steps_per_call=args.steps_per_call)
    batch = dp.shard_batch(b0)
    dt, _ = time_steps(step, state, batch, steps=args.steps)
    examples = args.global_batch * args.steps * args.steps_per_call
    extra = ({} if args.steps_per_call == 1
             else {"steps_per_call": args.steps_per_call})
    report("wide_deep_sync_dp_throughput", examples / dt, "examples/sec",
           **extra)


if __name__ == "__main__":
    main()
