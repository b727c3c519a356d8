#!/usr/bin/env python
"""Judged config 1: MNIST CNN, synchronous data parallelism (the
MirroredStrategy equivalent, tensorflow/python/distribute/mirrored_strategy.py:200).

Prints one JSON line; metric is global images/sec (no published reference
baseline exists — the guide never benchmarked, BASELINE.json)."""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.common import device_setup, report, time_steps


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--global-batch", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=500)
    # >1 scans that many optimizer steps per dispatch (synthetic mode: same
    # batch each inner step) — the TF steps_per_run knob; worth A/B-ing for
    # millisecond-step models, where the host's dispatch is the step. Echoed in the
    # JSON when set, so an A/B run is distinguishable from the judged config.
    ap.add_argument("--steps-per-call", type=int, default=1)
    ap.add_argument("--fake-devices", type=int, default=0)
    args = ap.parse_args()

    device_setup(args.fake_devices)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax.training import train_state

    from distributed_tensorflow_guide_tpu.core.dist import initialize
    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh
    from distributed_tensorflow_guide_tpu.models.mnist_cnn import (
        MNISTCNN,
        make_loss_fn,
    )
    from distributed_tensorflow_guide_tpu.parallel.data_parallel import (
        DataParallel,
    )

    initialize()
    mesh = build_mesh(MeshSpec(data=-1))
    dp = DataParallel(mesh)
    model = MNISTCNN()
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 28, 28, 1)))["params"]
    state = dp.replicate(train_state.TrainState.create(
        apply_fn=model.apply, params=params, tx=optax.sgd(0.05)))
    step = dp.make_train_step(make_loss_fn(model),
                              steps_per_call=args.steps_per_call)

    r = np.random.RandomState(0)
    batch = dp.shard_batch({
        "image": r.randn(args.global_batch, 28, 28, 1).astype(np.float32),
        "label": r.randint(0, 10, args.global_batch).astype(np.int32),
    })
    dt, _ = time_steps(step, state, batch, steps=args.steps)
    images = args.global_batch * args.steps * args.steps_per_call
    extra = ({} if args.steps_per_call == 1
             else {"steps_per_call": args.steps_per_call})
    report("mnist_cnn_sync_dp_throughput", images / dt, "images/sec", **extra)


if __name__ == "__main__":
    main()
