"""Judged config 1: MNIST CNN, synchronous data parallelism.

Reference equivalents: ⚠ Synchronous-SGD/ (SyncReplicasOptimizer barrier,
tensorflow/python/training/sync_replicas_optimizer.py:42) and the
MirroredStrategy surface (tensorflow/python/distribute/mirrored_strategy.py:200).

The reference needs a bash launcher spawning 1 PS + N worker processes with
role flags; here the SAME command runs everywhere — on the single local chip,
on a CPU fake mesh (--fake-devices 8), or on every host of a pod slice:

    python examples/mnist_sync_dp.py --steps 200
    python examples/mnist_sync_dp.py --steps 200 --fake-devices 8
"""

import argparse
import logging
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=256)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--eval-every", type=int, default=0,
                    help="held-out evaluation every N steps (always once at "
                         "the end); 0 = end-of-run only")
    ap.add_argument("--eval-batches", type=int, default=8,
                    help="batches per evaluation pass")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--fake-devices", type=int, default=0,
                    help="force N virtual CPU devices (testing without a pod)")
    ap.add_argument("--data", default=None, metavar="DIR",
                    help="directory with the standard MNIST IDX files "
                         "(train-images-idx3-ubyte[.gz], ...); imported once "
                         "into the native record format and streamed by the "
                         "C++ loader. Default: synthetic MNIST-shaped data.")
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
    from distributed_tensorflow_guide_tpu.core.mesh import (
        MeshSpec,
        axis_sizes,
        build_mesh,
    )
    from distributed_tensorflow_guide_tpu.data.synthetic import synthetic_mnist
    from distributed_tensorflow_guide_tpu.models.mnist_cnn import (
        MNISTCNN,
        make_loss_fn,
        make_metric_fn,
    )
    from distributed_tensorflow_guide_tpu.parallel.data_parallel import DataParallel
    from distributed_tensorflow_guide_tpu.train import (
        CheckpointHook,
        Checkpointer,
        EvalHook,
        Evaluator,
        LoggingHook,
        StepCounterHook,
        StopAtStepHook,
        TrainLoop,
    )

    # force=True: absl (pulled in by jax) installs a WARNING-level root
    # handler on import that would otherwise swallow INFO logs.
    logging.basicConfig(level=logging.INFO, format="%(message)s", force=True)
    initialize()

    mesh = build_mesh(MeshSpec(data=-1))
    n_dev = mesh.devices.size
    if args.global_batch % n_dev:
        raise SystemExit(f"--global-batch must divide by {n_dev} devices")

    dp = DataParallel(mesh)
    model = MNISTCNN()
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))["params"]
    state = dp.replicate(
        train_state.TrainState.create(
            apply_fn=model.apply, params=params, tx=optax.sgd(args.lr, momentum=0.9)
        )
    )

    step = dp.make_train_step(make_loss_fn(model))
    if args.data:
        # real MNIST: IDX -> record file (once), then the native mmap/
        # shuffle/prefetch loader feeds training — the reference's
        # read_data_sets + feed_dict path, TPU-track shape
        from distributed_tensorflow_guide_tpu.data.importers import (
            decode_mnist_batch,
            import_mnist,
        )
        from distributed_tensorflow_guide_tpu.data.native_loader import (
            open_record_loader,
        )
        from distributed_tensorflow_guide_tpu.data.importers import MNIST_FIELDS

        rec = import_mnist(args.data, Path(args.data) / "records")
        loader = open_record_loader(rec, MNIST_FIELDS, args.global_batch)
        print(f"native loader: {loader.num_records} records from {rec} "
              f"({type(loader).__name__})")
        data = (dp.shard_batch(decode_mnist_batch(b)) for b in loader)

        make_eval_data = None
        if args.eval_batches > 0:
            # data the optimizer never sees, streamed in-order (shuffle
            # off — eval order must not perturb results). Materialized
            # ONCE at setup: every eval pass sees the identical batches,
            # and a missing/too-small t10k split surfaces here as a
            # notice, not as a crash at the end-of-run evaluation.
            try:
                eval_rec = import_mnist(args.data,
                                        Path(args.data) / "records",
                                        split="test")
                eval_loader = open_record_loader(
                    eval_rec, MNIST_FIELDS, args.global_batch, shuffle=False)
            except (FileNotFoundError, ValueError) as e:
                print(f"held-out evaluation disabled: {e}")
            else:
                n = min(args.eval_batches, eval_loader.batches_per_epoch)
                it = iter(eval_loader)
                eval_batches = [
                    dp.shard_batch(decode_mnist_batch(next(it)))
                    for _ in range(n)
                ]
                eval_loader.close()

                def make_eval_data():
                    return eval_batches
    else:
        data = (dp.shard_batch(b) for b in synthetic_mnist(args.global_batch))

        make_eval_data = None
        if args.eval_batches > 0:
            # held-out synthetic stream: same class prototypes (same
            # task), disjoint sample draws — the synthetic train/test split
            eval_batches = [
                dp.shard_batch(b)
                for b in synthetic_mnist(args.global_batch,
                                         sample_seed=10_001).take(
                    args.eval_batches)
            ]

            def make_eval_data():
                return eval_batches

    eval_hook = None
    hooks = [StopAtStepHook(args.steps)]
    if make_eval_data is not None:
        evaluator = Evaluator(dp.make_eval_step(make_metric_fn(model)),
                              make_eval_data)
        eval_hook = EvalHook(evaluator, every_steps=args.eval_every,
                             name="mnist")
        hooks.append(eval_hook)
    if args.log_every:  # 0 = silent (smoke tests)
        hooks += [
            LoggingHook(args.log_every),
            StepCounterHook(args.log_every, batch_size=args.global_batch,
                            n_chips=n_dev),
        ]
    start_step = 0
    if args.ckpt_dir:
        ckpt = Checkpointer(args.ckpt_dir)
        if ckpt.latest_step() is not None:  # resume: restore + step counter
            start_step = ckpt.latest_step()
            state = ckpt.restore(state)
            print(f"resumed from step {start_step}")
        hooks.append(CheckpointHook(ckpt, every_steps=100))

    loop = TrainLoop(step, state, data, hooks=hooks, start_step=start_step)
    loop.run()
    tail = ""
    if eval_hook is not None and eval_hook.latest:
        tail = (f"; held-out accuracy {eval_hook.latest['accuracy']:.4f} "
                f"(loss {eval_hook.latest['loss']:.4f})")
    print(f"done: {loop.step} steps on {n_dev} device(s), mesh axes "
          f"{axis_sizes(mesh)}{tail}")


if __name__ == "__main__":
    main()
