"""Judged config 2: ResNet ImageNet, synchronous data parallelism + eval.

Reference equivalent: MultiWorkerMirroredStrategy with NCCL allreduce
(tensorflow/python/distribute/collective_all_reduce_strategy.py:57,
cross_device_ops.py:961) around a Keras ResNet. Here the NCCL allreduce is
an explicit ``pmean`` over the ``data`` mesh axis inside one compiled SPMD
step (parallel/data_parallel.py), BatchNorm running stats are pmean-
synchronized rather than racing on a PS, and held-out evaluation runs the
same SPMD structure without gradients (train/evaluation.py).

No network access in this environment, so pixels are synthetic (class
prototypes + noise — learnable, deterministic); the input-path-at-scale
story lives in examples/native_data_pipeline.py and the loader benches.

    python examples/resnet_imagenet_dp.py --steps 100            # ResNet-50/224
    python examples/resnet_imagenet_dp.py --steps 30 --fake-devices 8 \
        --model small --image-size 32 --global-batch 64          # CPU smoke
"""

import argparse
import logging
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=256)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--num-classes", type=int, default=1000)
    ap.add_argument("--model", choices=["resnet50", "small"],
                    default="resnet50",
                    help="small = ResNet18-ish, for CPU smoke runs")
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--eval-every", type=int, default=0,
                    help="held-out evaluation every N steps (always once at "
                         "the end); 0 = end-of-run only")
    ap.add_argument("--eval-batches", type=int, default=4)
    ap.add_argument("--steps-per-call", type=int, default=1,
                    help="optimizer steps fused into one compiled dispatch "
                         "(lax.scan); hooks still see every step's metrics")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="device-prefetch buffers: batch N+1 transfers to "
                         "the mesh while step N computes (data/prefetch.py)")
    ap.add_argument("--overlap", choices=["auto", "on", "off"],
                    default="auto",
                    help="bucketed backward gradient all-reduce "
                         "(parallel/overlap.py; 'auto' = on for TPU — "
                         "bitwise-identical grads, collectives overlap "
                         "the remaining backward)")
    ap.add_argument("--fake-devices", type=int, default=0)
    args = ap.parse_args()

    if args.fake_devices:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if args.fake_devices:
        jax.config.update("jax_num_cpu_devices", args.fake_devices)

    import jax.numpy as jnp
    import optax

    from distributed_tensorflow_guide_tpu.core.dist import initialize
    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh
    from distributed_tensorflow_guide_tpu.data.synthetic import (
        SyntheticClassification,
    )
    from distributed_tensorflow_guide_tpu.models.resnet import (
        ResNet18ish,
        ResNet50,
        make_loss_fn,
        make_metric_fn,
    )
    from distributed_tensorflow_guide_tpu.parallel.data_parallel import (
        DataParallel,
    )
    from distributed_tensorflow_guide_tpu.train import (
        EvalHook,
        Evaluator,
        LoggingHook,
        StepCounterHook,
        StopAtStepHook,
        TrainLoop,
    )
    from distributed_tensorflow_guide_tpu.train.state import TrainStateWithStats

    logging.basicConfig(level=logging.INFO, format="%(message)s", force=True)
    initialize()

    mesh = build_mesh(MeshSpec(data=-1))
    n_dev = mesh.devices.size
    if args.global_batch % n_dev:
        raise SystemExit(f"--global-batch must divide by {n_dev} devices")

    dp = DataParallel(mesh, overlap=args.overlap)
    model_cls = ResNet50 if args.model == "resnet50" else ResNet18ish
    model = model_cls(num_classes=args.num_classes, dtype=jnp.bfloat16)

    # one compiled init: op by op, each op is a compile of its own
    variables = jax.jit(lambda rng: model.init(
        rng, jnp.zeros((1, args.image_size, args.image_size, 3)),
        train=False))(jax.random.PRNGKey(0))
    state = dp.replicate(TrainStateWithStats.create(
        apply_fn=model.apply,
        params=variables["params"],
        tx=optax.sgd(args.lr, momentum=0.9),
        model_state={"batch_stats": variables["batch_stats"]},
    ))

    k = args.steps_per_call
    step = dp.make_train_step_with_stats(
        make_loss_fn(model), steps_per_call=k,
        stacked_batch=k > 1, per_step_metrics=k > 1)

    # The input overlap stage (data/prefetch.py): host batches are packed k
    # per dispatch and device_put onto the mesh ahead of the consumer, so
    # the transfer of pack N+1 rides under the compute of pack N.
    shape = (args.image_size, args.image_size, 3)
    data = dp.prefetch(
        SyntheticClassification(args.global_batch, image_shape=shape,
                                num_classes=args.num_classes),
        depth=args.prefetch_depth, steps_per_call=k)
    eval_hook = None
    hooks = [StopAtStepHook(args.steps)]
    if args.eval_batches > 0:
        eval_batches = [
            dp.shard_batch(b)
            for b in SyntheticClassification(
                args.global_batch, image_shape=shape,
                num_classes=args.num_classes, sample_seed=10_001,
            ).take(args.eval_batches)
        ]
        evaluator = Evaluator(
            dp.make_eval_step_with_stats(make_metric_fn(model)),
            lambda: eval_batches,
        )
        eval_hook = EvalHook(evaluator, every_steps=args.eval_every,
                             name="resnet")
        hooks.append(eval_hook)
    if args.log_every:
        hooks += [
            LoggingHook(args.log_every),
            StepCounterHook(args.log_every, batch_size=args.global_batch,
                            n_chips=n_dev),
        ]

    tail_step = (dp.make_train_step_with_stats(make_loss_fn(model))
                 if k > 1 else None)
    loop = TrainLoop(step, state, data, hooks=hooks, steps_per_call=k,
                     tail_step_fn=tail_step)
    loop.run()
    tail = ""
    if eval_hook is not None and eval_hook.latest:
        tail = (f"; held-out accuracy {eval_hook.latest['accuracy']:.4f} "
                f"(loss {eval_hook.latest['loss']:.4f})")
    print(f"done: {loop.step} steps ({args.model}, {args.image_size}px) on "
          f"{n_dev} device(s); overlap={'on' if dp.overlap else 'off'}"
          f"; dispatches: {loop.dispatch_stats.as_dict()}"
          f"; prefetch: {data.stats.as_dict()}{tail}")


if __name__ == "__main__":
    main()
