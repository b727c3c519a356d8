"""End-to-end input pipeline: native C++ record loader → sync-DP training.

The reference feeds ``sess.run`` from TF's compiled input machinery; here the
native tier is ours (data/native/dataloader.cpp — mmap, global seeded
shuffle, threaded gather, prefetch ring) and the device tier is the same
shard_map+psum step as examples/mnist_sync_dp.py.

    python examples/native_data_pipeline.py --steps 100
    python examples/native_data_pipeline.py --steps 100 --fake-devices 8
"""

import argparse
import logging
import os
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=256)
    ap.add_argument("--records", type=int, default=4096)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="device-side prefetch buffers (data/prefetch.py): "
                         "batch N+1 transfers while step N computes")
    ap.add_argument("--steps-per-call", type=int, default=1,
                    help="optimizer steps per compiled dispatch; the host "
                         "packs that many loader batches into one stacked "
                         "super-batch per dispatch")
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
    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh
    from distributed_tensorflow_guide_tpu.data import (
        make_fields,
        open_record_loader,
        write_records,
    )
    from distributed_tensorflow_guide_tpu.data.synthetic import synthetic_mnist
    from distributed_tensorflow_guide_tpu.models.mnist_cnn import (
        MNISTCNN,
        make_loss_fn,
    )
    from distributed_tensorflow_guide_tpu.parallel.data_parallel import (
        DataParallel,
    )

    logging.basicConfig(level=logging.INFO, format="%(message)s")
    initialize()

    # 1. materialize a record file from the synthetic source (stand-in for
    #    the real dataset-conversion step of an ImageNet pipeline)
    fields = make_fields({"image": (np.float32, (28, 28, 1)),
                          "label": (np.int32, ())})
    src = iter(synthetic_mnist(args.records))
    full = next(src)
    tmp = Path(tempfile.mkdtemp()) / "mnist.records"
    write_records(tmp, {"image": full["image"], "label": full["label"]},
                  fields)

    # 2. native loader shards by PROCESS (multi-host: each host reads its
    #    block); within a host DataParallel shards the batch over devices.
    #    Each process draws its 1/num_processes share of the global batch.
    per_process_batch = args.global_batch // jax.process_count()
    loader = open_record_loader(
        tmp, fields, per_process_batch,
        shard_id=jax.process_index(), num_shards=jax.process_count(),
        shuffle=True, seed=0, prefetch=4, n_threads=4)
    logging.info("loader: %s, %d records, %d batches/epoch",
                 type(loader).__name__, loader.num_records,
                 loader.batches_per_epoch)

    # 3. standard sync-DP training
    mesh = build_mesh(MeshSpec(data=-1))
    dp = DataParallel(mesh)
    model = MNISTCNN()
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 28, 28, 1)))["params"]
    state = dp.replicate(train_state.TrainState.create(
        apply_fn=model.apply, params=params, tx=optax.sgd(args.lr)))
    k = args.steps_per_call
    step = dp.make_train_step(make_loss_fn(model), steps_per_call=k,
                              stacked_batch=k > 1, per_step_metrics=k > 1)

    # 4. the hot-path overlap stage: the C++ prefetch ring hides the disk,
    #    the device-prefetch iterator hides host->device transfer, and (at
    #    --steps-per-call > 1) each dispatch carries k packed batches so
    #    per-dispatch host latency is amortized inside the compiled scan.
    #    Exactly --steps optimizer steps run: full packs through the
    #    multi-step program, the steps % k stragglers through a single-step
    #    sibling (the TrainLoop tail_step_fn contract, inlined).
    import itertools

    n_full, n_tail = divmod(args.steps, k)
    source = (loader.next_batch() for _ in range(n_full * k))
    feed = dp.prefetch(source, depth=args.prefetch_depth, steps_per_call=k)

    t0 = time.perf_counter()
    loss = None
    for s, batch in zip(itertools.count(), feed):
        state, metrics = step(state, batch)
        if s % max(1, 20 // k) == 0 or (s == n_full - 1 and not n_tail):
            last = (jax.tree.map(lambda x: x[-1], metrics) if k > 1
                    else metrics)
            loss = float(last["loss"])
            logging.info("step %3d  loss=%.4f", (s + 1) * k - 1, loss)
    if n_tail:
        tail_step = dp.make_train_step(make_loss_fn(model))
        for j in range(n_tail):
            state, metrics = tail_step(
                state, dp.shard_batch(loader.next_batch()))
        loss = float(metrics["loss"])
        logging.info("step %3d  loss=%.4f", args.steps - 1, loss)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    logging.info("%.1f examples/sec/process end-to-end "
                 "(native input + device step); overlap stats: %s",
                 args.steps * per_process_batch / dt,
                 feed.stats.as_dict())
    loader.close()


if __name__ == "__main__":
    main()
