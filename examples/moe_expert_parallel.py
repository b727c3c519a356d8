"""Expert parallelism (MoE) — beyond the reference's strategy set.

The reference shards whole *variables* across PS tasks
(tensorflow/python/training/device_setter.py:129 round-robins them over
/job:ps and moves them over gRPC every step). EP is that idea turned
TPU-native: shard whole *experts* over the ``expert`` mesh axis, and move
the **tokens** to the experts with one ``all_to_all`` each way over ICI
instead of moving parameters over the network.

    python examples/moe_expert_parallel.py --fake-devices 8
"""

import argparse
import logging
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--tokens", type=int, default=1024)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--num-experts", type=int, default=8)
    ap.add_argument("--top-k", type=int, default=2)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--fake-devices", type=int, default=0)
    args = ap.parse_args()

    if args.fake_devices:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if args.fake_devices:
        jax.config.update("jax_num_cpu_devices", args.fake_devices)
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_guide_tpu.core.dist import initialize
    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh
    from distributed_tensorflow_guide_tpu.parallel.expert import (
        ExpertParallel,
        MoEConfig,
        init_moe_params,
    )

    logging.basicConfig(level=logging.INFO, format="%(message)s")
    initialize()
    n_dev = len(jax.devices())
    n_exp_axis = min(args.num_experts, n_dev)
    while n_dev % n_exp_axis or args.num_experts % n_exp_axis:
        n_exp_axis -= 1

    cfg = MoEConfig(d_model=args.d_model, d_ff=4 * args.d_model,
                    num_experts=args.num_experts, top_k=args.top_k,
                    capacity_factor=1.5)
    mesh = build_mesh(MeshSpec(data=-1, expert=n_exp_axis))
    ep = ExpertParallel(mesh, cfg)
    params = ep.shard_params(init_moe_params(cfg, jax.random.PRNGKey(0)))
    step = ep.make_train_step(lr=args.lr)

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(args.tokens, cfg.d_model), jnp.float32)
    y = jnp.tanh(x @ jnp.asarray(rng.randn(cfg.d_model, cfg.d_model) * 0.3,
                                 jnp.float32))

    for s in range(args.steps):
        params, metrics = step(params, x, y)
        if s % 10 == 0 or s == args.steps - 1:
            logging.info(
                "step %3d  loss=%.5f  load_balance=%.3f  z=%.3f", s,
                float(metrics["loss"]), float(metrics["load_balance"]),
                float(metrics["z_loss"]))
    logging.info("experts sharded %d-way over %d devices; tokens moved via "
                 "all_to_all, parameters never moved", n_exp_axis, n_dev)


if __name__ == "__main__":
    main()
