"""The async-PS family (reference R4/R5/R6) on TPU: Hogwild → gossip,
DOWNPOUR → local SGD, ADAG → accumulated adaptive.

Reference equivalents: ⚠ Hogwild/hogwild.py, ⚠ DOWNPOUR/downpour.py,
⚠ ADAG/adag.py — each there is a separate PS/worker program plus a bash
launcher; each here is ONE flag on one SPMD program:

    python examples/async_ps_family.py --algo hogwild   --fake-devices 8
    python examples/async_ps_family.py --algo downpour  --fake-devices 8
    python examples/async_ps_family.py --algo adag      --fake-devices 8
    python examples/async_ps_family.py --algo emulate-hogwild   # exact host semantics

See docs/async_ps_semantics.md for the semantic delta.
"""

import argparse
import logging
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--algo", required=True,
                    choices=["hogwild", "downpour", "adag",
                             "emulate-hogwild", "emulate-downpour", "emulate-adag"])
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--global-batch", type=int, default=256)
    ap.add_argument("--sync-period", type=int, default=4,
                    help="fetch_period equivalent for downpour/adag")
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
    import optax
    from flax.training import train_state

    from distributed_tensorflow_guide_tpu.core.dist import initialize
    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, axis_sizes, build_mesh
    from distributed_tensorflow_guide_tpu.data.synthetic import synthetic_mnist
    from distributed_tensorflow_guide_tpu.models.mnist_cnn import MNISTCNN, make_loss_fn
    from distributed_tensorflow_guide_tpu.parallel.async_ps import (
        AccumulatedAdaptive,
        GossipSGD,
        LocalSGD,
    )
    from distributed_tensorflow_guide_tpu.parallel.ps_emulator import AsyncPSEmulator

    logging.basicConfig(level=logging.INFO, format="%(message)s", force=True)
    initialize()

    model = MNISTCNN()
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))["params"]
    loss_fn = make_loss_fn(model)

    if args.algo.startswith("emulate-"):
        mode = args.algo.removeprefix("emulate-")
        data = iter(synthetic_mnist(args.global_batch // 4))

        def scalar_loss(p, b):
            return loss_fn(p, b)[0]

        em = AsyncPSEmulator(
            scalar_loss, params, n_workers=4, mode=mode, lr=args.lr,
            fetch_period=args.sync_period,
        )
        losses = em.run(
            ({"image": jnp.asarray(b["image"]), "label": jnp.asarray(b["label"])}
             for b in data),
            args.steps,
        )
        print(f"{mode} emulation: loss {losses[0]:.3f} -> {losses[-1]:.3f} "
              f"({em.pushes} PS pushes by 4 workers)")
        return

    mesh = build_mesh(MeshSpec(data=-1))
    state = train_state.TrainState.create(
        apply_fn=model.apply, params=params, tx=optax.sgd(args.lr, momentum=0.9)
        if args.algo != "adag" else optax.adam(1e-3),
    )
    data = iter(synthetic_mnist(args.global_batch))
    k = args.sync_period

    if args.algo == "hogwild":
        strat = GossipSGD(mesh)
        state = strat.distribute(state)
        step = strat.make_train_step(loss_fn)
        get_batch = lambda: strat.shard_batch(next(data))
        rounds = args.steps
    else:
        cls = LocalSGD if args.algo == "downpour" else AccumulatedAdaptive
        strat = cls(mesh, k)
        state = strat.replicate(state)
        step = strat.make_train_step(loss_fn)

        def get_batch():
            bs = [next(data) for _ in range(k)]
            sb = {key: np.stack([b[key] for b in bs]) for key in bs[0]}
            return strat.shard_batch(sb, leading_time_axis=True)

        rounds = args.steps // k

    for r in range(rounds):
        state, m = step(state, get_batch())
        if r % max(rounds // 10, 1) == 0:
            print(f"round {r}: loss={float(m['loss']):.4f}")
    if args.algo == "hogwild":
        w = strat.consensus(state)
        n = sum(x.size for x in jax.tree.leaves(w))
        print(f"consensus params: {n} weights averaged over "
              f"{axis_sizes(mesh)['data']} diverged replicas")
    print(f"done: algo={args.algo} on {mesh.devices.size} device(s)")


if __name__ == "__main__":
    main()
