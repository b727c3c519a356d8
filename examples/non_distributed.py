"""The non-distributed control — reference ⚠ Non-Distributed-Setup/
(SURVEY.md §2a R2): a plain single-device trainer for the same model, loss,
and data stream as every distributed example. This is the baseline each
distributed configuration is diffed against: sync DP must match it to
numerical precision (tests/test_data_parallel.py), pipeline/TP within
tolerance, and the determinism gate (tests/test_aux_subsystems.py
``test_mnist_topology_determinism_gate``) runs exactly this script's train
function across {1-device, dp, dp x pp} topologies.

No mesh, no shard_map, no collectives — ``jax.jit`` on one device, the
reference's ``GradientDescentOptimizer`` loop
(tensorflow/python/training/gradient_descent.py:27) in its simplest form:

    python examples/non_distributed.py --steps 200
"""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def train(steps: int, global_batch: int, lr: float, seed: int = 0,
          log_every: int = 0):
    """Run the control trainer; returns the per-step metrics list.

    Importable (the determinism gate and parity tests call this); the CLI
    below is a thin wrapper.
    """
    import jax
    import jax.numpy as jnp
    import optax
    from flax.training import train_state

    from distributed_tensorflow_guide_tpu.data.synthetic import synthetic_mnist
    from distributed_tensorflow_guide_tpu.models.mnist_cnn import (
        MNISTCNN,
        make_loss_fn,
    )

    model = MNISTCNN()
    params = model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 28, 28, 1))
    )["params"]
    state = train_state.TrainState.create(
        apply_fn=model.apply, params=params,
        tx=optax.sgd(lr, momentum=0.9),
    )
    loss_fn = make_loss_fn(model)

    @jax.jit
    def step(state, batch):
        (loss, mets), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, batch
        )
        return state.apply_gradients(grads=grads), {"loss": loss, **mets}

    metrics = []
    data = synthetic_mnist(global_batch, seed=seed)
    for i, batch in enumerate(data.take(steps)):
        state, m = step(state, batch)
        metrics.append({k: float(v) for k, v in m.items()})
        if log_every and (i + 1) % log_every == 0:
            print(f"step {i + 1}: " +
                  " ".join(f"{k}={v:.4f}" for k, v in metrics[-1].items()))
    return metrics


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=256)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=20)
    args = ap.parse_args()

    ms = train(args.steps, args.global_batch, args.lr, args.seed,
               args.log_every)
    import jax

    print(f"platform: {jax.default_backend()} ({jax.device_count()} devices)")
    print(f"done: {len(ms)} steps, final loss {ms[-1]['loss']:.4f}, "
          f"final accuracy {ms[-1]['accuracy']:.3f}")


if __name__ == "__main__":
    main()
