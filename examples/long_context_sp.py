"""Sequence/context parallelism: ring attention and Ulysses over the
``context`` mesh axis — the long-context capability the reference never had
(SURVEY.md §5 long-context row; the guide's largest model is a small CNN).

Each device holds S/n of the sequence. Ring attention rotates KV blocks
around the ICI ring (`lax.ppermute`) with an online-softmax carry; Ulysses
reshards seq <-> heads with one `all_to_all` each way. Both are verified here
against full-sequence dense attention on one device:

    python examples/long_context_sp.py --fake-devices 8 --context 8
    python examples/long_context_sp.py --fake-devices 8 --impl ulysses
"""

import argparse
import logging
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq-len", type=int, default=2048, help="global tokens")
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--context", type=int, default=-1,
                    help="context-axis size (-1: all devices)")
    ap.add_argument("--impl", choices=["ring", "ulysses", "both"],
                    default="both")
    ap.add_argument("--causal", action=argparse.BooleanOptionalAction,
                    default=True, help="--no-causal for bidirectional")
    ap.add_argument("--fake-devices", type=int, default=0)
    args = ap.parse_args()

    from benchmarks.common import device_setup

    device_setup(args.fake_devices)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map
    from distributed_tensorflow_guide_tpu.core.dist import initialize
    from distributed_tensorflow_guide_tpu.core.mesh import (
        MeshSpec,
        axis_sizes,
        build_mesh,
    )
    from distributed_tensorflow_guide_tpu.ops.attention import dense_attention
    from distributed_tensorflow_guide_tpu.parallel.sequence import (
        ring_attention,
        ulysses_attention,
    )

    logging.basicConfig(level=logging.INFO, format="%(message)s", force=True)
    initialize()

    mesh = build_mesh(MeshSpec(data=1, context=args.context))
    n_ctx = axis_sizes(mesh)["context"]
    if args.seq_len % n_ctx:
        raise SystemExit(
            f"context-axis size {n_ctx} must divide --seq-len {args.seq_len}"
        )

    r = np.random.RandomState(0)
    shape = (args.batch, args.seq_len, args.heads, args.head_dim)
    q, k, v = (jnp.asarray(r.randn(*shape).astype(np.float32)) for _ in range(3))

    # single-device oracle: full-sequence dense attention
    oracle = dense_attention(q, k, v, causal=args.causal)

    seq_sharding = NamedSharding(mesh, P(None, "context"))

    def run(name, fn):
        sharded = jax.jit(shard_map(
            lambda q, k, v: fn(q, k, v, causal=args.causal),
            mesh=mesh,
            in_specs=(P(None, "context"), P(None, "context"),
                      P(None, "context")),
            out_specs=P(None, "context"),
            check_vma=False,
        ))
        qs, ks, vs = (jax.device_put(x, seq_sharding) for x in (q, k, v))
        out = sharded(qs, ks, vs)
        err = float(jnp.max(jnp.abs(out - oracle)))
        passes = 3
        t0 = time.perf_counter()
        for _ in range(passes):
            out = sharded(qs, ks, vs)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / passes
        logging.info(
            "%s: %d tokens over %d-way context axis, max|err vs dense|=%.2e, "
            "%.1f ms/pass (per-device KV memory 1/%d of dense)",
            name, args.seq_len, n_ctx, err, dt * 1e3, n_ctx,
        )
        assert err < 2e-4, f"{name} diverged from the dense oracle"

    if args.impl in ("ring", "both"):
        run("ring attention", ring_attention)
    if args.impl in ("ulysses", "both"):
        if args.heads % n_ctx == 0:
            run("ulysses", ulysses_attention)
        else:
            logging.info("ulysses skipped: heads %d %% context %d != 0",
                         args.heads, n_ctx)
    logging.info("long-context SP ok")


if __name__ == "__main__":
    main()
