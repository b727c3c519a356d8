#!/usr/bin/env python
"""Long-context ring attention: Pallas carry kernel vs the pure-XLA
blockwise path (SURVEY.md §5's designated hard native part).

Causal forward+backward through shard_map over the ``context`` axis; the
metric is tokens/sec for the Pallas implementation, with ``vs_baseline`` =
pallas/xla speedup at the same shapes (< 1.0 means XLA wins). Round-5
driver-verified on-chip numbers (B=4, H=12, D=64, bf16): seq 1024 — Pallas
87k vs XLA ~554k tok/s (0.157x); 2048 — 0.255x; 4096 — 0.487x. XLA wins at
every measured length, which is why ``ring_attention`` impl="auto" selects
it (parallel/sequence.py); the JSON line echoes what auto resolves to so a
capture can prove the policy matches the measurement.

    python benchmarks/bench_ring_attention.py --seq-len 2048
    python benchmarks/bench_ring_attention.py --fake-devices 8 --context 4
"""

import argparse
import functools
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.common import device_setup, report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq-len", type=int, default=2048,
                    help="GLOBAL sequence length (split over context axis)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--context", type=int, default=-1,
                    help="context-axis size (-1: all devices)")
    # >= 30 heavy steps amortizes the post-drain ramp (docs/performance.md);
    # the round-3 numbers of record were taken at 20 (understates, if
    # anything — the conservative direction).
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--fake-devices", type=int, default=0)
    args = ap.parse_args()

    device_setup(args.fake_devices)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from distributed_tensorflow_guide_tpu.core.dist import initialize
    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh
    from distributed_tensorflow_guide_tpu.parallel.sequence import (
        RING_AUTO_IMPL,
        ring_attention,
    )

    initialize()
    # context=-1 takes every device; otherwise data absorbs the rest
    # (specs below replicate over data, so those devices stay idle — fine
    # for a kernel bench). MeshSpec allows only one -1 axis.
    if args.context == -1:
        mesh = build_mesh(MeshSpec(data=1, context=-1))
    else:
        mesh = build_mesh(MeshSpec(data=-1, context=args.context))
    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32

    n_ctx = mesh.shape["context"]
    if args.seq_len % n_ctx or (args.seq_len // n_ctx) % 128:
        raise SystemExit(
            f"--seq-len {args.seq_len} over context={n_ctx} needs per-device "
            f"seq (= seq-len/context) to be a whole multiple of the kernel's "
            "128 block; raise --seq-len or lower --context"
        )
    r = np.random.RandomState(0)
    q = jnp.asarray(
        r.randn(args.batch, args.seq_len, args.heads, args.head_dim), dtype
    )

    def bench(impl) -> float:
        step = jax.jit(jax.value_and_grad(lambda q: jnp.sum(shard_map(
            functools.partial(ring_attention, causal=True, impl=impl),
            mesh=mesh,
            in_specs=(P(None, "context"),) * 3,
            out_specs=P(None, "context"),
            check_vma=False,
        )(q, q, q).astype(jnp.float32) ** 2)))
        loss, g = step(q)
        jax.block_until_ready(g)
        float(loss)  # warm + fence
        t0 = time.perf_counter()
        for _ in range(args.iters):
            loss, g = step(q)
        float(loss)
        np.asarray(jax.device_get(jax.tree.leaves(g)[0][0, 0, 0, :1]))
        dt = (time.perf_counter() - t0) / args.iters
        return args.batch * args.seq_len / dt

    tok_pallas = bench("pallas")
    tok_xla = bench("xla")
    # auto's pick is read from the policy's single source of truth
    # (sequence.RING_AUTO_IMPL) and echoed with both measured rates, so
    # the capture itself proves whether auto selected the faster path
    auto_is_faster = (tok_xla >= tok_pallas) == (RING_AUTO_IMPL == "xla")
    report("ring_attention_pallas_throughput", tok_pallas, "tokens/sec",
           baseline=tok_xla,
           xla_tokens_per_sec=round(tok_xla, 1),
           auto_impl=RING_AUTO_IMPL,
           auto_selected_measured_winner=bool(auto_is_faster))


if __name__ == "__main__":
    main()
