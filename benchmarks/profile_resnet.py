#!/usr/bin/env python
"""Profiler evidence for the ResNet-50 headline bench (VERDICT round-2 weak
item 1: docs claimed "backward is HBM-bound" with no trace to back it).

Runs the same step as ``bench.py --run`` under ``jax.profiler.trace`` and
prints the numbers the perf docs cite: device step time, MXU utilization,
HBM bandwidth utilization, and the top self-time ops — extracted from the
captured XPlane via xprof's own converter (the same data the TensorBoard
profile UI shows).

Usage: python benchmarks/profile_resnet.py [--steps 10] [--batch 128]
Writes the raw trace under /tmp/dtg_profile_resnet (inspectable with
TensorBoard) and prints a summary to stdout.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=256,
                    help="per-chip batch; bench.py's config (the recorded "
                         "round-3 roofline trace in docs/performance.md was "
                         "captured at 128, before the bench moved to 256)")
    ap.add_argument("--logdir", default="/tmp/dtg_profile_resnet")
    args = ap.parse_args()

    from benchmarks.common import setup_cache, time_steps

    setup_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributed_tensorflow_guide_tpu.core.dist import initialize
    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh
    from distributed_tensorflow_guide_tpu.models.resnet import (
        ResNet50,
        make_loss_fn,
    )
    from distributed_tensorflow_guide_tpu.parallel.data_parallel import (
        DataParallel,
    )
    from distributed_tensorflow_guide_tpu.train.state import TrainStateWithStats

    initialize()
    n_dev = len(jax.devices())
    mesh = build_mesh(MeshSpec(data=-1))
    dp = DataParallel(mesh)
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    variables = jax.jit(lambda rng: model.init(
        rng, jnp.zeros((1, 224, 224, 3)), train=False))(rng)
    tx = optax.sgd(0.1, momentum=0.9)
    state = dp.replicate(
        TrainStateWithStats.create(
            apply_fn=model.apply,
            params=variables["params"],
            tx=tx,
            model_state={"batch_stats": variables["batch_stats"]},
        )
    )
    step = dp.make_train_step_with_stats(make_loss_fn(model))
    r = np.random.RandomState(0)
    g = args.batch * n_dev
    batch = dp.shard_batch({
        "image": r.randn(g, 224, 224, 3).astype(np.float32),
        "label": r.randint(0, 1000, g).astype(np.int32),
    })

    # warmup/compile outside the trace
    dt, state = time_steps(step, state, batch, warmup=3, steps=3)

    with jax.profiler.trace(args.logdir):
        dt, state = time_steps(step, state, batch, warmup=0,
                               steps=args.steps)
    wall_ms = dt / args.steps * 1e3
    print(f"walltime/step: {wall_ms:.2f} ms  "
          f"({g * args.steps / dt / n_dev:.0f} images/sec/chip)")

    xplanes = sorted(glob.glob(
        os.path.join(args.logdir, "**", "*.xplane.pb"), recursive=True
    ), key=os.path.getmtime)
    if not xplanes:
        print("no xplane captured", file=sys.stderr)
        sys.exit(1)
    xplane = xplanes[-1]

    from xprof.convert import raw_to_tool_data as rtd

    # Overview page: step time breakdown + the utilization headline numbers.
    ov, _ = rtd.xspace_to_tool_data([xplane], "overview_page", {})
    ov = json.loads(ov if isinstance(ov, str) else ov.decode())

    def find(d, *keys):
        out = {}
        for entry in d if isinstance(d, list) else [d]:
            p = entry.get("p") if isinstance(entry, dict) else None
            if isinstance(p, dict):
                for k in keys:
                    if k in p:
                        out[k] = p[k]
        return out

    wanted = [
        "matrix_unit_utilization_percent",
        "mxu_utilization_percent",
        "flop_rate_utilization_relative_to_roofline",
        "memory_bw_utilization_relative_to_hw_limit",
        "device_duty_cycle_percent",
        "steptime_ms_average",
        "infeed_percent_average",
    ]
    summary = find(ov, *wanted)
    print("overview:", json.dumps(summary, indent=2, sort_keys=True))

    # Op profile: top self-time ops with per-op FLOPS + bandwidth util.
    try:
        op, _ = rtd.xspace_to_tool_data(
            [xplane], "framework_op_stats", {}
        )
        rows = json.loads(op if isinstance(op, str) else op.decode())
        if isinstance(rows, list) and len(rows) > 1:
            hdr = rows[0]
            body = rows[1:]
            idx = {name: i for i, name in enumerate(hdr)}
            tcol = next(
                (idx[c] for c in
                 ("total_self_time", "self_time_us", "totalSelfTime")
                 if c in idx), None,
            )
            if tcol is not None:
                body.sort(key=lambda r_: -float(r_[tcol] or 0))
            print("top ops by self time:")
            for r_ in body[:15]:
                print("   ", r_)
    except Exception as e:  # tool schema varies across xprof versions
        print(f"framework_op_stats unavailable: {e}", file=sys.stderr)


if __name__ == "__main__":
    main()
