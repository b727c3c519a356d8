#!/usr/bin/env python
"""Headline benchmark — ResNet-50 synthetic-ImageNet images/sec/chip.

This is BASELINE.json's metric: "ResNet-50 ImageNet images/sec/chip;
step-time parity vs 8xA100 NCCL". The baseline constant below is the
per-GPU ResNet-50 training throughput of an 8xA100 DGX with NCCL allreduce
and mixed precision (~22k images/sec total => 2770 images/sec/GPU, MLPerf
class numbers); vs_baseline >= 1.0 means step-time parity per chip. It is a
label on this cell's history, not a target.

``python bench.py`` runs the benchmark in THIS process — the chip belongs
to one process at a time — and prints exactly ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
     "platform": ..., "device_kind": ..., "device_count": N, ...}
"""

from __future__ import annotations

import argparse
import os
import sys

A100_IMAGES_PER_SEC_PER_GPU = 2770.0


def run_bench(*, fused_bn: bool, overlap: str) -> None:
    from benchmarks.common import (
        device_setup,
        mfu_extras,
        model_flops_per_step,
        report,
        time_steps,
        time_steps_sustained,
    )

    device_setup()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributed_tensorflow_guide_tpu.core.dist import initialize
    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh
    from distributed_tensorflow_guide_tpu.models.resnet import ResNet50, make_loss_fn
    from distributed_tensorflow_guide_tpu.parallel.data_parallel import DataParallel
    from distributed_tensorflow_guide_tpu.train.state import TrainStateWithStats

    initialize()
    n_dev = len(jax.devices())
    # 256/chip: measured +8% over 128 (interleaved A/B trials, round 3 —
    # amortizes per-op overheads on the HBM-bound backward; 512 regresses).
    # BENCH_BATCH / BENCH_REMAT are A/B knobs (defaults = judged config).
    per_chip_batch = int(os.environ.get("BENCH_BATCH", "256"))
    remat = os.environ.get("BENCH_REMAT", "0") == "1"
    # ``fused_bn`` (round 8): FusedBatchNormAct folds the normalize-
    # activate pair and reduces batch stats over the bf16 activations —
    # attacks the trace-proven backward BN/conv HBM re-reads. ``overlap``
    # (round 9): per-bucket custom_vjp markers emit each gradient bucket's
    # pmean mid-backward so XLA can overlap it with the remaining backward
    # compute (parallel/overlap.py); on ONE chip the data axis has no wire
    # traffic to hide. Both off in the judged config; the battery rows
    # resnet_fused_bn / dp_overlap pin them on, echoed in the JSON line
    # like every A/B knob.
    global_batch = per_chip_batch * n_dev
    image_size = 224

    # BENCH_MODE: "sustained" (default, round-6 record methodology) times a
    # multi-step-dispatch program over PAIRED windows so the fixed
    # drain-refill ramp cancels (benchmarks/common.py time_steps_sustained)
    # — the measured sustained rate the round-5 verdict asked for instead
    # of the marginal-cost inference; "windows" is the round-5 3x120-step
    # median, kept for A/B continuity.
    mode = os.environ.get("BENCH_MODE", "sustained")
    steps_per_call = int(os.environ.get("BENCH_SPC", "8"))

    mesh = build_mesh(MeshSpec(data=-1))
    dp = DataParallel(mesh, overlap=overlap)
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16, remat=remat,
                     fused_bn=fused_bn)

    # one compiled init, not an op-by-op one: each eager op is a compile of
    # its own on a cold chip
    variables = jax.jit(lambda rng: model.init(
        rng, jnp.zeros((1, image_size, image_size, 3)), train=False))(
            jax.random.PRNGKey(0))
    params = variables["params"]
    model_state = {"batch_stats": variables["batch_stats"]}
    tx = optax.sgd(0.1, momentum=0.9)
    state = dp.replicate(
        TrainStateWithStats.create(
            apply_fn=model.apply, params=params, tx=tx, model_state=model_state
        )
    )

    step = dp.make_train_step_with_stats(
        make_loss_fn(model),
        steps_per_call=steps_per_call if mode == "sustained" else 1,
    )

    # One fixed on-device batch: the bench measures compute+collectives, not
    # host data generation (data/ pipelines are benchmarked separately).
    rng_np = np.random.RandomState(0)
    batch = dp.shard_batch(
        {
            "image": rng_np.randn(global_batch, image_size, image_size, 3).astype(
                np.float32
            ),
            "label": rng_np.randint(0, 1000, global_batch).astype(np.int32),
        }
    )

    # Timing is closed by benchmarks/common.py ``fence``: host fetches that
    # data-depend on the final step's loss AND updated params (the steps
    # chain through ``state``), then ``block_until_ready`` on the state.
    #
    # "windows" mode: THREE independent 120-step windows, median + spread
    # reported; every window starts from a drained device, so its first
    # steps refill the dispatch pipeline, and a long window amortizes that.
    # BENCH_STEPS/BENCH_TRIALS: smoke/A-B knobs (CPU can't run the judged
    # 3x120 windows); defaults are the judged methodology.
    n_steps = int(os.environ.get("BENCH_STEPS", "120"))
    n_trials = int(os.environ.get("BENCH_TRIALS", "3"))
    trial_tput: list[float] = []
    extras: dict = {}
    # dispatch/host-gap accounting over every timed window: the number that
    # shows what multi-step dispatch amortizes (utils/profiling.py)
    from distributed_tensorflow_guide_tpu.utils.profiling import (
        DispatchStats,
    )

    dstats = DispatchStats()
    if mode == "sustained":
        # windows in DISPATCH units; the long window covers ~n_steps
        # optimizer steps, the short one a quarter of that, so the
        # difference (the measurement) spans >= half the old window budget.
        d_long = max(2, round(n_steps / steps_per_call))
        d_short = max(1, d_long // 4)
        detail = None
        warm = 1  # one multi-step dispatch = steps_per_call warm steps
        for _ in range(n_trials):
            marginal, detail, state = time_steps_sustained(
                step, state, batch, warmup=warm,
                dispatches_short=d_short, dispatches_long=d_long,
                steps_per_call=steps_per_call, stats=dstats)
            warm = 0
            if marginal > 0:
                trial_tput.append(per_chip_batch / marginal)
            else:
                # degenerate on sub-ms CPU smoke steps (noise exceeds the
                # window delta): fall back to the long window's average
                w = detail["window_long"]
                trial_tput.append(
                    per_chip_batch * w["steps"] / w["secs"])
        extras = {"mode": "sustained", "steps_per_call": steps_per_call,
                  **(detail or {})}
    else:
        dt, state = time_steps(step, state, batch, warmup=3, steps=n_steps,
                               stats=dstats)
        trial_tput.append(global_batch * n_steps / dt / n_dev)
        for _ in range(n_trials - 1):
            dt, state = time_steps(step, state, batch, warmup=0,
                                   steps=n_steps, stats=dstats)
            trial_tput.append(global_batch * n_steps / dt / n_dev)
        extras = {"mode": "windows"}
    dstats.steps = dstats.dispatches * (
        steps_per_call if mode == "sustained" else 1)
    extras.update(dstats.as_dict())
    # the same numbers through the unified metrics plane (obs/metrics.py):
    # one namespace for what the ad-hoc dicts carry per-bench
    from distributed_tensorflow_guide_tpu.obs.metrics import (
        Registry,
        absorb_dispatch,
    )

    obs_reg = Registry()
    absorb_dispatch(obs_reg, dstats)
    extras["obs_metrics"] = obs_reg.snapshot()
    trial_tput.sort()
    median = trial_tput[len(trial_tput) // 2]
    spread_pct = 100.0 * (trial_tput[-1] - trial_tput[0]) / median

    # MFU accounting (model-FLOP convention: 3x the traced forward; conv +
    # dot FLOPs from the jaxpr walker — abstract trace, no compile). Per
    # chip: the forward is traced on the per-chip batch.
    loss_fn = make_loss_fn(model)
    abstract_batch = {
        "image": jax.ShapeDtypeStruct(
            (per_chip_batch, image_size, image_size, 3), jnp.float32),
        "label": jax.ShapeDtypeStruct((per_chip_batch,), jnp.int32),
    }
    p_abs, ms_abs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (params, model_state))
    step_flops = model_flops_per_step(loss_fn, p_abs, ms_abs, abstract_batch)
    # median is img/s/chip; one "step" here = one per-chip batch
    dt_per_step = per_chip_batch / median
    report(
        "resnet50_synthetic_imagenet_throughput", median, "images/sec/chip",
        baseline=A100_IMAGES_PER_SEC_PER_GPU,
        trials=[round(t, 1) for t in trial_tput],
        spread_pct=round(spread_pct, 1),
        # echo the A/B knobs so an experiment run can never be mistaken
        # for the judged config (256, no remat)
        per_chip_batch=per_chip_batch,
        remat=remat,
        fused_bn=fused_bn,
        overlap=dp.overlap,
        **extras,
        **mfu_extras(step_flops, 1, dt_per_step, a100_mfu=None),
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    # the battery passes argv, not env; the env spelling stays for shells
    ap.add_argument("--fused-bn", action="store_true",
                    default=os.environ.get("BENCH_FUSED_BN", "0") == "1")
    ap.add_argument("--overlap", choices=("on", "off", "auto"),
                    default=os.environ.get("BENCH_OVERLAP", "off"))
    args = ap.parse_args()
    run_bench(fused_bn=args.fused_bn, overlap=args.overlap)
    return 0


if __name__ == "__main__":
    sys.exit(main())
