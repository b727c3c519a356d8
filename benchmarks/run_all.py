#!/usr/bin/env python
"""Run the full judged-config benchmark suite; one JSON line per config.

Each bench runs in its own process (separate XLA runtime, honest timing).

    python benchmarks/run_all.py            # real numbers on the local chip
    python benchmarks/run_all.py --smoke    # tiny configs on 8 fake CPU
                                            # devices — schema/liveness check

Any other flags are forwarded to every bench verbatim.

Every bench run — smoke or real, including failures (recorded as a
skip-shaped entry) — appends one row per result line to the persisted
``bench_history/`` store (``analysis/regress.py``), the trajectory the
``dtg-lint --regress`` gate checks for measured/modeled drift. Smoke
entries can never contaminate a chip's baseline: the gate groups by
``device_kind``, and the fake-CPU smoke is its own group."""

import json
import subprocess
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from distributed_tensorflow_guide_tpu.analysis import regress  # noqa: E402

BENCHES = [
    "bench_mnist_dp.py",      # config 1
    "bench_resnet50_dp.py",   # config 2 (the flagship bench.py)
    "bench_bert_tp.py",       # config 3
    "bench_wide_deep.py",     # config 4
    "bench_gpt2_pp.py",       # config 5
    "bench_native_input.py",  # config 1 fed from the C++ record loader
    "bench_ring_attention.py",  # long-context SP: Pallas kernel vs XLA path
    "bench_moe_lm.py",        # EP model family: Switch-MoE LM tokens/sec
    "bench_fsdp_memory.py",   # FSDP: per-device state bytes vs replicated DP
    "bench_sp_comm.py",       # SP layouts: ring vs Ulysses ICI traffic
    "bench_generate.py",      # serving: KV-cache decode tokens/sec
    "bench_flash_kernel.py",  # kernel-only flash/carry roofline fractions
    "bench_fused_ce.py",      # LM-head loss alone: naive vs chunked fused CE
    "bench_comm_overlap.py",  # ICI overlap: exposed-comm fraction A/B
    "bench_resilience.py",    # checkpoint overhead + MTTR/goodput (CPU-real)
    "bench_dcn_hybrid.py",    # two-tier DCN sync tradeoff + elastic resize
    "bench_serving.py",       # serving under load: continuous vs static
    "bench_obs.py",           # flight recorder overhead + cost recon
    "bench_lint.py",          # contract linter: full program-registry audit
]

# Tiny fake-device configs, small enough for CPU (also used by
# tests/test_benchmarks.py). bench_resnet50_dp.py is excluded: it delegates
# to the flag-less repo-root bench.py, which needs the real chip.
SMOKE = {
    "bench_mnist_dp.py":
        ["--fake-devices", "8", "--global-batch", "64", "--steps", "3"],
    "bench_bert_tp.py":
        ["--fake-devices", "8", "--model-parallel", "4", "--layers", "2",
         "--small", "--global-batch", "8", "--seq-len", "64",
         "--steps", "2"],
    "bench_wide_deep.py":
        ["--fake-devices", "8", "--global-batch", "64", "--steps", "3"],
    "bench_gpt2_pp.py":
        # the full 3D smoke: dp x tp x pp with the combined interleaved-
        # 1F1B schedule — the production composition, exercised end-to-end.
        # --fused-ce on: the smoke is what exercises the fused vocab-
        # parallel CE through the whole pipeline ("auto" resolves off on
        # the fake-CPU mesh)
        ["--fake-devices", "8", "--pipe", "2", "--model-parallel", "2",
         "--schedule", "1f1b", "--virtual-chunks", "2", "--small",
         "--microbatches", "2", "--microbatch-size", "1",
         "--seq-len", "64", "--steps", "2", "--fused-ce", "on"],
    "bench_native_input.py":
        ["--fake-devices", "8", "--global-batch", "64", "--records", "512",
         "--steps", "5"],
    "bench_ring_attention.py":
        ["--fake-devices", "8", "--context", "4", "--seq-len", "512",
         "--batch", "1", "--heads", "2", "--head-dim", "16", "--iters", "2"],
    "bench_moe_lm.py":
        ["--fake-devices", "8", "--expert", "4", "--num-experts", "8",
         "--layers", "2", "--d-model", "64", "--d-ff", "128", "--heads", "4",
         "--vocab", "256", "--seq-len", "32", "--global-batch", "16",
         "--steps", "2"],
    "bench_fsdp_memory.py":
        ["--fake-devices", "8", "--layers", "2", "--d-model", "64",
         "--d-ff", "128", "--heads", "4", "--vocab", "256",
         "--seq-len", "32", "--global-batch", "8", "--steps", "1"],
    "bench_sp_comm.py":
        # S/context must be >= the 128-lane kernel block: the fwd and
        # fwd+bwd rows both lower the PALLAS ring (same-impl contract)
        ["--fake-devices", "8", "--context", "4", "--seq-len", "512",
         "--heads", "8", "--head-dim", "16"],
    "bench_resnet_native_input.py":
        # --augment: crop+flip in the C++ gather copy — the input-path
        # contract the judged ResNet config trains under (round-5).
        # --small-model + 32px: the contract is model-independent and the
        # smoke was spending ~70s compiling ResNet-50 on CPU (round-8
        # tier-1 wall-clock budget)
        ["--fake-devices", "4", "--global-batch", "16", "--records", "64",
         "--steps", "2", "--image-size", "32", "--augment",
         "--small-model"],
    "bench_generate.py":
        # all three round-11 decode levers at once (CPU liveness: int8
        # quantized cache + interpret-mode Pallas decode-attend + the
        # speculative draft/verify loop run end to end; timings
        # meaningless — the one-variable A/B rows live in run_battery)
        ["--fake-devices", "1", "--small", "--batch", "2",
         "--prompt-len", "16", "--max-new", "8", "--iters", "2",
         "--unroll", "2", "--kv-dtype", "int8", "--decode-impl", "pallas",
         "--spec-draft-layers", "1"],
    "bench_flash_kernel.py":
        # interpret-mode liveness: every kernel (fwd/dq/dkv/carry, plus
        # the decode kernel at both cache dtypes) runs end to end and
        # emits its roofline-model keys; timings meaningless. The real-
        # mode --tune decode sweep prints the skip JSON off-TPU.
        ["--fake-devices", "1", "--small", "--decode-batch", "2"],
    "bench_fused_ce.py":
        # CPU liveness: naive + fused fwd/bwd run end to end and emit the
        # closed-form traffic keys; timings meaningless (off-TPU skip-JSON
        # contract covers the no-flag real-mode path)
        ["--fake-devices", "1", "--small"],
    "bench_comm_overlap.py":
        # CPU liveness on an 8-fake-device data axis: the bucketed-overlap
        # step, the monolithic step and the no-collective floor all run
        # and the comm_bytes/exposed_comm_frac keys are emitted; timings
        # meaningless (off-TPU skip-JSON contract covers real mode)
        ["--fake-devices", "8", "--small"],
    "bench_resilience.py":
        # NOT a liveness stub: this bench is platform-independent (disk +
        # host CPU are the hardware under test), so even the smoke's small
        # geometry produces real save_overhead/MTTR/goodput numbers
        ["--small", "--seed", "0"],
    "bench_dcn_hybrid.py":
        # same contract as bench_resilience: the two-tier round timings
        # and the outer-sync byte model are real on CPU. Elastic stays
        # OFF here (the kill/regrow multiprocess phase is covered by
        # tests/test_multislice.py and the battery's dcn_hybrid
        # continuity row — re-booting JAX processes per smoke run would
        # eat the tier-1 wall-clock budget for coverage tier-1 already
        # has)
        ["--fake-devices", "8", "--small", "--seed", "0"],
    "bench_serving.py":
        # platform-independent like bench_resilience: the virtual clock
        # charges real measured launch times and skips idle, so the
        # goodput/TTFT/TPOT numbers and the continuous-vs-static A/B are
        # real on CPU (rates and SLOs self-calibrate to the machine);
        # --chaos/--snapshot-restore run the serving-under-fire phase
        # (fault storm, mid-run kill, restore) and --prefix-mix the
        # prefix-sharing/tenancy phase (cache ON vs OFF A/B + the
        # tenant-0 burst fairness leg) in the same smoke — no extra
        # compiles, the phases reuse the main engine's two programs
        # --trace-out: the flight-recorder timeline of the top-rate run,
        # self-validated (the bench exits 1 unless the written file loads
        # back as trace-event JSON with >0 complete spans)
        ["--fake-devices", "1", "--small", "--requests", "6",
         "--chaos", "--snapshot-restore", "--prefix-mix", "2",
         "--trace-out", "/tmp/dtg_bench_serving_trace.json"],
    "bench_obs.py":
        # platform-independent like bench_resilience: recorder throughput
        # and the disabled-overhead gate (<1% of a step) are host-CPU
        # numbers, and the recon phase is an abstract trace (no compile)
        ["--fake-devices", "8", "--events", "100000", "--steps", "15",
         "--small"],
    "bench_lint.py":
        # NOT a liveness stub either: lint is trace-time only, so the
        # smoke run IS the full registry audit at the pinned 8-device
        # geometry — this line is what puts dtg-lint inside tier-1.
        # --cost arms the derived-cost pins (CostSpec vs the
        # benchmarks/common.py closed forms) and the golden-fingerprint
        # drift gate in the same pass; --regress adds the continuous
        # regression gate (analysis/regress.py): its synthetic-history
        # selftest always runs (the gate itself is under test in the
        # smoke), and any persisted bench_history/ drift fails the run
        ["--fake-devices", "8", "--cost", "--regress"],
}


def main() -> int:
    here = Path(__file__).resolve().parent
    extra = sys.argv[1:]
    smoke = "--smoke" in extra
    if smoke:
        extra = [a for a in extra if a != "--smoke"]
    # device_kind comes from each bench's own line: this parent stays off
    # jax, or it would hold the chip its children need
    hist = {"git_rev": regress.git_sha()}
    failed = []
    for name in BENCHES:
        if smoke:
            if name not in SMOKE:
                continue
            args = SMOKE[name] + extra
        else:
            # bench.py (via the resnet delegator) takes no flags
            args = [] if name == "bench_resnet50_dp.py" else extra
        r = subprocess.run([sys.executable, str(here / name), *args],
                           stdout=subprocess.PIPE, text=True)
        sys.stdout.write(r.stdout)
        sys.stdout.flush()
        results = []
        for ln in r.stdout.splitlines():
            if ln.lstrip().startswith("{"):
                try:
                    results.append(json.loads(ln))
                except json.JSONDecodeError:
                    pass
        row = name.removesuffix(".py")
        for res in ([x for x in results if isinstance(x, dict)]
                    or [{"skipped": f"no result line (rc={r.returncode})"}]):
            regress.append_entry(regress.make_entry(row, res, **hist))
        if r.returncode != 0:
            failed.append(name)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
