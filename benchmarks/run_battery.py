#!/usr/bin/env python
"""On-chip capture battery: every number of record, one command.

Runs the full benchmark suite in a fixed order, each bench in its own
subprocess with a hard timeout, and appends one JSON object per bench to
``bench_results/battery_<stamp>.jsonl`` — the bench's own result line plus
{name, argv, rc, secs, tail-on-failure}. A bench that fails or hangs does
not stop the battery (partial evidence beats none), but the battery then
exits 1.

Order is by evidence value for the round: flagship ResNet first (the
driver's metric), then the compute-bound MFU configs (GPT-2 pipeline,
BERT TP), the round-4 wire-format claims (ring attention, SP comm), the
dense-attention repro, then the rest of the suite.

Use ``--only NAME...`` to re-run a subset, ``--list`` to see names.
``--row-timeout N`` caps every row at N seconds (a time-boxed capture:
a row the cap cuts off records a skip, not a failure). Every row —
including skips and timeouts — also appends one entry per result line
to the persisted ``bench_history/`` store (``analysis/regress.py``),
which is what ``dtg-lint --regress`` gates for measured/modeled drift.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from distributed_tensorflow_guide_tpu.analysis import regress  # noqa: E402

# (name, argv, timeout_s) — argv relative to repo root.
BATTERY: list[tuple[str, list[str], int]] = [
    # round 9: every DP continuity row pins --overlap off explicitly — the
    # bucketed backward all-reduce must never flip a number of record by
    # default (the round-7 one-variable lesson); the dp_overlap row below
    # is argv-identical except the knob and carries the A/B
    ("resnet_flagship", ["bench.py", "--overlap", "off"], 2400),
    # fused BN+ReLU A/B vs the flagship row above (round 8): the ONLY
    # changed variable is the BN path — same batch, same sustained mode
    ("resnet_fused_bn", ["bench.py", "--fused-bn", "--overlap", "off"],
     2400),
    # bucketed-overlap A/B vs the flagship: the one changed variable is
    # the gradient-reduction schedule (single chip: world=1 makes this a
    # no-op pair — the row exists so a multi-chip capture slots in)
    ("dp_overlap", ["bench.py", "--overlap", "on"], 2400),
    # bench_gpt2_pp's default schedule is now "auto" (GPipe at pipe=1, the
    # measured record config); the 1F1B rows pin it explicitly so the A/B
    # stays an A/B. Round 8: every continuity row ALSO pins --fused-ce off
    # — fused_ce="auto" resolves ON for TPU + GPT-2 vocab, and letting it
    # flip would change two variables at once (the round-7 schedule-pinning
    # lesson); the dedicated fused_ce rows below carry the A/B.
    ("gpt2_pp_1f1b",
     ["benchmarks/bench_gpt2_pp.py", "--schedule", "1f1b",
      "--fused-ce", "off"], 1800),
    ("gpt2_pp_interleaved_1f1b",
     ["benchmarks/bench_gpt2_pp.py", "--schedule", "1f1b",
      "--virtual-chunks", "2", "--fused-ce", "off"], 1800),
    ("gpt2_pp_gpipe",
     ["benchmarks/bench_gpt2_pp.py", "--schedule", "gpipe",
      "--fused-ce", "off"], 1800),
    # fused-CE chunk sweep FIRST (records the winning chunk into the
    # autotune table), then the pipeline A/B row: identical argv to
    # gpt2_pp_gpipe except --fused-ce on — fused CE is the only changed
    # variable vs that row. The pair adjudicates the round-8 MFU>=0.45
    # target (BASELINE.json config 5).
    ("fused_ce_kernel",
     ["benchmarks/bench_fused_ce.py", "--tune"], 1200),
    ("gpt2_pp_fused_ce",
     ["benchmarks/bench_gpt2_pp.py", "--schedule", "gpipe",
      "--fused-ce", "on"], 1800),
    ("gpt2_pp_1f1b_spc8",
     ["benchmarks/bench_gpt2_pp.py", "--schedule", "1f1b",
      "--steps-per-call", "8", "--steps", "8", "--fused-ce", "off"], 1800),
    ("gpt2_pp_1f1b_noremat",
     ["benchmarks/bench_gpt2_pp.py", "--schedule", "1f1b",
      "--no-remat", "--fused-ce", "off"], 1800),
    # kernel-only roofline + autotune FIRST: --tune records the winning
    # blocks into the persistent table; --tune-seqs covers every seq the
    # rows below key on (the table matches s exactly: 1024/2048 for the
    # gpt2_flash rows, 4096 so the single-chip ring rows — whose carry/
    # dq/dkv run at s_local = seq — hit tuned entries too). The bisect
    # instrument for the MFU-0.155 / carry-regression verdict items.
    # Prints an explicit skip line (rc=0) when no TPU transport is present.
    ("flash_kernel_roofline",
     ["benchmarks/bench_flash_kernel.py", "--tune",
      "--tune-seqs", "1024", "2048", "4096"], 2400),
    # flash rows keep --schedule 1f1b: round 5 measured MFU 0.155 under
    # the then-default 1F1B, and these rows exist to attribute MFU
    # movement to the BLOCK tuning — letting the new auto default flip
    # the schedule would change two variables at once
    ("gpt2_flash_seq1024",
     ["benchmarks/bench_gpt2_pp.py", "--schedule", "1f1b",
      "--seq-len", "1024", "--microbatch-size", "1",
      "--fused-ce", "off"], 1800),
    ("gpt2_flash_seq2048",
     ["benchmarks/bench_gpt2_pp.py", "--schedule", "1f1b",
      "--seq-len", "2048", "--microbatch-size", "1",
      "--fused-ce", "off"], 1800),
    ("bert_tp", ["benchmarks/bench_bert_tp.py"], 1800),
    # ICI overlap microbench (round 9): --tune sweeps the gradient-bucket
    # candidates and records the winner BEFORE the headline rows; each row
    # measures the full on/off/compute-floor triple and emits the
    # exposed-comm fraction + ICI roofline fields — the flag only selects
    # the headline side, so the comm_overlap_*/overlapped pairs are
    # argv-identical except the one knob
    ("comm_overlap_dp",
     ["benchmarks/bench_comm_overlap.py", "--mode", "dp", "--tune",
      "--overlap", "off", "--compress", "off"], 1800),
    ("dp_overlap_kernel",
     ["benchmarks/bench_comm_overlap.py", "--mode", "dp", "--tune",
      "--overlap", "on", "--compress", "off"], 1800),
    # int8-compressed gradient all-reduce (round 19): argv-identical to
    # dp_overlap_kernel except the wire representation — quarter the
    # grad bytes on the bucket seams + a 4-byte scale pmax per bucket
    ("dp_overlap_int8",
     ["benchmarks/bench_comm_overlap.py", "--mode", "dp", "--tune",
      "--overlap", "on", "--compress", "int8"], 1800),
    ("comm_overlap_fsdp",
     ["benchmarks/bench_comm_overlap.py", "--mode", "fsdp",
      "--fsdp-prefetch", "off"], 1800),
    ("fsdp_prefetch",
     ["benchmarks/bench_comm_overlap.py", "--mode", "fsdp",
      "--fsdp-prefetch", "on"], 1800),
    # decode continuity row (round 11): pins ALL THREE new levers off
    # explicitly — decode_impl="auto" resolves to the Pallas kernel on TPU
    # and letting it (or int8 / speculative) flip would silently move the
    # number of record (the round-7 one-variable lesson). Each lever row
    # below is argv-identical except its one knob. The decode-kernel
    # --tune sweep runs in flash_kernel_roofline ABOVE (it covers the
    # decode_attend key at both cache dtypes), so these rows pick up the
    # tuned KV block.
    ("gpt2_decode",
     ["benchmarks/bench_generate.py", "--kv-dtype", "model",
      "--decode-impl", "dense", "--spec-draft-layers", "0",
      "--weight-dtype", "model"], 1800),
    # decode-roofline A/B: scan unroll (the donation default is already on)
    ("gpt2_decode_unroll4",
     ["benchmarks/bench_generate.py", "--kv-dtype", "model",
      "--decode-impl", "dense", "--spec-draft-layers", "0",
      "--weight-dtype", "model", "--unroll", "4"], 1800),
    # one-variable lever rows vs the continuity row: quantized cache,
    # length-aware Pallas decode-attend, self-speculative decoding
    ("gpt2_decode_kv_int8",
     ["benchmarks/bench_generate.py", "--kv-dtype", "int8",
      "--decode-impl", "dense", "--spec-draft-layers", "0",
      "--weight-dtype", "model"], 1800),
    # weight-only quantized decode (round 19): per-column int8 / packed
    # int4 kernels with fused dequant — argv-identical to gpt2_decode
    # except the one knob; the params term of the roofline drops ~4x/~8x
    ("gpt2_decode_wq8",
     ["benchmarks/bench_generate.py", "--kv-dtype", "model",
      "--decode-impl", "dense", "--spec-draft-layers", "0",
      "--weight-dtype", "int8"], 1800),
    ("gpt2_decode_wq4",
     ["benchmarks/bench_generate.py", "--kv-dtype", "model",
      "--decode-impl", "dense", "--spec-draft-layers", "0",
      "--weight-dtype", "int4"], 1800),
    ("gpt2_decode_pallas",
     ["benchmarks/bench_generate.py", "--kv-dtype", "model",
      "--decode-impl", "pallas", "--spec-draft-layers", "0",
      "--weight-dtype", "model"], 1800),
    ("gpt2_decode_spec",
     ["benchmarks/bench_generate.py", "--kv-dtype", "model",
      "--decode-impl", "dense", "--spec-draft-layers", "4",
      "--weight-dtype", "model"], 1800),
    # serving-under-load rows (PR 10): the continuity row is STATIC
    # batching with every lever pinned off; each row below flips exactly
    # one knob against its neighbour (static->continuous batching,
    # whole-prompt->chunked prefill, model->int8 cache, dense->pallas
    # reads). bench_serving measures both disciplines every run, so the
    # continuity row's JSON also carries the continuous side for
    # cross-checking the A/B.
    ("serve_continuity",
     ["benchmarks/bench_serving.py", "--mode", "static",
      "--prefill-chunk", "32", "--kv-dtype", "model",
      "--decode-impl", "dense", "--weight-dtype", "model",
      "--host-blocks", "0", "--fleet", "0"], 1800),
    ("serve_paged",
     ["benchmarks/bench_serving.py", "--mode", "continuous",
      "--prefill-chunk", "32", "--kv-dtype", "model",
      "--decode-impl", "dense", "--weight-dtype", "model",
      "--host-blocks", "0", "--fleet", "0"], 1800),
    ("serve_chunked_prefill",
     ["benchmarks/bench_serving.py", "--mode", "continuous",
      "--prefill-chunk", "8", "--kv-dtype", "model",
      "--decode-impl", "dense", "--weight-dtype", "model",
      "--host-blocks", "0", "--fleet", "0"], 1800),
    ("serve_kv_int8",
     ["benchmarks/bench_serving.py", "--mode", "continuous",
      "--prefill-chunk", "32", "--kv-dtype", "int8",
      "--decode-impl", "dense", "--weight-dtype", "model",
      "--host-blocks", "0", "--fleet", "0"], 1800),
    ("serve_pallas",
     ["benchmarks/bench_serving.py", "--mode", "continuous",
      "--prefill-chunk", "32", "--kv-dtype", "model",
      "--decode-impl", "pallas", "--weight-dtype", "model",
      "--host-blocks", "0", "--fleet", "0"], 1800),
    # serving under fire (PR 11): one knob each — serve_paged + the
    # chaos storm, then + the mid-run kill/snapshot-restore leg
    ("serve_chaos",
     ["benchmarks/bench_serving.py", "--mode", "continuous",
      "--prefill-chunk", "32", "--kv-dtype", "model",
      "--decode-impl", "dense", "--weight-dtype", "model",
      "--host-blocks", "0", "--fleet", "0",
      "--chaos"], 1800),
    ("serve_snapshot_restore",
     ["benchmarks/bench_serving.py", "--mode", "continuous",
      "--prefill-chunk", "32", "--kv-dtype", "model",
      "--decode-impl", "dense", "--weight-dtype", "model",
      "--host-blocks", "0", "--fleet", "0",
      "--chaos", "--snapshot-restore"], 1800),
    # prefix sharing + tenancy (PR 12): one knob each — chunked prefill
    # + the prefix-mix phase (prefix cache ON vs OFF in one run), the
    # same under chunking-off geometry (tenancy/fair-share focus), then
    # + batched multi-LoRA decode
    ("serve_prefix_cache",
     ["benchmarks/bench_serving.py", "--mode", "continuous",
      "--prefill-chunk", "8", "--kv-dtype", "model",
      "--decode-impl", "dense", "--weight-dtype", "model",
      "--host-blocks", "0", "--fleet", "0",
      "--prefix-mix", "3"], 1800),
    ("serve_multi_tenant",
     ["benchmarks/bench_serving.py", "--mode", "continuous",
      "--prefill-chunk", "32", "--kv-dtype", "model",
      "--decode-impl", "dense", "--weight-dtype", "model",
      "--host-blocks", "0", "--fleet", "0",
      "--prefix-mix", "4"], 1800),
    ("serve_lora",
     ["benchmarks/bench_serving.py", "--mode", "continuous",
      "--prefill-chunk", "32", "--kv-dtype", "model",
      "--decode-impl", "dense", "--weight-dtype", "model",
      "--host-blocks", "0", "--fleet", "0",
      "--prefix-mix", "3", "--lora-rank", "2"], 1800),
    # cache hierarchy (PR 16): one knob each — serve_continuity + the
    # longtail phase (hierarchy ON vs pool-only OFF in one run), then
    # + the warm-restart persistence leg
    ("serve_spill",
     ["benchmarks/bench_serving.py", "--mode", "static",
      "--prefill-chunk", "32", "--kv-dtype", "model",
      "--decode-impl", "dense", "--weight-dtype", "model",
      "--host-blocks", "0", "--fleet", "0",
      "--longtail-mix", "6"], 1800),
    ("serve_warm_restart",
     ["benchmarks/bench_serving.py", "--mode", "static",
      "--prefill-chunk", "32", "--kv-dtype", "model",
      "--decode-impl", "dense", "--weight-dtype", "model",
      "--host-blocks", "0", "--fleet", "0",
      "--longtail-mix", "6", "--persist-cache"], 1800),
    # scale-out fleet (PR 18): one knob each vs serve_continuity — the
    # N-replica fleet tier (global admission/DRR/routing over stock
    # engines), + disaggregated prefill/decode roles (KV blocks shipped
    # prefill->decode, priced against the DCN roofline), + fleet-level
    # prefix routing (longest-cached-prefix replica wins)
    ("serve_fleet",
     ["benchmarks/bench_serving.py", "--mode", "static",
      "--prefill-chunk", "32", "--kv-dtype", "model",
      "--decode-impl", "dense", "--weight-dtype", "model",
      "--host-blocks", "0", "--fleet", "2"], 1800),
    ("serve_disagg",
     ["benchmarks/bench_serving.py", "--mode", "static",
      "--prefill-chunk", "32", "--kv-dtype", "model",
      "--decode-impl", "dense", "--weight-dtype", "model",
      "--host-blocks", "0", "--fleet", "2",
      "--fleet-roles", "disagg"], 1800),
    ("serve_fleet_prefix",
     ["benchmarks/bench_serving.py", "--mode", "static",
      "--prefill-chunk", "32", "--kv-dtype", "model",
      "--decode-impl", "dense", "--weight-dtype", "model",
      "--host-blocks", "0", "--fleet", "2",
      "--fleet-prefix"], 1800),
    # fleet under fire (PR 20): one knob each off serve_fleet — the
    # seeded crash/stall/torn storm (breaker, re-anchoring, exactly-once
    # adoption, MTTR + goodput-under-chaos + zero-dropped-streams), then
    # + the mid-storm fleet kill/snapshot/restore leg
    ("serve_fleet_chaos",
     ["benchmarks/bench_serving.py", "--mode", "static",
      "--prefill-chunk", "32", "--kv-dtype", "model",
      "--decode-impl", "dense", "--weight-dtype", "model",
      "--host-blocks", "0", "--fleet", "2",
      "--fleet-chaos"], 1800),
    ("serve_fleet_restore",
     ["benchmarks/bench_serving.py", "--mode", "static",
      "--prefill-chunk", "32", "--kv-dtype", "model",
      "--decode-impl", "dense", "--weight-dtype", "model",
      "--host-blocks", "0", "--fleet", "2",
      "--fleet-chaos", "--fleet-restore"], 1800),
    # MoE serving (PR 19): one knob each — serve_continuity + the MoE
    # A/B phase (expert-parallel decode vs dense at matched active
    # params), then + int8 expert banks (the wq8 diet applied to the
    # routed FFN)
    ("serve_moe",
     ["benchmarks/bench_serving.py", "--mode", "static",
      "--prefill-chunk", "32", "--kv-dtype", "model",
      "--decode-impl", "dense", "--weight-dtype", "model",
      "--host-blocks", "0", "--fleet", "0",
      "--moe", "4"], 1800),
    ("serve_moe_wq8",
     ["benchmarks/bench_serving.py", "--mode", "static",
      "--prefill-chunk", "32", "--kv-dtype", "model",
      "--decode-impl", "dense", "--weight-dtype", "int8",
      "--host-blocks", "0", "--fleet", "0",
      "--moe", "4"], 1800),
    ("ring_attention_1024",
     ["benchmarks/bench_ring_attention.py", "--seq-len", "1024"], 1500),
    ("ring_attention_2048",
     ["benchmarks/bench_ring_attention.py", "--seq-len", "2048"], 1500),
    ("ring_attention_4096",
     ["benchmarks/bench_ring_attention.py", "--seq-len", "4096"], 1500),
    # fake-8/context-4 per the bench's own docstring: the comm accounting is
    # mesh-shape math traced on virtual devices — a real single chip would
    # only yield the degenerate context=1 row (all ratios None)
    ("sp_comm", ["benchmarks/bench_sp_comm.py", "--fake-devices", "8",
                 "--context", "4"], 1200),
    ("dense_attn_repro",
     ["benchmarks/repro_dense_attn.py", "--seqs", "512", "1024",
      "--cases", "grad"], 2400),
    ("mnist_dp", ["benchmarks/bench_mnist_dp.py"], 1200),
    ("wide_deep", ["benchmarks/bench_wide_deep.py"], 1200),
    # continuity pin, same rule as the gpt2_pp rows: SwitchLM's
    # fused_ce="auto" would otherwise flip this row's loss path on TPU
    ("moe_lm", ["benchmarks/bench_moe_lm.py", "--fused-ce", "off"], 1800),
    # dropless router A/B (PR 19): argv-identical to moe_lm except the
    # one knob — capacity-factor-free dispatch, zero dropped tokens
    ("moe_dropless", ["benchmarks/bench_moe_lm.py", "--fused-ce", "off",
                      "--dropless"], 1800),
    # resilience A/B (round 10): argv-identical except the one knob — the
    # headline side of the sync/async save pair (both sides are measured in
    # each row; the knob only selects which one is `value`). Platform-
    # independent: these rows produce real numbers even off-TPU.
    ("resilience_overhead",
     ["benchmarks/bench_resilience.py", "--async-save", "on"], 1200),
    ("resilience_overhead_sync",
     ["benchmarks/bench_resilience.py", "--async-save", "off"], 1200),
    # DCN-hybrid two-tier rows (round 12). Continuity row pins EVERY new
    # knob explicitly (slices/sync-period/outer-momentum/elastic — none
    # may drift by default) and carries the elastic resize MTTR capture;
    # the sync rows are argv-identical to each other except --sync-period
    # (the round-7 one-variable convention), elastic pinned off so the
    # knob is the only difference. Platform-independent: real numbers on
    # CPU over the multiprocess runner, like the resilience rows.
    ("dcn_hybrid",
     ["benchmarks/bench_dcn_hybrid.py", "--slices", "2", "--sync-period",
      "8", "--outer-momentum", "0.9", "--elastic", "on", "--seed", "0",
      "--compress", "off"], 1800),
    ("dcn_hybrid_sync1",
     ["benchmarks/bench_dcn_hybrid.py", "--slices", "2", "--sync-period",
      "1", "--outer-momentum", "0.9", "--elastic", "off", "--seed", "0",
      "--compress", "off"], 1200),
    ("dcn_hybrid_sync8",
     ["benchmarks/bench_dcn_hybrid.py", "--slices", "2", "--sync-period",
      "8", "--outer-momentum", "0.9", "--elastic", "off", "--seed", "0",
      "--compress", "off"], 1200),
    ("dcn_hybrid_sync64",
     ["benchmarks/bench_dcn_hybrid.py", "--slices", "2", "--sync-period",
      "64", "--outer-momentum", "0.9", "--elastic", "off", "--seed", "0",
      "--compress", "off"], 1200),
    # int8-compressed outer sync (round 19): argv-identical to
    # dcn_hybrid_sync8 except the wire representation — the DiLoCo-style
    # lever quarters outer_sync_bytes on the slow DCN tier
    ("dcn_hybrid_int8_outer",
     ["benchmarks/bench_dcn_hybrid.py", "--slices", "2", "--sync-period",
      "8", "--outer-momentum", "0.9", "--elastic", "off", "--seed", "0",
      "--compress", "int8"], 1200),
    ("native_input", ["benchmarks/bench_native_input.py"], 1200),
    ("resnet_native_input",
     ["benchmarks/bench_resnet_native_input.py"], 1800),
    # static program audit (PR 13): trace-time only, so the battery row
    # is the same full-registry run as the tier-1 smoke — it rides along
    # so every on-chip capture also records the cost table and the
    # fingerprint-drift verdict for the exact tree being measured
    ("lint_cost_audit",
     ["benchmarks/bench_lint.py", "--fake-devices", "8", "--cost",
      "--regress"], 900),
]

# battery row -> the registered lint program whose trace covers the
# row's hot loop (analysis/contracts.py names). Lets the regression
# gate join a drifted row to the golden-fingerprint bless that last
# changed the trace being measured. Best-effort — rows without a traced
# program (ResNet, the input pipelines) simply have no join.
ROW_PROGRAMS: dict[str, str] = {
    "fused_ce_kernel": "fused_ce_loss_grad",
    "gpt2_pp_fused_ce": "pipeline_fused_ce_train_step",
    "comm_overlap_dp": "dp_train_step",
    "dp_overlap_kernel": "dp_overlap_train_step",
    "dp_overlap_int8": "dp_overlap_int8_round",
    "fsdp_prefetch": "fsdp_prefetch_train_step",
    "moe_lm": "moe_train_step",
    "dcn_hybrid_sync1": "multislice_outer_on_round",
    "gpt2_decode": "decode_step",
    "gpt2_decode_spec": "decode_spec_step",
    "gpt2_decode_wq8": "serve_decode_step_wq8",
    "serve_continuity": "serve_decode_step",
    "serve_paged": "serve_decode_step",
    "serve_chunked_prefill": "serve_prefill_chunk_step",
    "serve_lora": "serve_decode_step_lora",
    # fleet replicas run the SAME decode program; the disagg row's hot
    # seam is the cross-replica KV handoff, so it joins to the DCN
    # block-transfer program instead
    "serve_fleet": "serve_decode_step",
    "serve_disagg": "serve_kv_block_transfer_dcn",
    "serve_fleet_prefix": "serve_decode_step",
    # the chaos rows compile NOTHING new: crash-replacement replicas and
    # restored fleets hit the build_step_fns memo, so both join to the
    # same decode program as serve_fleet
    "serve_fleet_chaos": "serve_decode_step",
    "serve_fleet_restore": "serve_decode_step",
    "moe_dropless": "moe_dropless_train_step",
    "serve_moe": "serve_decode_step_moe",
    "serve_moe_wq8": "serve_decode_step_moe_wq8",
}


def run_one(name: str, argv: list[str], timeout: int, out, *,
            row_cap: int | None = None, hist: dict | None = None) -> bool:
    t0 = time.time()
    rec: dict = {"name": name, "argv": argv}
    eff_timeout = timeout if row_cap is None else min(timeout, row_cap)
    try:
        proc = subprocess.run(
            [sys.executable, *argv], cwd=ROOT, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            timeout=eff_timeout)
        rec["rc"] = proc.returncode
        lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
        results = []
        for ln in lines:
            if ln.lstrip().startswith("{"):
                try:
                    results.append(json.loads(ln))
                except json.JSONDecodeError:
                    pass
        rec["results"] = results
        if proc.returncode != 0 or not results:
            rec["tail"] = lines[-8:]
    except subprocess.TimeoutExpired:
        if row_cap is not None and eff_timeout < timeout:
            # the battery-wide cap expired, not the row's own budget: a
            # time-boxed capture DECIDED not to wait, so this records as
            # a skip (capable, not failed) — same contract as a bench
            # printing its own "skipped" result line
            rec["rc"] = 0
            rec["results"] = [
                {"skipped": f"row-timeout {eff_timeout}s expired"}]
        else:
            rec["rc"] = "timeout"
            rec["results"] = []
    rec["secs"] = round(time.time() - t0, 1)
    # every row leaves a history breadcrumb — skips and timeouts too
    # (continuity evidence: "the row ran and produced nothing" is a
    # different fact from "the row never ran"). append_entry is
    # best-effort by contract; bookkeeping never fails the battery.
    if hist is not None:
        hrows = [r for r in rec["results"] if isinstance(r, dict)] or [
            {"skipped": f"no result line (rc={rec['rc']})"}]
        for r in hrows:
            regress.append_entry(regress.make_entry(
                name, r, program=ROW_PROGRAMS.get(name), **hist))
    # a bench may declare itself structurally impossible on this mesh
    # (e.g. interleaved 1F1B on one chip) by printing a result line with a
    # "skipped" reason — recorded as skipped, counted as capable (the
    # 20/20 bar is "no entry that CANNOT pass", not "every entry ran")
    skips = [r["skipped"] for r in rec.get("results", [])
             if isinstance(r, dict) and r.get("skipped")]
    if rec.get("rc") == 0 and skips:
        rec["skipped"] = skips[0]
    out.write(json.dumps(rec) + "\n")
    out.flush()
    ok = rec["rc"] == 0 and rec["results"]
    status = "skipped" if rec.get("skipped") else ("ok" if ok else rec["rc"])
    print(f"[battery] {name}: {status} ({rec['secs']}s)", file=sys.stderr)
    return bool(ok)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="+", default=None,
                    help="subset of battery names")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--row-timeout", type=int, default=None,
                    help="cap every row's timeout at this many seconds; "
                         "a row the cap expires records a skip entry "
                         "(time-boxed capture), not a failure")
    ap.add_argument("--no-history", action="store_true",
                    help="skip the bench_history/ regression-gate "
                         "breadcrumbs (analysis/regress.py)")
    args = ap.parse_args()

    if args.list:
        for name, argv, t in BATTERY:
            print(f"{name}: {' '.join(argv)} (timeout {t}s)")
        return

    todo = [b for b in BATTERY if args.only is None or b[0] in args.only]
    if args.only:
        missing = set(args.only) - {b[0] for b in todo}
        if missing:
            sys.exit(f"unknown battery names: {sorted(missing)}")
    if not todo:
        # ADVICE round 5: an empty battery_*.jsonl got committed as if it
        # were evidence — never create an artifact with nothing to record
        sys.exit("run_battery: empty selection, refusing to create an "
                 "empty artifact")

    outdir = ROOT / "bench_results"
    outdir.mkdir(exist_ok=True)
    stamp = time.strftime("%Y%m%d_%H%M%S")
    path = Path(args.out) if args.out else outdir / f"battery_{stamp}.jsonl"
    # history context computed ONCE. The device_kind of each entry is the
    # bench's own (its result line): this driver never touches jax, or it
    # would hold the chip every row's subprocess needs.
    hist = None if args.no_history else {"git_rev": regress.git_sha()}
    n_ok = 0
    n_recs = 0  # bench records actually written (run_one writes one each)
    try:
        with open(path, "a") as out:
            out.write(json.dumps(
                {"battery_start": stamp, "n_benches": len(todo)}) + "\n")
            for name, argv, timeout in todo:
                n_ok += run_one(name, argv, timeout, out,
                                row_cap=args.row_timeout, hist=hist)
                n_recs += 1
    finally:
        # same ADVICE item, the belt to the selection check's suspenders:
        # the loop can die BEFORE any bench record lands (the first spawn
        # raises, ctrl-C during bench 1) and a header-only artifact reads
        # as "a battery ran here" to anyone listing bench_results/ —
        # remove it on the way out (once a real record exists the partial
        # artifact is genuine evidence and stays)
        if n_recs == 0 and path.exists():
            path.unlink()
    print(f"[battery] {n_ok}/{len(todo)} ok -> {path}", file=sys.stderr)
    if n_ok < len(todo):
        sys.exit(1)


if __name__ == "__main__":
    main()
