#!/usr/bin/env python
"""Serving under load: continuous batching + paged KV vs static batching.

A seeded deterministic load generator (Poisson arrivals, a fixed
prompt/output length mix) drives the serve engine through a VIRTUAL
clock: arrival times are synthetic, but every program launch is charged
its real measured wall time, and idle periods fast-forward to the next
arrival instead of sleeping. That makes the bench platform-independent —
it reports real numbers on CPU — while exercising exactly the scheduling
behaviour that matters at load: admission mid-flight, chunked prefill
interleaved with decode, block growth and preemption.

Both serving disciplines are measured every run at the top offered rate
(the A/B is in the JSON line, the ``--mode`` flag only picks which side
is the headline):

* ``static`` — the continuity baseline: requests are batched by prompt
  length through the one-shot ``make_generate_fn`` program; a batch
  decodes to its LONGEST request's budget (overshoot truncated — the
  prefix property keeps per-request tokens valid) and nothing joins
  mid-flight.
* ``continuous`` — the paged engine: fixed-slot decode batch, paged KV
  pool, queued prompts admitted the tick a slot frees.

Offered rates and SLOs are derived from the machine itself (a calibration
drain measures the engine's service capacity and a single-request run its
unloaded TTFT/TPOT), so the same invocation is meaningful on a laptop CPU
and a v5e: rates are ``--load-factors`` x capacity, SLOs are
``--slo-ttft-x`` / ``--slo-tpot-x`` multiples of unloaded latency.
Goodput counts only tokens of requests that met BOTH SLOs.

The headline metric is goodput at the highest offered rate;
``vs_baseline`` (continuous mode) is continuous/static at that rate —
the paged+continuous side strictly improving it is the point.

``--chaos`` adds a serving-under-fire phase (PR 11): the same top-rate
mix driven through a fresh engine with a seeded fault storm
(:meth:`FaultSchedule.random_serve` — injected step exceptions, client
abandons, arrival bursts, pool-pressure spikes) plus admission control
(``max_queue``). ``--snapshot-restore`` additionally snapshots the
engine every few ticks, kills it mid-run at ~1/3 of total token
progress, restores a fresh engine from the latest valid snapshot and
finishes the workload. Reported: ``recovery_mttr_s`` (virtual seconds
from kill until token progress catches back up to the kill point),
``goodput_under_chaos_frac`` (chaos goodput / clean goodput at the same
rate), ``shed_rate`` and the ``zero_dropped_streams`` verdict (every
workload request reaches a terminal state — completed, cancelled,
expired or shed — none silently vanish, even through the kill).

``--longtail-mix N`` adds the cache-hierarchy phase (PR 16): N
multi-turn interactive sessions — each turn's prompt is the previous
turn's prompt plus the engine's own greedy reply plus a fresh suffix —
with cohort-scale idle think-time between turns, driven at the top
calibrated rate through hierarchy ON (``host_blocks`` > 0) and OFF
engines in one invocation. The sessions' combined context exceeds the
pool, so the OFF side destroys cold prefixes (re-prefill on the next
turn) while the ON side demotes them to host RAM and swaps them back
through the prefix-claim path. Reported: goodput A/B, spill counters,
modeled-vs-traced swap bytes (h2d equality is exact; d2h may dedup
COW-shared blocks) and ``spill_streams_bitwise_identical`` — the
hierarchy moves COST, never CONTENT. ``--persist-cache`` adds the
warm-restart leg: the warm cache (spilled blocks + trie) snapshots to
disk, restores into a fresh engine, and every session's final turn
replays with zero cached-prefix re-prefill.

``--fleet N`` adds the scale-out phase (PR 18): the top-rate mix drives
an N-replica :class:`FleetScheduler` — global admission, fleet-wide
per-tenant DRR and request->replica routing over N stock engines running
the same two jitted serve programs — against the single-engine side
already measured, in one invocation. ``--fleet-roles disagg`` splits
prefill and decode roles: each stream's written KV blocks are exported
at the phase flip and shipped to a decode replica (counted, priced
against the DCN roofline, reconciled by ``obs/recon``).
``--fleet-prefix`` routes each request to the replica holding its
longest cached prefix, pinned by a repeat wave. Reported:
``fleet_goodput_gain`` vs the single engine, the disagg TTFT/TPOT
split, ``prefix_route_hits`` and the migrated-stream bitwise verdict —
placement moves COST, never CONTENT.

``--fleet-chaos`` adds the fleet-under-fire leg (PR 20): a seeded
``random_fleet`` storm (replica hard-crashes, watchdog stalls, torn
migration handoffs) burns the same workload on a deterministic
per-tick virtual clock, against a storm-free clean leg. Reported:
``recovery_mttr_s`` (replica down -> routable again),
``goodput_under_chaos_frac`` (clean span / chaos span),
``zero_dropped_streams`` (every stream completes bitwise vs the clean
leg), and the fleet event-signature determinism pin (two runs of the
same seed, equal signatures). ``--fleet-restore`` adds the mid-storm
kill: at 1/3 of the workload's tokens the fleet snapshots through the
PR-5 manifested/CRC path and a fresh fleet restores and finishes —
still bitwise vs the clean leg.

``--moe E`` adds the MoE A/B phase (PR 19): the model is rebuilt with E
routed experts at the dense FFN width (top-1 routing = matched ACTIVE
params per token, E x the held weights) and the top-rate arrival mix
drives an MoE engine through the same two fixed-slot serve programs.
A hot expert past ``--moe-capacity`` stalls its extra slots one tick
each (degrade-to-overflow: goodput bends, tokens never drop or
corrupt). Reported: MoE-vs-dense goodput at the same offered rate,
per-expert load/overflow, stall ticks, and the expert all-to-all a
one-expert-per-device placement would pay — priced forward-only
(``passes=2``) by the same ``moe_all_to_all_bytes`` closed form the
training bench pins, reconciled by ``obs/recon``.
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.common import device_setup, report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["static", "continuous"],
                    default="continuous",
                    help="which serving discipline is the headline; the "
                         "other side is still measured at the top rate "
                         "for the A/B keys")
    ap.add_argument("--requests", type=int, default=12,
                    help="requests per offered rate")
    ap.add_argument("--load-factors", default="0.25,0.5,1.0",
                    help="offered rates as multiples of the calibrated "
                         "service capacity (>=3 for the rate sweep)")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode batch width (resident requests)")
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--num-blocks", type=int, default=65,
                    help="pool size incl. the trash block; the default "
                         "fits the full-size length mix's longest draw "
                         "(256 prompt + 192 decode = 56 blocks) — the "
                         "old 33 made the admission gate reject it")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prefill chunk width; the default covers the "
                         "whole length mix in one chunk (chunking OFF), "
                         "a small value (e.g. 8) interleaves long "
                         "prompts with decode (chunking ON)")
    ap.add_argument("--kv-dtype", choices=["model", "int8"],
                    default="model")
    ap.add_argument("--decode-impl", choices=["auto", "dense", "pallas"],
                    default="auto")
    ap.add_argument("--weight-dtype", choices=["model", "int8", "int4"],
                    default="model",
                    help="projection-weight storage for BOTH serving "
                         "sides (the A/B stays apples-to-apples): "
                         "'int8'/'int4' serves per-column-quantized "
                         "kernels with dequant fused into each matmul")
    ap.add_argument("--slo-ttft-x", type=float, default=10.0,
                    help="TTFT SLO as a multiple of unloaded TTFT")
    ap.add_argument("--slo-tpot-x", type=float, default=6.0,
                    help="TPOT SLO as a multiple of unloaded TPOT")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chaos", action="store_true",
                    help="add the serving-under-fire phase: the top-rate "
                         "mix against a seeded fault storm + admission "
                         "control")
    ap.add_argument("--snapshot-restore", action="store_true",
                    help="with the chaos phase: periodic engine "
                         "snapshots, a mid-run kill, restore from the "
                         "latest valid snapshot (implies --chaos)")
    ap.add_argument("--prefix-mix", type=int, default=0, metavar="N",
                    help="add the prefix-sharing phase (PR 12): N "
                         "tenants share a common system prompt; the same "
                         "top-rate mix runs prefix-cache ON vs OFF in "
                         "one invocation (TTFT A/B + tokens saved), "
                         "plus a tenant-0 burst under a slots quota "
                         "(fair-share bound)")
    ap.add_argument("--host-blocks", type=int, default=0,
                    help="host-RAM spill tier capacity in KV blocks for "
                         "every engine in the run (0 = hierarchy off, "
                         "the pool-only legacy paths); the longtail "
                         "phase's ON side defaults to 4x --num-blocks "
                         "when this is 0")
    ap.add_argument("--longtail-mix", type=int, default=0, metavar="N",
                    help="add the cache-hierarchy phase (PR 16): N "
                         "multi-turn interactive sessions with long "
                         "idle think-time gaps drive the engine at the "
                         "top calibrated rate, hierarchy ON vs OFF in "
                         "one invocation — goodput A/B, spill counters, "
                         "modeled-vs-traced swap bytes and the bitwise "
                         "stream cross-check")
    ap.add_argument("--persist-cache", action="store_true",
                    help="with --longtail-mix: snapshot the warm cache "
                         "(spilled blocks + trie) at the end of the ON "
                         "run, restore it into a fresh engine and "
                         "replay every session's final turn — pins "
                         "zero cached-prefix re-prefill")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="add the scale-out phase (PR 18): the top-rate "
                         "mix drives an N-replica FleetScheduler (global "
                         "admission + per-tenant DRR + routing over "
                         "stock engines) against the single-engine side "
                         "already measured — fleet goodput A/B, the "
                         "disagg TTFT/TPOT split, prefix-route hits and "
                         "the migrated-stream bitwise verdict (0 = off)")
    ap.add_argument("--fleet-roles", choices=["colocated", "disagg"],
                    default="colocated",
                    help="fleet placement policy: 'disagg' alternates "
                         "prefill/decode roles and ships each stream's "
                         "KV blocks prefill->decode at the phase flip "
                         "(counted and priced against the DCN roofline)")
    ap.add_argument("--fleet-chaos", action="store_true",
                    help="fleet-under-fire leg (PR 20): a seeded "
                         "replica_crash/replica_stall/migration_torn "
                         "storm over the fleet, reporting "
                         "recovery_mttr_s, goodput_under_chaos_frac and "
                         "zero_dropped_streams, with the fleet event "
                         "signature pinned deterministic per seed "
                         "(implies --fleet 2 when --fleet is off)")
    ap.add_argument("--fleet-restore", action="store_true",
                    help="with --fleet-chaos: kill the fleet at 1/3 of "
                         "its tokens mid-storm, fleet-snapshot, restore "
                         "into a fresh fleet and finish — every stream "
                         "must complete bitwise vs the clean leg")
    ap.add_argument("--fleet-prefix", action="store_true",
                    help="fleet-level prefix routing: requests route to "
                         "the replica holding their longest cached "
                         "prefix (turns the per-replica prefix cache on)")
    ap.add_argument("--moe", type=int, default=0, metavar="E",
                    help="add the MoE A/B phase (PR 19): rebuild the "
                         "model with E routed experts at the DENSE FFN "
                         "width (top-1 routing = matched active params "
                         "per token), drive the top-rate arrival mix "
                         "through an MoE engine, and report MoE-vs-dense "
                         "goodput plus per-expert load/overflow, with "
                         "the expert all-to-all priced by "
                         "moe_all_to_all_bytes and reconciled by "
                         "obs/recon")
    ap.add_argument("--moe-capacity", type=int, default=0, metavar="C",
                    help="decode expert capacity per launch (0 = auto: "
                         "ceil(2*slots/E)); a hot expert past C stalls "
                         "its extra slots one tick (degrade, never "
                         "drop)")
    ap.add_argument("--lora-rank", type=int, default=0,
                    help="serve the continuous side multi-LoRA: each "
                         "request decodes under adapter rid %% 4 (0 = "
                         "base) through the gathered-delta step programs")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace-event JSON of the "
                         "top-rate continuous run (per-slot request "
                         "timelines, queue-wait bars, lifecycle instants) "
                         "plus a ttft_breakdown in the JSON line")
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--fake-devices", type=int, default=0)
    args = ap.parse_args()

    device_setup(args.fake_devices)
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_guide_tpu.models.generation import (
        decode_cache_bytes_per_step,
        decode_hbm_bytes_per_step,
        make_generate_fn,
        paged_decode_cache_bytes_per_step,
    )
    from distributed_tensorflow_guide_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
        gpt2_124m,
    )
    from distributed_tensorflow_guide_tpu.serve.engine import (
        Request,
        ServeEngine,
    )

    # ---- model + workload mix ------------------------------------------
    if args.small:
        cfg = TransformerConfig(
            vocab_size=1024, num_layers=2, num_heads=4, d_model=128,
            d_ff=512, max_len=64, causal=True, dtype=jnp.float32)
        plens, pmix = (8, 16, 32), (0.5, 0.3, 0.2)
        mnews, mmix = (8, 24), (0.6, 0.4)
    else:
        cfg = dataclasses.replace(gpt2_124m(), max_len=1024)
        plens, pmix = (64, 128, 256), (0.5, 0.3, 0.2)
        mnews, mmix = (64, 192), (0.6, 0.4)
    wq = args.weight_dtype if args.weight_dtype != "model" else None
    if wq and args.lora_rank:
        raise SystemExit("--weight-dtype and --lora-rank are mutually "
                         "exclusive (no f32 kernel for the deltas)")
    if args.moe and args.lora_rank:
        raise SystemExit("--moe and --lora-rank are mutually exclusive "
                         "(no adapter targets in the routed FFN)")
    if args.moe == 1:
        raise SystemExit("--moe needs >= 2 experts (1 expert is the "
                         "dense model)")
    if args.fleet_restore and not args.fleet_chaos:
        raise SystemExit("--fleet-restore requires --fleet-chaos (it is "
                         "the storm's mid-run kill/restore leg)")
    if args.fleet_chaos and not args.fleet:
        args.fleet = 2  # the storm needs a fleet to burn
    cfg = dataclasses.replace(
        cfg,
        kv_dtype="int8" if args.kv_dtype == "int8" else None,
        decode_impl=args.decode_impl,
        weight_dtype=wq)
    # init the f32 sibling, then quantize post-hoc (the checkpoint flow)
    model = Transformer(dataclasses.replace(cfg, weight_dtype=None))
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0),
        jnp.zeros((1, cfg.max_len), jnp.int32))["params"]
    if wq:
        from distributed_tensorflow_guide_tpu.ops import quant

        params = quant.quantize_params(params, bits=8 if wq == "int8"
                                       else 4)

    # multi-LoRA: the continuous side's config gains the delta banks;
    # the static baseline stays the base model (adapter 0 is bitwise
    # base, so the A/B is still apples-to-apples for tagged requests)
    n_adapters = 3 if args.lora_rank else 0
    serve_cfg, bank = cfg, None
    if args.lora_rank:
        from distributed_tensorflow_guide_tpu.serve.engine import (
            init_adapter_bank,
        )

        serve_cfg = dataclasses.replace(
            cfg, lora_rank=args.lora_rank, lora_adapters=n_adapters)
        leaves, treedef = jax.tree.flatten(init_adapter_bank(serve_cfg))
        keys = jax.random.split(jax.random.PRNGKey(args.seed + 3), len(leaves))
        bank = jax.tree.unflatten(treedef, [
            (0.02 * jax.random.normal(k, l.shape, l.dtype)).at[0].set(0.0)
            for k, l in zip(keys, leaves)])

    def adapter_of(rid):
        return rid % (n_adapters + 1) if args.lora_rank else 0

    def make_workload(rate, n, tag):
        """Deterministic per-rate trace: a fresh seeded stream makes the
        LENGTH/token sequence identical across rates (same draw order),
        only the arrival spacing scales with the rate."""
        rng = np.random.RandomState(args.seed * 7919 + 13)
        now, out = 0.0, []
        for i in range(n):
            now += rng.exponential(1.0 / rate)
            P = int(rng.choice(plens, p=pmix))
            M = int(rng.choice(mnews, p=mmix))
            toks = rng.randint(0, cfg.vocab_size, P).astype(np.int32)
            out.append((tag * 100000 + i, now, toks, M))
        return out

    # ---- continuous side ------------------------------------------------
    # flight recorder (PR 14): observe-only; the engine stamps events
    # with the bench's VIRTUAL clock, so the exported timeline shows the
    # same seconds the latency numbers are computed in
    rec = None
    if args.trace_out:
        from distributed_tensorflow_guide_tpu.obs import (
            events as obs_events,
        )

        rec = obs_events.FlightRecorder(capacity=1 << 16)
    eng = ServeEngine(serve_cfg, params, slots=args.slots,
                      num_blocks=args.num_blocks,
                      block_size=args.block_size,
                      prefill_chunk=args.prefill_chunk,
                      temperature=0.0, adapters=bank, recorder=rec,
                      host_blocks=args.host_blocks)
    if args.persist_cache and not args.longtail_mix:
        raise SystemExit("--persist-cache requires --longtail-mix")

    def drive(workload, e=None):
        """Virtual clock: launches charged their measured wall time,
        idle gaps skipped. Returns (events, mean live blocks)."""
        e = eng if e is None else e
        for rid, arr, toks, M, *rest in workload:
            e.submit(Request(rid=rid, prompt=toks, max_new_tokens=M,
                             rng=jax.random.PRNGKey(rid % (1 << 20)),
                             arrival=arr, adapter=adapter_of(rid),
                             tenant=rest[0] if rest else 0))
        now, events, live = 0.0, [], []
        while e.sched.has_queued or e.sched.has_resident:
            t0 = time.perf_counter()
            evs, kind = e.step(now)
            dt = time.perf_counter() - t0
            if kind == "idle":
                nxt = e.sched.next_arrival()
                if nxt is None:
                    break
                now = max(now, nxt)
                continue
            now += dt
            live.append(e.live_blocks())
            events.extend(dataclasses.replace(ev, time=now) for ev in evs)
        return events, (sum(live) / len(live) if live else 0.0)

    def latencies(events, workload):
        arr = {w[0]: w[1] for w in workload}
        firsts, lasts, counts = {}, {}, {}
        for e in events:
            if e.rid not in arr:
                continue  # warmup / calibration residue
            if e.token < 0 or e.status != "ok":
                continue  # terminal pseudo-events carry no token
            if e.first:
                firsts[e.rid] = e.time
            lasts[e.rid] = e.time
            counts[e.rid] = counts.get(e.rid, 0) + 1
        out = []
        for rid, a in arr.items():
            if rid not in firsts:
                continue
            n = counts[rid]
            tpot = ((lasts[rid] - firsts[rid]) / (n - 1)) if n > 1 else 0.0
            out.append((firsts[rid] - a, tpot, n, lasts[rid]))
        return out

    def goodput(lat, slo_ttft, slo_tpot, t0_arrival):
        if not lat:
            return 0.0
        span = max(last for _, _, _, last in lat) - t0_arrival
        good = sum(n for ttft, tpot, n, _ in lat
                   if ttft <= slo_ttft and tpot <= slo_tpot)
        return good / span if span > 0 else 0.0

    # calibration drain: compiles both programs (population-independent —
    # exactly two compiles, however the mix schedules) and measures the
    # engine's service capacity in requests/sec of THIS machine
    calib = make_workload(rate=1e9, n=args.requests, tag=9)
    t0 = time.perf_counter()
    ev, _ = drive(calib)
    cap_req_per_s = args.requests / (time.perf_counter() - t0)
    # unloaded latency: one request alone = the SLO yardstick
    solo = make_workload(rate=1e9, n=1, tag=8)
    ev, _ = drive(solo)
    lat = latencies(ev, [(r, a, t, m) for r, a, t, m in solo])
    ttft0 = max(lat[0][0], 1e-9)
    tpot0 = max(lat[0][1], 1e-9)
    slo_ttft = args.slo_ttft_x * ttft0
    slo_tpot = args.slo_tpot_x * tpot0

    factors = [float(f) for f in args.load_factors.split(",")]
    rates = [f * cap_req_per_s for f in factors]

    cont_good, ttft_p50, tpot_p50, completed = [], [], [], []
    mean_live = 0.0
    for k, rate in enumerate(rates):
        if rec is not None:
            rec.clear()  # the exported trace covers the top rate only
        wl = make_workload(rate, args.requests, tag=10 + k)
        ev, mean_live = drive(wl)
        lat = latencies(ev, wl)
        cont_good.append(goodput(lat, slo_ttft, slo_tpot, wl[0][1]))
        ttft_p50.append(float(np.median([x[0] for x in lat])))
        tpot_p50.append(float(np.median([x[1] for x in lat])))
        completed.append(len(lat))

    # ---- trace export (PR 14) -------------------------------------------
    trace_extras = {}
    if rec is not None:
        import json

        from distributed_tensorflow_guide_tpu.obs import (
            tracing as obs_trace,
        )

        tr = obs_trace.to_chrome_trace(rec.events())
        out_path = Path(args.trace_out)
        out_path.write_text(json.dumps(tr))
        # self-validate: the written file must load back as trace-event
        # JSON with at least one complete (X) span — a trace Perfetto
        # would render as an empty screen fails the bench loudly
        back = json.loads(out_path.read_text())
        n_x = sum(1 for ev in back["traceEvents"] if ev.get("ph") == "X")
        if n_x <= 0:
            raise SystemExit(
                f"--trace-out self-check failed: {args.trace_out} has "
                "no complete (X) spans")
        bk = obs_trace.ttft_breakdown(rec.events())
        trace_extras = {
            "trace_out": str(out_path),
            "trace_events": len(back["traceEvents"]),
            "trace_complete_spans": n_x,
            "ttft_breakdown": {
                "queue_wait_s_p50": round(float(np.median(
                    [v["queue_wait_s"] for v in bk.values()])), 6),
                "prefill_s_p50": round(float(np.median(
                    [v["prefill_s"] for v in bk.values()])), 6),
                "first_decode_s_p50": round(float(np.median(
                    [v["first_decode_s"] for v in bk.values()])), 6),
            } if bk else {},
        }

    # ---- static (continuity) side at every rate -------------------------
    gens = {}

    def static_gen(P, M):
        if (P, M) not in gens:
            g = make_generate_fn(cfg, max_new_tokens=M, temperature=0.0)
            prompt = np.zeros((args.slots, P), np.int32)
            g(params, prompt, jax.random.PRNGKey(0))  # warm outside clock
            gens[(P, M)] = g
        return gens[(P, M)]

    def drive_static(workload):
        pending = list(workload)
        now, done = 0.0, []  # (rid, arrival, finish, n_tokens)
        while pending:
            arrived = [r for r in pending if r[1] <= now]
            if not arrived:
                now = min(r[1] for r in pending)
                continue
            head_P = len(arrived[0][2])
            batch = [r for r in arrived
                     if len(r[2]) == head_P][:args.slots]
            M = max(r[3] for r in batch)
            prompt = np.zeros((args.slots, head_P), np.int32)
            for j, r in enumerate(batch):
                prompt[j] = r[2]
            gen = static_gen(head_P, M)
            t0 = time.perf_counter()
            out = gen(params, prompt, jax.random.PRNGKey(0))
            np.asarray(out)
            now += time.perf_counter() - t0
            for r in batch:  # overshoot truncated: each counts its own M
                done.append((r[0], r[1], now, r[3]))
                pending.remove(r)
        return done

    static_good = []
    for k, rate in enumerate(rates):
        wl = make_workload(rate, args.requests, tag=20 + k)
        done = drive_static(wl)
        lat = [(finish - a, 0.0, n, finish) for _, a, finish, n in done]
        static_good.append(goodput(lat, slo_ttft, slo_tpot, wl[0][1]))

    top = len(rates) - 1

    # ---- chaos phase: serving under fire (PR 11) ------------------------
    chaos_extras = {}
    if args.chaos or args.snapshot_restore:
        import tempfile

        from distributed_tensorflow_guide_tpu.serve.scheduler import (
            EngineOverloaded,
        )
        from distributed_tensorflow_guide_tpu.testing.chaos import (
            FaultSchedule,
        )

        burst_rng = np.random.RandomState(args.seed * 104729 + 5)
        burst_log = []  # rids the storm injected

        def burst_factory(n, burst_now):
            out = []
            for _ in range(n):
                rid = 3_000_000 + len(burst_log)
                burst_log.append(rid)
                P = int(burst_rng.choice(plens, p=pmix))
                toks = burst_rng.randint(
                    0, cfg.vocab_size, P).astype(np.int32)
                out.append(Request(
                    rid=rid, prompt=toks, max_new_tokens=min(mnews),
                    rng=jax.random.PRNGKey(rid % (1 << 20)),
                    arrival=burst_now))
            return out

        snap_dir = (tempfile.mkdtemp(prefix="bench_serve_snap_")
                    if args.snapshot_restore else None)

        def make_chaos_engine(storm):
            return ServeEngine(
                cfg, params, slots=args.slots,
                num_blocks=args.num_blocks, block_size=args.block_size,
                prefill_chunk=args.prefill_chunk, temperature=0.0,
                max_queue=2 * args.slots,
                chaos=(FaultSchedule.random_serve(
                    args.seed + 17, max_position=60) if storm else None),
                burst_factory=burst_factory,
                snapshot_dir=snap_dir,
                host_blocks=args.host_blocks)

        def mkreq(rid, arr, toks, M):
            return Request(rid=rid, prompt=toks, max_new_tokens=M,
                           rng=jax.random.PRNGKey(rid % (1 << 20)),
                           arrival=arr)

        def drive_chaos(pending, e, start_now, *, snap_every=0,
                        kill_at_tokens=None, progress_rids=None,
                        progress_target=None):
            """Closed-loop variant of ``drive``: requests enter at their
            virtual arrival (so ``max_queue`` gates on real queue depth),
            shed submissions are recorded, snapshots are taken every
            ``snap_every`` non-idle ticks, and ``kill_at_tokens`` aborts
            mid-run once token progress reaches it (the kill leg).
            ``progress_target`` reports the first virtual time progress
            over ``progress_rids`` crosses it (the MTTR probe)."""
            pending = sorted(pending, key=lambda r: r[1])
            now, events, shed_rids, caught_up = start_now, [], [], None
            killed = False

            def progress():
                return sum(len(e.sched.emitted.get(r, []))
                           for r in progress_rids or ())

            while True:
                while pending and pending[0][1] <= now:
                    rid, arr, toks, M = pending.pop(0)
                    try:
                        e.submit(mkreq(rid, arr, toks, M))
                    except EngineOverloaded:
                        shed_rids.append(rid)
                busy = (e.sched.has_queued or e.sched.has_resident
                        or e._pressure_holds)
                if not busy and not pending:
                    break
                t0 = time.perf_counter()
                evs, kind = e.step(now)
                dt = time.perf_counter() - t0
                if kind == "idle" and not evs:
                    if e._pressure_holds:
                        continue  # holds release by tick, keep stepping
                    nxt = [t for t in (e.sched.next_arrival(),
                                       pending[0][1] if pending else None)
                           if t is not None]
                    if not nxt:
                        break
                    now = max(now, min(nxt))
                    continue
                now += dt
                events.extend(
                    dataclasses.replace(ev, time=now) for ev in evs)
                if snap_every and e._tick % snap_every == 0:
                    e.save_snapshot()
                if progress_target is not None and caught_up is None \
                        and progress() >= progress_target:
                    caught_up = now
                if kill_at_tokens is not None \
                        and progress() >= kill_at_tokens:
                    killed = True
                    break
            # the engine hands a launch's events out one call later: what
            # it still holds (a snapshot settled it, or the kill came
            # first) the client has seen by now
            events.extend(dataclasses.replace(ev, time=now)
                          for ev in e.settle())
            return dict(events=events, now=now, pending=pending,
                        shed=shed_rids, killed=killed, caught_up=caught_up)

        wl = make_workload(rates[top], args.requests, tag=30)
        wl_rids = [r for r, _, _, _ in wl]
        total_tokens = sum(M for _, _, _, M in wl)
        e1 = make_chaos_engine(storm=True)
        leg1 = drive_chaos(
            wl, e1, 0.0,
            snap_every=8 if args.snapshot_restore else 0,
            kill_at_tokens=(total_tokens // 3
                            if args.snapshot_restore else None),
            progress_rids=wl_rids)
        events, shed_rids = leg1["events"], list(leg1["shed"])
        mttr, restored_step, e2 = 0.0, None, None
        if leg1["killed"]:
            # engine killed: e1 is abandoned where it stood; a fresh
            # engine restores the latest valid snapshot, clients
            # re-submit requests the snapshot never saw (they hold no
            # done=True event), arrivals after the kill proceed as normal
            kill_now = leg1["now"]
            kill_progress = sum(
                len(e1.sched.emitted.get(r, [])) for r in wl_rids)
            e2 = make_chaos_engine(storm=False)
            restored_step = e2.restore_latest_snapshot()
            shed_base = e2.sched.shed  # snapshot-era sheds, already in e1's
            by_rid = {r[0]: r for r in wl}
            lost = [by_rid[r] for r in wl_rids
                    if r not in e2.sched.meta
                    and r not in e1.sched.finished  # terminal: client saw it
                    and r not in {p[0] for p in leg1["pending"]}
                    and r not in shed_rids]
            leg2 = drive_chaos(
                lost + list(leg1["pending"]), e2, kill_now,
                progress_rids=wl_rids, progress_target=kill_progress)
            events = events + leg2["events"]
            shed_rids += leg2["shed"]
            end = leg2["caught_up"] if leg2["caught_up"] else leg2["now"]
            mttr = end - kill_now
        fin = (e2 or e1).sched

        def emitted_of(r):
            return max(len(e1.sched.emitted.get(r, [])),
                       len(fin.emitted.get(r, [])))

        # distinct-token counts come from the emitted ledger (the event
        # stream legitimately re-emits the snapshot..kill span bitwise
        # after a restore; clients dedupe by position), first-seen time
        # from the event stream (client view)
        arrmap = {rid: a for rid, a, _, _ in wl}
        firsts, lasts = {}, {}
        for ev in events:
            if ev.rid in arrmap and ev.token >= 0 and ev.status == "ok":
                firsts.setdefault(ev.rid, ev.time)
                lasts[ev.rid] = ev.time
        lat = []
        for rid, a in arrmap.items():
            if rid not in firsts:
                continue
            n = emitted_of(rid)
            tpot = ((lasts[rid] - firsts[rid]) / (n - 1)) if n > 1 else 0.0
            lat.append((firsts[rid] - a, tpot, n, lasts[rid]))
        chaos_good = goodput(lat, slo_ttft, slo_tpot, wl[0][1])
        dropped = [r for r in wl_rids
                   if r not in fin.finished and r not in shed_rids
                   and r not in e1.sched.finished]
        shed_total = e1.sched.shed + (
            (e2.sched.shed - shed_base) if e2 is not None else 0)
        attempts = len(wl_rids) + len(burst_log)
        chaos_extras = {
            "chaos_seed": args.seed + 17,
            "chaos_faults_fired": len(e1.chaos.fired),
            "chaos_goodput": round(chaos_good, 2),
            "goodput_under_chaos_frac": round(
                chaos_good / cont_good[top], 3) if cont_good[top] else 0.0,
            "recovery_mttr_s": round(mttr, 4),
            "snapshot_restored_step": restored_step,
            "shed_rate": round(shed_total / max(1, attempts), 3),
            "burst_requests": len(burst_log),
            "cancelled": fin.cancelled,
            "expired": fin.expired,
            "zero_dropped_streams": not dropped,
            "chaos_health": (e2 or e1).health(),
        }
        for e in (e1, e2):
            if e is not None:
                e.close()

    # ---- prefix-sharing + tenancy phase (PR 12) --------------------------
    prefix_extras = {}
    if args.prefix_mix:
        NT = args.prefix_mix
        # the shared system prompt: a multiple of both the block size and
        # the prefill chunk, so a repeat claim covers it exactly
        import math

        g = math.lcm(args.block_size, args.prefill_chunk)
        sfx_len = 8
        # largest shareable prompt the geometry affords: bounded by the
        # position budget AND by each resident's fair share of the pool
        budget = min(cfg.max_len,
                     (args.num_blocks - 1) * args.block_size // args.slots)
        sys_len = max(g, (budget - sfx_len - min(mnews)) // g * g)
        if sys_len + sfx_len + min(mnews) > cfg.max_len:
            raise SystemExit("--prefix-mix: max_len too small for the "
                             "system prompt + suffix + decode budget")
        prng = np.random.RandomState(args.seed * 31337 + 7)
        sys_prompt = prng.randint(0, cfg.vocab_size, sys_len).astype(np.int32)

        def make_prefix_workload(rate, n, tag, tenant_of_i=None):
            rng = np.random.RandomState(args.seed * 6007 + tag)
            now, out = 0.0, []
            for i in range(n):
                now += rng.exponential(1.0 / rate)
                sfx = rng.randint(0, cfg.vocab_size,
                                  sfx_len).astype(np.int32)
                toks = np.concatenate([sys_prompt, sfx])
                out.append((tag * 100000 + i, now, toks, int(min(mnews)),
                            (i % NT) if tenant_of_i is None
                            else tenant_of_i(i)))
            return out

        def prefix_engine(on, quotas=None):
            return ServeEngine(
                serve_cfg, params, slots=args.slots,
                num_blocks=args.num_blocks, block_size=args.block_size,
                prefill_chunk=args.prefill_chunk, temperature=0.0,
                adapters=bank, prefix_cache=on, tenant_quotas=quotas,
                host_blocks=args.host_blocks)

        def ttft_p50_of(e, wl):
            ev, _ = drive(wl, e)
            lat = latencies(ev, wl)
            by_tenant = {}
            wl_tenant = {w[0]: w[4] for w in wl}
            firsts = {x.rid: x.time for x in ev
                      if x.first and x.status == "ok"}
            arr = {w[0]: w[1] for w in wl}
            for rid, t in firsts.items():
                if rid in arr:
                    by_tenant.setdefault(wl_tenant[rid], []).append(
                        t - arr[rid])
            p50 = float(np.median([x[0] for x in lat])) if lat else 0.0
            return p50, lat, by_tenant

        rate = rates[top]
        # one untimed warmup request per engine: populates the trie (ON
        # side) so the measured wave hits it, and keeps the two sides'
        # work symmetric (compile state is already shared via the step-fn
        # memo). latencies() drops the warmup rid — it is not in the
        # measured workload's arrival map.
        warm = make_prefix_workload(1e9, 1, tag=43)
        wl_on = make_prefix_workload(rate, args.requests, tag=40)
        e_on = prefix_engine(on=True)
        drive(warm, e_on)
        ttft_on, lat_on, by_t_on = ttft_p50_of(e_on, wl_on)
        h_on = e_on.health()
        good_on = goodput(lat_on, slo_ttft, slo_tpot, wl_on[0][1])
        e_on.close()
        e_on.sched.pool.check_leaks()

        wl_off = make_prefix_workload(rate, args.requests, tag=40)
        e_off = prefix_engine(on=False)
        drive(warm, e_off)
        ttft_off, lat_off, _ = ttft_p50_of(e_off, wl_off)
        good_off = goodput(lat_off, slo_ttft, slo_tpot, wl_off[0][1])
        e_off.close()

        # fair-share leg: tenant 0 floods (3x everyone's volume at once)
        # under a slots quota — the victims' TTFT must stay bounded
        burst_extra = make_prefix_workload(
            1e9, 3 * args.requests, tag=41, tenant_of_i=lambda i: 0)
        steady = make_prefix_workload(rate, args.requests, tag=42)
        e_fair = prefix_engine(
            on=True, quotas={0: {"slots": max(1, args.slots // 2)}})
        drive(warm, e_fair)
        wl_fair = sorted(burst_extra + steady, key=lambda w: w[1])
        _, lat_fair, by_t_fair = ttft_p50_of(e_fair, wl_fair)
        fair_health = e_fair.health()
        e_fair.close()
        victims_on = [v for t, vs in by_t_on.items() if t != 0
                      for v in vs]
        victims_fair = [v for t, vs in by_t_fair.items() if t != 0
                        for v in vs]
        victim_ratio = (
            float(np.median(victims_fair) / max(np.median(victims_on),
                                                1e-9))
            if victims_on and victims_fair else 0.0)

        prefix_extras = {
            "prefix_mix_tenants": NT,
            "prefix_sys_len": int(sys_len),
            "prefix_ttft_p50_on": round(ttft_on, 4),
            "prefix_ttft_p50_off": round(ttft_off, 4),
            "prefix_ttft_speedup": round(ttft_off / max(ttft_on, 1e-9), 2),
            "prefix_goodput_on": round(good_on, 2),
            "prefix_goodput_off": round(good_off, 2),
            "prefix_hit_tokens": h_on["prefix_hit_tokens"],
            "prefill_tokens_saved": h_on["prefill_tokens_saved"],
            "prefix_evictions": h_on["prefix_evictions"],
            "fair_share_victim_ttft_ratio": round(victim_ratio, 2),
            "fair_share_tenants": {
                t: {"done": c["done"], "tokens": c["tokens"],
                    "shed": c["shed"]}
                for t, c in fair_health["tenants"].items()},
        }

    # ---- cache-hierarchy longtail phase (PR 16) --------------------------
    longtail_extras = {}
    if args.longtail_mix:
        import math as _math
        import tempfile

        from benchmarks.common import spill_bytes_per_swap, spill_extras

        N = args.longtail_mix
        TURNS = 4
        reply = int(min(mnews))
        sfx_len = args.block_size
        P0 = args.block_size
        if P0 + (TURNS - 1) * (reply + sfx_len) + reply > cfg.max_len:
            raise SystemExit("--longtail-mix: max_len too small for "
                             f"{TURNS} turns of {reply} tokens")
        # the ON side's host tier: generous by default — the point of
        # the A/B is residency, not host-capacity tuning
        HB = args.host_blocks if args.host_blocks else 4 * args.num_blocks
        g = _math.lcm(args.block_size, args.prefill_chunk)
        snap_dir = (tempfile.mkdtemp(prefix="bench_serve_cache_")
                    if args.persist_cache else None)
        rate = rates[top]

        def longtail_engine(host_blocks, persist=False):
            return ServeEngine(
                serve_cfg, params, slots=args.slots,
                num_blocks=args.num_blocks, block_size=args.block_size,
                prefill_chunk=args.prefill_chunk, temperature=0.0,
                adapters=bank, prefix_cache=True,
                host_blocks=host_blocks,
                snapshot_dir=snap_dir if persist else None,
                persist_cache=persist)

        def draw_sessions():
            """The deterministic workload skeleton: initial prompts,
            per-turn fresh suffixes, arrival times and think-time gaps
            are all drawn up front from one seed, so the ON and OFF
            engines see byte-identical session traces (the replies the
            sessions feed back are greedy, hence identical too — that
            equality IS the bitwise cross-check)."""
            rng = np.random.RandomState(args.seed * 52711 + 50)
            prompts0 = [rng.randint(0, cfg.vocab_size, P0).astype(np.int32)
                        for _ in range(N)]
            sfxs = [[rng.randint(0, cfg.vocab_size,
                                 sfx_len).astype(np.int32)
                     for _ in range(TURNS - 1)] for _ in range(N)]
            arr0, now0 = [], 0.0
            for _ in range(N):
                now0 += rng.exponential(1.0 / rate)
                arr0.append(now0)
            # long idle gaps: cohort-scale think time between turns —
            # sessions go COLD between turns, so their context blocks
            # sit in the trie under pool pressure (N sessions' contexts
            # exceed the pool), which is exactly what the hierarchy
            # demotes instead of destroying
            think = [[rng.exponential(2.0 * N / rate)
                      for _ in range(TURNS - 1)] for _ in range(N)]
            return prompts0, sfxs, arr0, think

        def drive_longtail(e, tag):
            """Closed-loop multi-turn driver on the virtual clock: turn
            k+1's prompt is turn k's prompt + the engine's own emitted
            reply + a fresh suffix; a finished session turn schedules
            its next arrival one think-gap later."""
            prompts0, sfxs, arr0, think = draw_sessions()
            ctx = [p.copy() for p in prompts0]
            turn, nxt, act = [0] * N, list(arr0), [None] * N
            arrmap, streams, events, now = {}, {}, [], 0.0
            busy = 0.0
            while True:
                for i in range(N):
                    if act[i] is None and turn[i] < TURNS \
                            and nxt[i] <= now:
                        rid = tag * 100000 + i * 100 + turn[i]
                        arrmap[rid] = nxt[i]
                        e.submit(Request(
                            rid=rid, prompt=ctx[i].copy(),
                            max_new_tokens=reply,
                            rng=jax.random.PRNGKey(rid % (1 << 20)),
                            arrival=nxt[i]))
                        act[i] = rid
                waiting = [nxt[i] for i in range(N)
                           if act[i] is None and turn[i] < TURNS]
                if not (e.sched.has_queued or e.sched.has_resident) \
                        and not waiting:
                    break
                t0 = time.perf_counter()
                evs, kind = e.step(now)
                dt = time.perf_counter() - t0
                if kind == "idle":
                    nq = e.sched.next_arrival()
                    cand = waiting + ([nq] if nq is not None else [])
                    if not cand:
                        break
                    now = max(now, min(cand))
                    continue
                now += dt
                busy += dt
                events.extend(
                    dataclasses.replace(ev, time=now) for ev in evs)
                for i in range(N):
                    rid = act[i]
                    if rid is not None and rid in e.sched.finished:
                        # completions() settles: a stream is finished in
                        # the books a call before its last token is here
                        toks = np.asarray(e.completions()[rid], np.int32)
                        streams[rid] = toks
                        act[i] = None
                        turn[i] += 1
                        if turn[i] < TURNS:
                            ctx[i] = np.concatenate(
                                [ctx[i], toks, sfxs[i][turn[i] - 1]])
                            nxt[i] = now + think[i][turn[i] - 1]
            wl = [(rid, a, None, reply) for rid, a in arrmap.items()]
            lat = latencies(events, wl)
            # goodput over ENGINE-BUSY seconds, not wall span: the wall
            # span is dominated by the (identical-by-construction) idle
            # think gaps, which would average the A/B toward 1.0; per
            # busy second is where saved prefill work is visible
            good_toks = sum(n for ttft, tpot, n, _ in lat
                            if ttft <= slo_ttft and tpot <= slo_tpot)
            good = good_toks / busy if busy > 0 else 0.0
            # TTFT of the turns that can hit the cache (turn >= 1)
            later = [first - arrmap[x.rid] for x in events
                     if x.rid in arrmap and x.rid % 100 >= 1
                     and x.first and x.status == "ok" and x.token >= 0
                     for first in (x.time,)]
            ttft_later = float(np.median(later)) if later else 0.0
            return streams, good, ctx, ttft_later

        e_on = longtail_engine(HB, persist=args.persist_cache)
        st_on, good_lt_on, final_prompts, ttft_lt_on = \
            drive_longtail(e_on, tag=50)
        h_on_lt = e_on.health()
        steps_on_lt = dict(e_on.steps)
        e_on.sched.check_leaks()

        e_off = longtail_engine(0)
        st_off, good_lt_off, _, ttft_lt_off = drive_longtail(e_off, tag=50)
        h_off_lt = e_off.health()
        steps_off_lt = dict(e_off.steps)
        e_off.close()

        bitwise = (set(st_on) == set(st_off) and all(
            np.array_equal(st_on[r], st_off[r]) for r in st_on))

        # modeled-vs-traced swap bytes: the h2d side copies every block
        # it counts (the d2h side legitimately dedups COW-shared blocks
        # against live host copies, so its bytes are <= blocks x model)
        hd = serve_cfg.d_model // serve_cfg.num_heads
        per_block_model = spill_bytes_per_swap(
            serve_cfg.num_layers, serve_cfg.num_heads, args.block_size,
            hd, serve_cfg.kv_dtype,
            activation_dtype_bytes=np.dtype(serve_cfg.dtype).itemsize)
        n_in = h_on_lt["spill_in_blocks"]
        traced_per_block = (h_on_lt["spill_h2d_bytes"] / n_in
                            if n_in else 0.0)
        longtail_extras = {
            "longtail_sessions": N,
            "longtail_turns": TURNS,
            "longtail_host_blocks": HB,
            "longtail_goodput_on": round(good_lt_on, 2),
            "longtail_goodput_off": round(good_lt_off, 2),
            "longtail_goodput_gain": round(
                good_lt_on / max(good_lt_off, 1e-9), 3),
            "longtail_later_turn_ttft_p50_on": round(ttft_lt_on, 4),
            "longtail_later_turn_ttft_p50_off": round(ttft_lt_off, 4),
            "longtail_prefill_steps_on": steps_on_lt.get("prefill", 0),
            "longtail_prefill_steps_off": steps_off_lt.get("prefill", 0),
            "spill_streams_bitwise_identical": bitwise,
            "spill_out_blocks": h_on_lt["spill_out_blocks"],
            "spill_in_blocks": n_in,
            "spill_prefetched_blocks": h_on_lt["spill_prefetched_blocks"],
            "spill_resumes": h_on_lt["spill_resumes"],
            "swapin_tokens_saved": h_on_lt["swapin_tokens_saved"],
            "prefix_evictions_on": h_on_lt["prefix_evictions"],
            "prefix_evictions_off": h_off_lt["prefix_evictions"],
            "spill_bytes_model_per_block": per_block_model,
            "spill_bytes_traced_per_block": round(traced_per_block, 1),
            "spill_bytes_model_match": (
                traced_per_block == per_block_model if n_in else None),
        }
        longtail_extras.update(spill_extras(
            h_on_lt["spill_d2h_bytes"], h_on_lt["spill_h2d_bytes"]))

        # warm-restart leg: persist the warm cache, restore into a
        # fresh engine, replay every session's FINAL turn — the whole
        # cached context must come back through the prefix-claim path
        # (swap-in), never through re-prefill
        if args.persist_cache:
            e_on.save_snapshot()
            e_on.close()
            P_last = len(final_prompts[0])
            expected_saved = N * ((P_last - 1) // g * g)
            e_warm = longtail_engine(HB, persist=True)
            restored = e_warm.restore_latest_snapshot()
            base_saved = e_warm.sched.prefill_tokens_saved
            gap = 100.0 * N / rate  # sequential replay: no pool races
            replay = [(51 * 100000 + i, (i + 1) * gap,
                       final_prompts[i], reply) for i in range(N)]
            drive(replay, e_warm)
            warm_saved = e_warm.sched.prefill_tokens_saved - base_saved
            warm_bitwise = all(np.array_equal(
                np.asarray(e_warm.sched.emitted[51 * 100000 + i],
                           np.int32),
                st_on[50 * 100000 + i * 100 + (TURNS - 1)])
                for i in range(N))
            h_warm = e_warm.health()
            longtail_extras.update({
                "warm_restored_step": restored,
                "warm_restored_prefix_nodes": h_warm["prefix_nodes"],
                "warm_prefill_tokens_saved": warm_saved,
                "warm_expected_tokens_saved": expected_saved,
                "warm_zero_cold_prefix_refill":
                    warm_saved == expected_saved,
                "warm_replay_bitwise_identical": warm_bitwise,
                "warm_prefill_steps": dict(e_warm.steps).get(
                    "prefill", 0),
                "warm_spill_in_blocks": h_warm["spill_in_blocks"],
            })
            e_warm.close()
        else:
            e_on.close()

    # ---- scale-out fleet phase (PR 18) -----------------------------------
    fleet_extras = {}
    if args.fleet:
        from benchmarks.common import dcn_extras, device_dcn_peak
        from distributed_tensorflow_guide_tpu.obs import recon as obs_recon
        from distributed_tensorflow_guide_tpu.serve.fleet import (
            FleetScheduler,
        )

        fl = FleetScheduler(
            serve_cfg, params, replicas=args.fleet,
            roles=args.fleet_roles,
            slots=args.slots, num_blocks=args.num_blocks,
            block_size=args.block_size, prefill_chunk=args.prefill_chunk,
            temperature=0.0, adapters=bank,
            prefix_cache=args.fleet_prefix,
            host_blocks=args.host_blocks)

        def drive_fleet(workload):
            """The fleet's virtual-clock driver: same discipline as
            ``drive``, except a tick is charged the SLOWEST replica's
            measured wall time plus the supervisor's own overhead (the
            in-process loop steps replicas serially, but they are
            independent machines); idle ticks fast-forward to the
            fleet-wide next arrival."""
            for rid, arr, toks, M, *rest in workload:
                fl.submit(Request(
                    rid=rid, prompt=toks, max_new_tokens=M,
                    rng=jax.random.PRNGKey(rid % (1 << 20)),
                    arrival=arr, adapter=adapter_of(rid),
                    tenant=rest[0] if rest else 0))
            now, events = 0.0, []
            while fl._has_work():
                t0 = time.perf_counter()
                evs, kind = fl.step(now)
                total = time.perf_counter() - t0
                if kind == "idle":
                    nxt = fl.next_arrival()
                    if nxt is None:
                        break
                    now = max(now, nxt)
                    continue
                per_replica = list(fl.step_secs.values())
                now += total - sum(per_replica) + max(per_replica,
                                                      default=0.0)
                events.extend(
                    dataclasses.replace(ev, time=now) for ev in evs)
            return events

        # N replicas are provisioned for N x the single engine's
        # calibrated capacity, so the A/B offers BOTH sides that rate:
        # the single engine saturates (queueing blows its SLOs), the
        # fleet keeps pace — that headroom is the point of scale-out.
        # The length/token draw is seed-identical across tags (only
        # rids shift), so the sides — and the bitwise cross-check —
        # stay apples-to-apples.
        rate_f = args.fleet * rates[top]
        wl_fleet = make_workload(rate_f, args.requests, tag=60)
        ev_f = drive_fleet(wl_fleet)
        lat_f = latencies(ev_f, wl_fleet)
        fleet_good = goodput(lat_f, slo_ttft, slo_tpot, wl_fleet[0][1])
        if args.fleet_prefix:
            # a repeat wave with the SAME prompts (fresh rids): every
            # request now has a warm prefix somewhere in the fleet, and
            # the router must concentrate it there instead of diluting
            drive_fleet(make_workload(rate_f, args.requests, tag=61))
        fh = fl.health()
        fl.check_leaks()
        comps = fl.completions()
        wl_one = make_workload(rate_f, args.requests, tag=62)
        ev_one, _ = drive(wl_one)
        lat_one = latencies(ev_one, wl_one)
        single_good = goodput(lat_one, slo_ttft, slo_tpot, wl_one[0][1])
        base_rid = 62 * 100000
        mig = sorted(set(fl.migrated_rids))

        def fleet_matches(rid):
            return np.array_equal(
                np.asarray(comps.get(rid, []), np.int32),
                np.asarray(eng.sched.emitted.get(
                    base_rid + rid % 100000, []), np.int32))

        bitwise_mig = all(fleet_matches(r) for r in mig)
        bitwise_all = all(fleet_matches(60 * 100000 + i)
                          for i in range(args.requests))
        def p50(lat, j):
            return float(np.median([x[j] for x in lat])) if lat else 0.0

        fleet_extras = {
            "fleet_replicas": args.fleet,
            "fleet_roles": args.fleet_roles,
            "fleet_prefix_routing": bool(args.fleet_prefix),
            "fleet_offered_req_per_s": round(rate_f, 3),
            "fleet_goodput": round(fleet_good, 2),
            "single_goodput_at_fleet_rate": round(single_good, 2),
            "fleet_goodput_gain": round(
                fleet_good / max(single_good, 1e-9), 3),
            "fleet_ttft_p50": round(p50(lat_f, 0), 4),
            "fleet_tpot_p50": round(p50(lat_f, 1), 4),
            "single_ttft_p50": round(p50(lat_one, 0), 4),
            "single_tpot_p50": round(p50(lat_one, 1), 4),
            "fleet_completed": len(lat_f),
            "fleet_migrations": fh["migrations"],
            "fleet_migration_bytes": fh["migration_bytes"],
            "prefix_route_hits": fh["prefix_route_hits"],
            "prefix_route_hit_tokens": fh["prefix_route_hit_tokens"],
            "migrated_streams": len(mig),
            "migrated_streams_bitwise_identical": bitwise_mig,
            "fleet_streams_bitwise_identical": bitwise_all,
            "fleet_autoscale_signal": fl.autoscale_signal(),
        }
        if fh["migration_bytes"]:
            # the disagg KV handoff priced like every other DCN-tier
            # bench: bytes + achieved rate + roofline fraction (modeled
            # off-TPU), then obs/recon's modeled-vs-measured join against
            # the serve_kv_block_transfer_dcn cost shape
            fleet_extras.update(dcn_extras(
                fh["migration_bytes"], fh["migration_secs"],
                assumed_gbytes_per_s=25.0))
            roof = dataclasses.replace(
                obs_recon.Roofline.from_env(),
                peak_ici_bytes_s=device_dcn_peak() or 25e9)
            r = obs_recon.reconcile(
                {"flops": 0.0, "hbm_bytes": 0.0,
                 "collective_bytes": {
                     "ppermute[dcn]": float(fh["migration_bytes"])}},
                max(fh["migration_secs"], 1e-9), roof)
            fleet_extras["migration_recon"] = {
                "achieved_gb_s": round(r["achieved_ici_gb_s"], 3),
                "dcn_frac": (round(r["ici_frac"], 6)
                             if r["ici_frac"] is not None else None),
                "bound": r["bound"],
            }
        fl.close()

        # ---- fleet under fire (PR 20) --------------------------------
        if args.fleet_chaos:
            import tempfile

            from distributed_tensorflow_guide_tpu.obs import (
                events as obs_events,
            )
            from distributed_tensorflow_guide_tpu.testing.chaos import (
                FaultSchedule,
            )

            def chaos_fleet(storm=None, recorder=None,
                            snapshot_dir=None):
                return FleetScheduler(
                    serve_cfg, params, replicas=args.fleet,
                    roles=args.fleet_roles,
                    slots=args.slots, num_blocks=args.num_blocks,
                    block_size=args.block_size,
                    prefill_chunk=args.prefill_chunk,
                    temperature=0.0, adapters=bank,
                    prefix_cache=args.fleet_prefix,
                    host_blocks=args.host_blocks,
                    fleet_chaos=storm, recorder=recorder,
                    snapshot_dir=snapshot_dir)

            def resume_det(flc, *, dt=0.01, stop_tokens=None, now=0.0,
                           emitted=0):
                """Deterministic virtual clock for the chaos legs:
                every tick charges a FIXED dt (idle ticks fast-forward
                to the next arrival), so two seeded runs of the same
                storm walk the same tick sequence — what makes the
                event signature pinnable.  Stops once ``stop_tokens``
                have been emitted (the kill point)."""
                wedged = 0
                while flc._has_work():
                    evs, kind = flc.step(now)
                    now += dt
                    if kind == "idle":
                        wedged += 1
                        if wedged > 256:
                            raise RuntimeError("fleet wedged under "
                                               "chaos: no progress")
                        nxt = flc.next_arrival()
                        if nxt is not None:
                            now = max(now, nxt)
                        continue
                    wedged = 0
                    emitted += sum(1 for e in evs
                                   if e.status == "ok" and e.token >= 0)
                    if (stop_tokens is not None
                            and emitted >= stop_tokens):
                        break
                return now, emitted

            def drive_det(flc, workload, **kw):
                for rid, arr, toks, M, *rest in workload:
                    flc.submit(Request(
                        rid=rid, prompt=toks, max_new_tokens=M,
                        rng=jax.random.PRNGKey(rid % (1 << 20)),
                        arrival=arr, adapter=adapter_of(rid),
                        tenant=rest[0] if rest else 0))
                return resume_det(flc, **kw)

            def storm():
                return FaultSchedule.random_fleet(
                    args.seed, max_position=24, replicas=args.fleet,
                    n_faults=4)

            wl_fc = make_workload(rate_f, args.requests, tag=63)
            total_tokens = sum(w[3] for w in wl_fc)

            # clean leg: same workload, no storm — the bitwise baseline
            # and the goodput denominator
            fl_clean = chaos_fleet()
            span_clean, _ = drive_det(fl_clean, wl_fc)
            comp_clean = fl_clean.completions()
            fl_clean.check_leaks()
            fl_clean.close()

            def chaos_leg():
                rec_fc = obs_events.FlightRecorder(capacity=1 << 16)
                flc = chaos_fleet(storm=storm(), recorder=rec_fc)
                span, _ = drive_det(flc, wl_fc)
                comp = flc.completions()
                h = flc.health()
                flc.check_leaks()
                flc.close()
                return comp, span, h, [
                    e for e in rec_fc.events()
                    if str(e.kind).startswith("fleet.")]

            comp_c, span_c, h_c, ev_c = chaos_leg()
            _, _, _, ev_c2 = chaos_leg()  # the determinism pin
            deterministic = (obs_events.signature(ev_c)
                             == obs_events.signature(ev_c2))

            # MTTR: replica down (crash/stall/ejection) -> that replica
            # recovered, on the deterministic virtual clock
            mttrs, downs = [], {}
            for e in ev_c:
                p = e.payload or {}
                if e.kind in ("fleet.replica_crash",
                              "fleet.replica_stall",
                              "fleet.replica_ejected"):
                    downs.setdefault(p.get("replica"), e.t)
                elif e.kind == "fleet.replica_recovered":
                    t0 = downs.pop(p.get("replica"), None)
                    if t0 is not None:
                        mttrs.append(e.t - t0)
            zero_dropped = (
                sorted(comp_c) == sorted(comp_clean)
                and all(comp_c[r] == comp_clean[r] for r in comp_clean))
            fleet_extras.update({
                "fleet_chaos_seed": args.seed,
                "recovery_mttr_s": (round(sum(mttrs) / len(mttrs), 4)
                                    if mttrs else None),
                "recoveries_measured": len(mttrs),
                "goodput_under_chaos_frac": round(
                    span_clean / max(span_c, 1e-9), 3),
                "zero_dropped_streams": bool(zero_dropped),
                "fleet_chaos_bitwise_identical": bool(zero_dropped),
                "fleet_chaos_deterministic": bool(deterministic),
                "fleet_replica_crashes": h_c["replica_crashes"],
                "fleet_replica_stalls": h_c["replica_stalls"],
                "fleet_breaker_ejections": h_c["breaker_ejections"],
                "fleet_breaker_probes": h_c["breaker_probes"],
                "fleet_breaker_recoveries": h_c["breaker_recoveries"],
                "fleet_migration_dups_dropped":
                    h_c["migration_dups_dropped"],
            })

            # mid-storm kill at 1/3 tokens -> fleet snapshot -> restore
            # into a fresh fleet -> finish: still bitwise vs clean
            if args.fleet_restore:
                snapdir = tempfile.mkdtemp(prefix="fleet_snap_")
                flk = chaos_fleet(storm=storm(), snapshot_dir=snapdir)
                now_k, emitted_k = drive_det(
                    flk, wl_fc, stop_tokens=max(1, total_tokens // 3))
                label = flk.save_snapshot()
                crashes_at_kill = flk.replica_crashes
                flk.close()
                flr = chaos_fleet(snapshot_dir=snapdir)
                restored = flr.restore_latest_snapshot()
                resume_det(flr, now=now_k, emitted=emitted_k)
                comp_r = flr.completions()
                restore_bitwise = (
                    sorted(comp_r) == sorted(comp_clean)
                    and all(comp_r[r] == comp_clean[r]
                            for r in comp_clean))
                flr.check_leaks()
                flr.close()
                fleet_extras.update({
                    "fleet_restore_label": restored,
                    "fleet_restore_saved_label": label,
                    "fleet_restore_kill_tokens": emitted_k,
                    "fleet_restore_crashes_before_kill": crashes_at_kill,
                    "fleet_restore_bitwise_identical":
                        bool(restore_bitwise),
                })

    # ---- MoE A/B phase (PR 19) -------------------------------------------
    moe_extras = {}
    if args.moe:
        from benchmarks.common import moe_all_to_all_bytes
        from distributed_tensorflow_guide_tpu.obs import (
            recon as obs_recon,
        )

        E = args.moe
        cap = args.moe_capacity or max(1, -(-2 * args.slots // E))
        # matched ACTIVE params: every expert is the dense FFN's width
        # and top-1 routing activates exactly one per token, so the MoE
        # side pays the dense side's per-token FLOPs while holding E x
        # the FFN weights — the whole point of the A/B
        moe_cfg = dataclasses.replace(
            cfg, weight_dtype=None, moe_experts=E, moe_capacity=cap)
        moe_params = jax.jit(Transformer(moe_cfg).init)(
            jax.random.PRNGKey(1),
            jnp.zeros((1, moe_cfg.max_len), jnp.int32))["params"]
        if wq:
            from distributed_tensorflow_guide_tpu.ops import quant

            moe_params = quant.quantize_params(
                moe_params, bits=8 if wq == "int8" else 4)
            moe_cfg = dataclasses.replace(moe_cfg, weight_dtype=wq)
        e_moe = ServeEngine(moe_cfg, moe_params, slots=args.slots,
                            num_blocks=args.num_blocks,
                            block_size=args.block_size,
                            prefill_chunk=args.prefill_chunk,
                            temperature=0.0)
        # warm both MoE serve programs outside the clock (the static
        # side's discipline), then zero the counters the warmup touched
        # so the reported load/overflow/a2a cover the workload only
        drive([(70 * 100000 - 1, 0.0,
                np.zeros(args.prefill_chunk, np.int32), 2)], e_moe)
        for k in e_moe.steps:
            e_moe.steps[k] = 0
        e_moe._moe_load[:] = 0
        e_moe._moe_overflow[:] = 0
        e_moe._moe_stall_slot_ticks = e_moe._moe_stall_ticks = 0
        wl_moe = make_workload(rates[top], args.requests, tag=70)
        wall0 = time.perf_counter()
        ev_m, _ = drive(wl_moe, e_moe)
        moe_secs = time.perf_counter() - wall0
        lat_m = latencies(ev_m, wl_moe)
        moe_good = goodput(lat_m, slo_ttft, slo_tpot, wl_moe[0][1])
        hm = e_moe.health()
        steps_m = dict(e_moe.steps)
        e_moe.sched.check_leaks()
        e_moe.close()

        # the expert all-to-all a one-expert-per-device placement would
        # pay, priced by the SAME closed form the training bench pins —
        # forward-only (passes=2), per launch, decode capacity C vs the
        # prefill chunk's dropless t-wide buffer
        item = np.dtype(moe_cfg.dtype).itemsize
        b_dec = E * cap * moe_cfg.d_model * item
        b_pre = E * args.prefill_chunk * moe_cfg.d_model * item
        a2a_bytes = (
            moe_all_to_all_bytes(b_dec, E, moe_cfg.num_layers, passes=2)
            * steps_m.get("decode", 0)
            + moe_all_to_all_bytes(b_pre, E, moe_cfg.num_layers,
                                   passes=2)
            * steps_m.get("prefill", 0))
        r = obs_recon.reconcile(
            {"flops": 0.0, "hbm_bytes": 0.0,
             "collective_bytes": {"all_to_all[expert]": float(a2a_bytes)}},
            max(moe_secs, 1e-9), obs_recon.Roofline.from_env())
        moe_extras = {
            "moe_experts": E,
            "moe_capacity": cap,
            "moe_weight_dtype": args.weight_dtype,
            "moe_active_params_matched": True,
            "moe_goodput": round(moe_good, 2),
            "dense_goodput_at_rate": round(cont_good[top], 2),
            "moe_vs_dense_goodput": round(
                moe_good / max(cont_good[top], 1e-9), 3),
            "moe_ttft_p50": round(float(np.median(
                [x[0] for x in lat_m])) if lat_m else 0.0, 4),
            "moe_tpot_p50": round(float(np.median(
                [x[1] for x in lat_m])) if lat_m else 0.0, 4),
            "moe_completed": len(lat_m),
            "moe_expert_load": hm["moe"]["expert_load"],
            "moe_expert_overflow": hm["moe"]["expert_overflow"],
            "moe_stall_slot_ticks": hm["moe"]["stall_slot_ticks"],
            "moe_stall_ticks": hm["moe"]["stall_ticks"],
            "moe_hbm_bytes_per_decode_step": decode_hbm_bytes_per_step(
                moe_cfg, moe_params, args.slots),
            "moe_a2a_bytes_model": round(a2a_bytes, 1),
            "moe_a2a_recon": {
                "achieved_gb_s": round(r["achieved_ici_gb_s"], 3),
                "ici_frac": (round(r["ici_frac"], 6)
                             if r["ici_frac"] is not None else None),
                "bound": r["bound"],
            },
        }

    # ---- the JSON line ---------------------------------------------------
    side = cont_good if args.mode == "continuous" else static_good
    other = static_good if args.mode == "continuous" else cont_good
    extras = {
        "mode": args.mode,
        "kv_dtype": args.kv_dtype,
        "weight_dtype": args.weight_dtype,
        # leaf-driven over the (possibly quantized) tree: the params
        # term shrinks ~4x/~8x under --weight-dtype int8/int4
        "hbm_bytes_per_decode_step": decode_hbm_bytes_per_step(
            cfg, params, args.slots),
        "decode_impl": cfg.resolve_decode_impl(),
        "prefill_chunk": args.prefill_chunk,
        "slots": args.slots,
        "host_blocks": args.host_blocks,
        "offered_req_per_s": [round(r, 3) for r in rates],
        "goodput_per_rate": [round(g, 2) for g in cont_good],
        "static_goodput_per_rate": [round(g, 2) for g in static_good],
        "ttft_p50_per_rate": [round(t, 4) for t in ttft_p50],
        "tpot_p50_per_rate": [round(t, 4) for t in tpot_p50],
        "completed_per_rate": completed,
        "slo_ttft_s": round(slo_ttft, 4),
        "slo_tpot_s": round(slo_tpot, 4),
        "preemptions": eng.sched.preemptions,
        "engine_steps": dict(eng.steps),
        # the paged byte model (live blocks, not max_len) vs what the
        # dense static cache pays every step — same shared definitions
        # bench_generate's roofline uses
        "paged_cache_bytes_per_step": paged_decode_cache_bytes_per_step(
            cfg, block_size=args.block_size,
            live_blocks=int(round(mean_live)),
            active_slots=args.slots),
        "static_cache_bytes_per_step": decode_cache_bytes_per_step(
            cfg, args.slots),
    }
    extras.update(trace_extras)
    extras.update(chaos_extras)
    extras.update(prefix_extras)
    extras.update(longtail_extras)
    extras.update(fleet_extras)
    extras.update(moe_extras)
    report("serve_goodput", side[top], "tokens/sec",
           baseline=other[top] if other[top] > 0 else None,
           **extras)


if __name__ == "__main__":
    main()
