#!/usr/bin/env python
"""Observability-plane liveness + overhead bench (PR 14).

Three phases, all CPU-honest:

1. **Recorder throughput** — emit ``--events`` typed events into a
   bounded :class:`~distributed_tensorflow_guide_tpu.obs.events.
   FlightRecorder` ring and report events/sec and ns/event (the enabled
   hot-path cost), plus the dump cost of the retained tail.
2. **Disabled overhead** — the observe-only contract quantified: with
   recording OFF and no profiler session, a span site
   (``obs/tracing.span``, always on) costs one check that no session runs
   and one ``rec.enabled`` check on each side; the stall watch
   (``obs/tracing.watch_stalls``) adds a young collection's early return,
   a full collection's pair of callbacks and two counters on the tick's
   span. Each pattern is timed directly, a tiny jitted proxy train step is
   timed for scale (each the least of interleaved rounds' block means), and
   the derived ``disabled_overhead_frac`` = (the step's spans and callbacks,
   at the rates stated below) / step-ns must come in under 1% — the
   acceptance gate that keeps the spans always-on in any loop.
3. **Cost reconciliation** — ``obs/recon.py`` joined end-to-end: the
   static cost vectors of the registered ``dp_train_step`` and
   ``serve_decode_step`` programs (abstract ``make_jaxpr`` trace — no
   compile, no execution; the same interpreter the lint gate pins) are
   reconciled against a measured step time into achieved GF/s / GB/s
   and roofline fractions. On CPU the measured time is the PROXY step's
   (labeled ``measured_s_source`` so it can never be read as a TPU
   capture); on real hardware the same call takes the real step time.

The JSON line's ``value`` is recorder throughput (events/sec).
"""

import argparse
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.common import device_setup, report

#: the most spans one iteration of a hot loop crosses: an engine tick's
#: six (engine.tick and its five phases; a TrainLoop step behind a
#: prefetching feed crosses five). This is the per-step multiplier for
#: the derived disabled-overhead fraction.
SPANS_PER_STEP = 6
#: the stall watch's callbacks a step (obs/tracing.watch_stalls): a young
#: collection's ``start`` and ``stop`` for four collections of generation
#: 0 or 1, and a full collection's pair every hundred steps. Upper bounds
#: of what a v5e window was counted to hold (PERF.md section 6, stalls):
#: GPT-2 medium training met 212 young collections and one full one in
#: 213 steps (~2 callbacks and 0.005 full pairs a step), GPT-2 XL serving
#: ~0.04 young collections a tick and no full one
YOUNG_CALLS_PER_STEP = 8
FULL_PAIRS_PER_STEP = 0.01
#: rounds of every timing; the least is the interference-free estimate
ROUNDS = 15


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--events", type=int, default=200_000,
                    help="events to emit in the throughput phase")
    ap.add_argument("--capacity", type=int, default=4096,
                    help="recorder ring capacity")
    ap.add_argument("--steps", type=int, default=30,
                    help="proxy train steps for the overhead scale")
    ap.add_argument("--small", action="store_true",
                    help="shrink the proxy step (smoke-suite parity)")
    ap.add_argument("--fake-devices", type=int, default=0)
    args = ap.parse_args()

    device_setup(args.fake_devices)
    import json
    import tempfile

    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_guide_tpu.obs import events as obs_events
    from distributed_tensorflow_guide_tpu.obs import recon as obs_recon
    from distributed_tensorflow_guide_tpu.obs import tracing as obs_tracing

    # ---- phase 1: enabled recorder throughput ---------------------------
    rec = obs_events.FlightRecorder(capacity=args.capacity,
                                    clock=lambda: 0.0)
    n = args.events
    t0 = time.perf_counter()
    for i in range(n):
        rec.emit("bench.tick", cat="bench", actor="bench_obs",
                 payload={"i": i})
    dt_emit = time.perf_counter() - t0
    events_per_s = n / dt_emit
    ns_per_event = dt_emit / n * 1e9
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        dump_path = f.name
    t0 = time.perf_counter()
    rec.dump(dump_path)
    dump_s = time.perf_counter() - t0
    dumped = json.loads(Path(dump_path).read_text())
    Path(dump_path).unlink()
    assert dumped["total"] == n and len(dumped["events"]) <= args.capacity

    # ---- phase 2: disabled overhead -------------------------------------
    # each side is its interference-free estimate: every round times a
    # block of each pattern and a block of proxy steps, each block's mean
    # a call, and each side keeps its least block over rounds that
    # interleave them, so that a busy host (the smoke suite's parallel
    # workers) inflates neither the spans nor the step they are measured
    # against unless it is busy through all of them. A block and not one
    # step: the fastest of many single steps is a tail (on the CPU it
    # falls with the number of steps timed), a block's mean is not
    null = obs_events.NULL_RECORDER
    watch = obs_tracing.StallWatch()  # registered nowhere: driven by hand
    young, full = {"generation": 0}, {"generation": 2}

    def spans(m):
        for i in range(m):
            with obs_tracing.span(null, "loop.dispatch", step=i):
                pass

    def ticks(m):  # the tick's span, with the watch's two counters
        for i in range(m):
            with obs_tracing.span(null, "engine.tick", tick=i,
                                  gc_full=watch.gc_full,
                                  compiles=watch.compiles):
                pass

    def young_returns(m):
        for _ in range(m):
            watch.on_gc("start", young)

    def full_pairs(m):
        for _ in range(m):
            watch.on_gc("start", full)
            watch.on_gc("stop", full)

    # proxy step: a few chained matmuls — sized so one step is real work
    # on CPU but the bench stays inside the smoke budget
    d = 256 if args.small else 512
    x0 = jnp.eye(d, dtype=jnp.float32)

    @jax.jit
    def proxy_step(x):
        for _ in range(4):
            x = x @ x0 + x
        return x

    x = proxy_step(x0)
    jax.block_until_ready(x)  # warm (compile outside the clock)
    patterns = {"span": (spans, 10_000), "tick": (ticks, 10_000),
                "young": (young_returns, 10_000), "full": (full_pairs, 1_000)}
    steps = max(args.steps, 3)
    best = dict.fromkeys([*patterns, "step"], math.inf)
    for _ in range(ROUNDS):
        for key, (body, m) in patterns.items():
            t0 = time.perf_counter()
            body(m)
            best[key] = min(best[key], (time.perf_counter() - t0) / m)
        t0 = time.perf_counter()
        for _ in range(steps):
            x = proxy_step(x)
            jax.block_until_ready(x)
        best["step"] = min(best["step"], (time.perf_counter() - t0) / steps)
    span_ns, tick_ns, young_ns, full_ns = (
        best[k] * 1e9 for k in ("span", "tick", "young", "full"))
    step_s = best["step"]
    watched_ns = ((SPANS_PER_STEP - 1) * span_ns + tick_ns
                  + YOUNG_CALLS_PER_STEP * young_ns
                  + FULL_PAIRS_PER_STEP * full_ns)
    disabled_frac = watched_ns * 1e-9 / step_s
    if disabled_frac >= 0.01:
        raise SystemExit(
            f"disabled-recorder overhead {disabled_frac:.2%} >= 1% of a "
            f"{step_s * 1e3:.2f} ms step — {SPANS_PER_STEP} spans of "
            f"{span_ns:.0f} ns (the tick's {tick_ns:.0f}) and the stall "
            f"watch's callbacks with nothing listening")

    # ---- phase 3: modeled-vs-measured reconciliation --------------------
    # abstract trace only (make_jaxpr): the SAME cost interpreter the
    # lint gate pins, no compile, no execution
    from distributed_tensorflow_guide_tpu.analysis import cost as ana_cost
    from distributed_tensorflow_guide_tpu.analysis import lint, rules

    roof = obs_recon.Roofline.from_env()
    recon_extras = {}
    for cname in ("dp_train_step", "serve_decode_step"):
        (contract,) = lint._registered([cname])
        fn, cargs = contract.build()
        jaxpr = jax.make_jaxpr(fn)(*cargs)
        traced = rules.TracedProgram(
            name=cname, jaxpr=jaxpr,
            arg_leaf_avals=[lint._leaf_avals(a) for a in cargs])
        vec = ana_cost.program_cost(traced, contract)
        r = obs_recon.reconcile(vec, step_s, roof)
        recon_extras[f"recon_{cname}"] = {
            k: (round(v, 6) if isinstance(v, float) else v)
            for k, v in r.items()}

    report(
        "obs_recorder_events_per_sec", events_per_s, "events/sec",
        baseline=None,
        ns_per_event=round(ns_per_event, 1),
        ring_capacity=args.capacity,
        ring_dropped=dumped["dropped"],
        dump_ms=round(dump_s * 1e3, 3),
        disabled_span_ns=round(span_ns, 1),
        disabled_tick_span_ns=round(tick_ns, 1),
        gc_young_return_ns=round(young_ns, 1),
        gc_full_pair_ns=round(full_ns, 1),
        spans_per_step=SPANS_PER_STEP,
        gc_young_calls_per_step=YOUNG_CALLS_PER_STEP,
        gc_full_pairs_per_step=FULL_PAIRS_PER_STEP,
        proxy_step_ms=round(step_s * 1e3, 4),
        disabled_overhead_frac=round(disabled_frac, 6),
        measured_s_source=(
            "proxy step (4 chained %dx%d f32 matmuls, CPU)" % (d, d)),
        **recon_extras,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
