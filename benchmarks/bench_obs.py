#!/usr/bin/env python
"""Observability-plane liveness + overhead bench (PR 14).

Three phases, all CPU-honest:

1. **Recorder throughput** — emit ``--events`` typed events into a
   bounded :class:`~distributed_tensorflow_guide_tpu.obs.events.
   FlightRecorder` ring and report events/sec and ns/event (the enabled
   hot-path cost), plus the dump cost of the retained tail.
2. **Disabled overhead** — the observe-only contract quantified: with
   recording OFF and no profiler session, a span site
   (``obs/tracing.span``, always on) costs one profiler annotation that
   records nothing and one ``rec.enabled`` check on each side. That
   exact pattern is timed directly, a tiny jitted proxy train step is
   timed for scale, and the derived ``disabled_overhead_frac`` =
   spans-per-step x span-ns / step-ns must come in under 1% — the
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
    null = obs_events.NULL_RECORDER
    m = 100_000
    t0 = time.perf_counter()
    for i in range(m):
        with obs_tracing.span(null, "loop.dispatch", step=i):
            pass
    span_ns = (time.perf_counter() - t0) / m * 1e9

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
    times = []
    for _ in range(max(args.steps, 3)):
        t0 = time.perf_counter()
        x = proxy_step(x)
        jax.block_until_ready(x)
        times.append(time.perf_counter() - t0)
    times.sort()
    step_s = times[len(times) // 2]
    disabled_frac = SPANS_PER_STEP * span_ns * 1e-9 / step_s
    if disabled_frac >= 0.01:
        raise SystemExit(
            f"disabled-recorder overhead {disabled_frac:.2%} >= 1% of a "
            f"{step_s * 1e3:.2f} ms step — {SPANS_PER_STEP} spans of "
            f"{span_ns:.0f} ns with nothing listening")

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
        spans_per_step=SPANS_PER_STEP,
        proxy_step_ms=round(step_s * 1e3, 4),
        disabled_overhead_frac=round(disabled_frac, 6),
        measured_s_source=(
            "proxy step (4 chained %dx%d f32 matmuls, CPU)" % (d, d)),
        **recon_extras,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
