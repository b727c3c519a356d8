"""The serving driver: seeded requests through ``ServeEngine.submit()`` and
``ServeEngine.step(now)`` on the wall clock.

A request is submitted when its due time has come and is timed from then.
Set-up submits what is due at 0 (a backlog: everything) and runs the engine
until every slot decodes, the state a long job is in for all but its first
seconds; the window opens there. Tokens per second is every token handed
back in the window over the window's whole length.

After the window a sample of the finished requests, drawn from the seed
with the longest in it, goes through the plain reference once each: prompt
and served tokens in, and for every served token how far its logit lies
below the reference's best. That covers chunked prefill, decode through the
paged cache and the scheduler's interleaving, on what the timed engine
itself emitted.
"""

from __future__ import annotations

import time

import numpy as np

from yardstick import compare, counts, harness, weights

WARM_RID = 1 << 30


class Driver:
    def __init__(self, cell, seed: int, devices, spans):
        self.cell, self.seed, self.devices, self.spans = (
            cell, int(seed), devices, spans)
        self.sizes = weights.sizes_of(cell.config)
        self.mix = cell.traffic
        self.eng = None
        self.offered: list = []
        self.served: dict[int, list[int]] = {}
        self.finished: list[int] = []
        # set by tests to break the timed path underneath (a wrapper
        # around the built engine); never set by the command
        self.wrap_engine = None

    def build(self):
        import jax
        import jax.numpy as jnp

        from distributed_tensorflow_guide_tpu.models.transformer import (
            TransformerConfig,
        )
        from distributed_tensorflow_guide_tpu.serve.engine import (
            Request,
            ServeEngine,
        )

        dep, z = self.cell.config["deployment"], self.sizes
        cfg = TransformerConfig(
            vocab_size=z["vocab"], num_layers=z["L"], num_heads=z["h"],
            d_model=z["d"], d_ff=z["ff"], max_len=z["positions"],
            causal=True, dtype=jnp.dtype(dep["compute_dtype"]))
        with jax.default_device(self.devices[0]):
            params = jax.jit(lambda s: weights.flax_tree(s, z))(
                weights.seed_arg(self.seed))
        eng = ServeEngine(
            cfg, params, slots=int(dep["slots"]),
            num_blocks=int(dep["num_blocks"]),
            block_size=int(dep["block_size"]),
            prefill_chunk=int(dep["prefill_chunk"]),
            temperature=float(dep["temperature"]))
        self.Request = Request
        # one throwaway request that takes both programs: two prefill
        # chunks, then decode steps
        chunk = int(dep["prefill_chunk"])
        warm = np.arange(chunk + 2, dtype=np.int32) % int(
            self.mix["vocab_below"])
        eng.submit(Request(rid=WARM_RID, prompt=warm, max_new_tokens=3,
                           rng=np.zeros((2,), np.uint32)))
        eng.run()
        eng.sched.pool.check_leaks()
        return eng

    def run(self, seconds: float, window) -> dict:
        from distributed_tensorflow_guide_tpu.ops.flash_attention import (
            fallback_stats,
        )
        from distributed_tensorflow_guide_tpu.serve.scheduler import DECODE

        eng = self.build()
        if self.wrap_engine is not None:
            eng = self.wrap_engine(eng)
        self.eng = eng
        self.offered = self.cell.generator.requests(self.mix, self.seed)
        by_rid = {r.rid: r for r in self.offered}
        queue = sorted(self.offered, key=lambda r: r.due)
        spans, Request = self.spans, self.Request

        ticks = []  # (kind, in the window?, [(rid, first, done)...])
        ended_badly = set()
        token_times: dict[int, list[float]] = {}
        state = {"next": 0, "tokens": 0, "open": None, "longest": 0.0}
        t0 = time.perf_counter()  # the engine's clock starts here

        def tick() -> str:
            """Submit what is due, run one engine step, keep what it
            handed back."""
            now = time.perf_counter() - t0
            opened = state["open"]
            since = now - (opened or now)
            i = state["next"]
            if i < len(queue) and queue[i].due <= since:
                with spans.span("submit"):
                    while i < len(queue) and queue[i].due <= since:
                        r = queue[i]
                        eng.submit(Request(
                            rid=r.rid, prompt=r.prompt,
                            max_new_tokens=r.max_new_tokens,
                            rng=np.asarray([0, r.rid], np.uint32),
                            arrival=now))
                        i += 1
                state["next"] = i
            with spans.span("engine_step"):
                events, kind = eng.step(now)
            after = time.perf_counter() - t0
            if opened is not None:  # a stalled host shows as one long tick
                state["longest"] = max(state["longest"], after - now)
            row = []
            for e in events:
                if e.status != "ok":
                    ended_badly.add(e.rid)
                    continue
                row.append((e.rid, e.first, e.done))
                if opened is not None:
                    state["tokens"] += 1
                    token_times.setdefault(e.rid, []).append(after - opened)
                    if e.done:
                        self.finished.append(e.rid)
            ticks.append((kind, opened is not None, row))
            return kind

        # the state a long job is in: the backlog submitted and every slot
        # decoding; filling them is set-up, not the window
        for _ in range(40 * len(eng.sched.slots)):
            tick()
            if all(s is not None and s.phase == DECODE
                   for s in eng.sched.slots):
                break
        else:
            raise RuntimeError("the slots never all reached decode")
        steps_before = dict(eng.steps)
        window.open()
        state["open"] = time.perf_counter() - t0
        while time.perf_counter() - t0 - state["open"] < seconds:
            if tick() == "idle" and state["next"] >= len(queue):
                break  # everything offered has been served
        window_s = time.perf_counter() - t0 - state["open"]
        window.close()

        self.served = {rid: toks for rid, toks in eng.completions().items()
                       if rid in by_rid}
        health = eng.health()
        launched = {k: eng.steps[k] - steps_before[k] for k in eng.steps}
        tokens = state["tokens"]
        facts = {
            "window_s": window_s, "tokens": tokens,
            "longest_tick_s": state["longest"],
            "requests_submitted": state["next"],
            "requests_finished": len(self.finished),
            "ticks": launched, "sizes": self.sizes,
            "preemptions": health["preemptions"],
            "launch_failures": health["launch_failures"],
            "token_times": token_times,
            "fallbacks": {str(k): v for k, v in fallback_stats().items()},
            **self.window_work(ticks, by_rid),
        }
        return {"attempted": state["next"],
                "failed": len(ended_badly) + health["launch_failures"],
                "end_to_end": {"serve_tokens_per_s": tokens / window_s},
                "facts": facts}

    def window_work(self, ticks, by_rid) -> dict:
        """What the window's launches had to do, from what they handed
        back: the model operations of the valid prompt and output tokens
        (padding rows and empty slots count nothing), and for each decode
        launch its rows and the live keys they attended."""
        z = self.sizes
        size = dict(d=z["d"], ff=z["ff"], layers=z["L"])
        flops = 0
        emitted: dict[int, int] = {}
        decode_launches = []
        for kind, in_window, row in ticks:
            rows = keys = 0
            for rid, first, _ in row:
                p = len(by_rid[rid].prompt)
                j = emitted.get(rid, 0)
                emitted[rid] = j + 1
                if not in_window:
                    continue
                flops += counts.head_flops(d=z["d"], vocab=z["vocab"])
                if kind == "prefill":
                    if first:  # the prompt's last chunk just ran
                        flops += counts.lm_span_flops(start=0, stop=p,
                                                      **size)
                else:
                    flops += counts.lm_token_flops(position=p + j - 1,
                                                   **size)
                    rows += 1
                    keys += p + j
            if kind == "decode" and in_window:
                decode_launches.append((rows, keys))
        # prompts still mid-prefill when the window closed
        chunk = self.eng.sched.prefill_chunk
        for s in self.eng.sched.slots:
            if (s is not None and s.rid in by_rid
                    and emitted.get(s.rid, 0) == 0):
                done = min(len(by_rid[s.rid].prompt), s.chunk_cursor * chunk)
                flops += counts.lm_span_flops(start=0, stop=done, **size)
        return {"model_flops": flops, "decode_launches": decode_launches}

    def release(self) -> None:
        if self.eng is not None:
            self.eng.close()
            self.eng.params = None
            self.eng.pool = None
            self.eng = None

    # ---- the comparison, after the window
    def sample(self) -> list[int]:
        """The finished requests to check: the longest, and others drawn
        from the seed."""
        if not self.finished:
            return []
        by_rid = {r.rid: r for r in self.offered}
        total = {rid: len(by_rid[rid].prompt) + len(self.served[rid])
                 for rid in self.finished}
        longest = max(sorted(total), key=total.get)
        rest = sorted(set(self.finished) - {longest})
        rng = np.random.default_rng([self.seed, 1])
        n = min(len(rest), int(self.mix["checked_requests"]) - 1)
        return [longest] + [int(x) for x in rng.choice(
            rest, size=n, replace=False)]

    def gaps(self, control: bool = False) -> dict[str, float]:
        """Over the sample's served tokens, the widest gap by which a
        served token's logit lies below the reference's best, and the mean
        gap (0 wherever the served token is the reference's own choice);
        with ``control`` also those of the token int8 operands put first."""
        import jax

        from yardstick.reference import gpt2

        z = self.sizes
        eps = float(weights.as_run(self.cell.config, "layer_norm_epsilon"))
        by_rid = {r.rid: r for r in self.offered}
        rids = self.sample()
        if not rids:
            return {"served_logit_gap": float("nan"),
                    "served_logit_gap_mean": float("nan"),
                    "checked_tokens": 0}
        params = jax.jit(lambda s: weights.stacked_tree(s, z))(
            weights.seed_arg(self.seed))
        one = jax.jit(lambda p, t, n, f: gpt2.served_gaps(
            p, t, n, f, eps=eps, control=control))
        worst = {"gap": 0.0, "control_gap": 0.0}
        total = {"gap": 0.0, "control_gap": 0.0}
        checked = 0
        for rid in rids:
            prompt, out = by_rid[rid].prompt, self.served[rid]
            toks = np.zeros((z["positions"],), np.int32)
            n = len(prompt) + len(out)
            toks[:n] = np.concatenate([prompt, np.asarray(out, np.int32)])
            got = jax.device_get(one(params, toks, np.int32(n),
                                     np.int32(len(prompt))))
            for k, v in got.items():
                worst[k] = max(worst[k], float(np.max(v)))
                total[k] += float(np.sum(v))
            checked += len(out)
        out = {"served_logit_gap": worst["gap"],
               "served_logit_gap_mean": total["gap"] / checked,
               "checked_tokens": checked}
        if control:
            out["control_logit_gap"] = worst["control_gap"]
            out["control_logit_gap_mean"] = total["control_gap"] / checked
        return out

    def check(self):
        numbers = self.gaps()
        # the widest gap is said, not compared: see the cell's limits file
        harness.say(checked_tokens=numbers.pop("checked_tokens"),
                    checked_requests=self.sample(),
                    served_logit_gap=numbers["served_logit_gap"])
        return compare.against_limits(
            numbers, compare.load_limits(self.cell.name))
