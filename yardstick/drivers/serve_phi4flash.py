"""The serving driver for Phi-4-mini-flash: ``drivers.serve.Driver``'s run
(seeded requests through ``ServeEngine.submit()`` and ``step(now)`` on the
wall clock, the window open once every slot decodes) over another model.
What differs is what is built (a ``TransformerConfig`` whose layers are
Mamba-1 mixers, window attention, one full-attention layer, gated memory
units and cross-attention over that layer's cache, differential attention
and a head tied to the embedding; a bfloat16 tree from
``weights_phi4flash``), how the window's work is counted
(``counts_phi4flash``: a window layer's queries by the keys of their
window, the full layer's cache by every layer that reads it) and the
reference the served tokens are held to (``reference/phi4flash.py``, the
sampled requests through one layer at a time, a request at a time).
"""

from __future__ import annotations

import time

import numpy as np

from yardstick import counts_phi4flash, harness, weights_phi4flash
from yardstick.drivers import serve


class Driver(serve.Driver):
    def __init__(self, cell, seed: int, devices, spans):
        self.cell, self.seed, self.devices, self.spans = (
            cell, int(seed), devices, spans)
        self.sizes = weights_phi4flash.sizes_of(cell.config)
        self.mix = cell.traffic
        self.eng = None
        self.offered: list = []
        self.served: dict[int, list[int]] = {}
        self.finished: list[int] = []
        self.wrap_engine = None  # as in serve.Driver: tests only

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
        # first, and cheap: a program that lacks one of these mixers or
        # fields stops here
        cfg = TransformerConfig(
            vocab_size=z["vocab"], num_layers=z["L"], num_heads=z["h"],
            d_model=z["d"], d_ff=z["ff"], max_len=z["positions"],
            causal=True, dtype=jnp.dtype(dep["compute_dtype"]),
            layers=z["layers"], norm="layernorm", norm_eps=z["eps"],
            ffn_gate="silu", positions="none", num_kv_heads=z["kv"],
            conv_kernel=z["taps"], ssm_inner=z["inner"], ssm_state=z["N"],
            ssm_dt_rank=z["R"], window=z["window"], differential=True,
            attn_bias=True, tie_embeddings=True)
        t0 = time.perf_counter()
        with jax.default_device(self.devices[0]):
            params = jax.block_until_ready(
                weights_phi4flash.flax_tree(self.seed, z))
        t1 = time.perf_counter()
        eng = ServeEngine(
            cfg, params, slots=int(dep["slots"]),
            num_blocks=int(dep["num_blocks"]),
            block_size=int(dep["block_size"]),
            prefill_chunk=int(dep["prefill_chunk"]),
            temperature=float(dep["temperature"]))
        self.Request = Request
        # one throwaway request that takes both programs: two prefill
        # chunks (the second from the first's state and ring), then decode
        chunk = int(dep["prefill_chunk"])
        warm = np.arange(chunk + 2, dtype=np.int32) % int(
            self.mix["vocab_below"])
        eng.submit(Request(rid=serve.WARM_RID, prompt=warm,
                           max_new_tokens=3,
                           rng=np.zeros((2,), np.uint32)))
        eng.run()
        # set-up by its parts (the rest is the process's start and the
        # slots' filling): a fresh check-out compiles in both
        harness.say(weights_s=t1 - t0, engine_warm_s=time.perf_counter() - t1)
        eng.sched.pool.check_leaks()
        return eng

    def window_work(self, ticks, by_rid) -> dict:
        """As ``serve.Driver.window_work``, in this model's operations: per
        valid token the mixers, the feed-forwards and the head; the full
        layer and each layer that reads its cache by live keys, a window
        layer by the keys of its window; padding rows and idle slots
        nothing."""
        z = self.sizes
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
                flops += counts_phi4flash.head_flops(z)
                if kind == "prefill":
                    if first:  # the prompt's last chunk just ran
                        flops += counts_phi4flash.span_flops(z, start=0,
                                                             stop=p)
                else:
                    flops += counts_phi4flash.token_flops(
                        z, position=p + j - 1)
                    rows += 1
                    keys += p + j
            if kind == "decode" and in_window:
                decode_launches.append((rows, keys))
        chunk = self.eng.sched.prefill_chunk
        for s in self.eng.sched.slots:  # prompts still mid-prefill
            if (s is not None and s.rid in by_rid
                    and emitted.get(s.rid, 0) == 0):
                done = min(len(by_rid[s.rid].prompt), s.chunk_cursor * chunk)
                flops += counts_phi4flash.span_flops(z, start=0, stop=done)
        health = self.eng.health()
        return {"model_flops": flops, "decode_launches": decode_launches,
                "pool_bytes": health["pool_bytes"],
                "state_bytes": health["state_bytes"],
                "window_bytes": health["window_bytes"]}

    def gaps(self, control: bool = False, faults: tuple = ()) -> dict:
        """As ``serve.Driver.gaps``, against ``reference/phi4flash.py``: the
        sample goes through the reference together, a layer at a time. With
        ``control`` also the gaps of the token int8 operands put first, and
        for each of ``faults`` (``reference.phi4flash.FAULTS``) those of the
        token the faulty reference puts first, under ``<fault>_gap`` and
        ``<fault>_gap_mean``."""
        import jax

        from yardstick.reference import phi4flash

        by_rid = {r.rid: r for r in self.offered}
        rids = self.sample()
        if not rids:
            return {"served_logit_gap": float("nan"),
                    "served_logit_gap_mean": float("nan"),
                    "checked_tokens": 0}
        rows = [np.concatenate([by_rid[rid].prompt,
                                np.asarray(self.served[rid], np.int32)])
                for rid in rids]
        longest = max(len(r) for r in rows)
        # whole thousands: few lengths, so a run finds an earlier run's
        # compiled reference in the persistent cache
        toks = np.zeros((len(rows), -(-longest // 1024) * 1024), np.int32)
        for i, r in enumerate(rows):
            toks[i, :len(r)] = r
        harness.say(padded_to=toks.shape[1])
        got = jax.device_get(phi4flash.served_gaps(
            self.seed, toks, np.asarray([len(r) for r in rows], np.int32),
            np.asarray([len(by_rid[rid].prompt) for rid in rids], np.int32),
            self.sizes, control=control, faults=tuple(faults)))
        checked = sum(len(self.served[rid]) for rid in rids)
        out = {"served_logit_gap": float(np.max(got["gap"])),
               "served_logit_gap_mean": float(np.sum(got["gap"])) / checked,
               "checked_tokens": checked}
        if control:
            out["control_logit_gap"] = float(np.max(got["control_gap"]))
            out["control_logit_gap_mean"] = float(
                np.sum(got["control_gap"])) / checked
        for fault in faults:
            out[f"{fault}_gap"] = float(np.max(got[fault]))
            out[f"{fault}_gap_mean"] = float(np.sum(got[fault])) / checked
        return out
