"""The serving driver for LFM2: ``drivers.serve.Driver``'s run (seeded
requests through ``ServeEngine.submit()`` and ``step(now)`` on the wall
clock, the window open once every slot decodes) over another model. What
differs is what is built (a patterned ``TransformerConfig`` and a bfloat16
tree from ``weights_lfm2``), how the window's work is counted
(``counts_lfm2``), and the reference the served tokens are held to
(``reference/lfm2.py``, the sampled requests through one layer at a time).
"""

from __future__ import annotations

import numpy as np

from yardstick import counts_lfm2, weights_lfm2
from yardstick.drivers import serve


class Driver(serve.Driver):
    def __init__(self, cell, seed: int, devices, spans):
        self.cell, self.seed, self.devices, self.spans = (
            cell, int(seed), devices, spans)
        self.sizes = weights_lfm2.sizes_of(cell.config)
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
        # first, and cheap: a program without patterned models stops here
        cfg = TransformerConfig(
            vocab_size=z["vocab"], num_layers=z["L"], num_heads=z["h"],
            d_model=z["d"], d_ff=z["ff"], max_len=z["positions"],
            causal=True, dtype=jnp.dtype(dep["compute_dtype"]),
            layers=z["layers"], norm="rmsnorm", norm_eps=z["eps"],
            ffn_gate="silu", rope_theta=z["theta"], num_kv_heads=z["kv"],
            qk_norm=True, conv_kernel=z["taps"], routed_experts=z["E"],
            routed_top_k=z["k"], routed_d_ff=z["eff"])
        with jax.default_device(self.devices[0]):
            params = weights_lfm2.flax_tree(self.seed, z)
        eng = ServeEngine(
            cfg, params, slots=int(dep["slots"]),
            num_blocks=int(dep["num_blocks"]),
            block_size=int(dep["block_size"]),
            prefill_chunk=int(dep["prefill_chunk"]),
            temperature=float(dep["temperature"]))
        self.Request = Request
        # one throwaway request that takes both programs: two prefill
        # chunks (the second reads the first's conv state), then decode
        chunk = int(dep["prefill_chunk"])
        warm = np.arange(chunk + 2, dtype=np.int32) % int(
            self.mix["vocab_below"])
        eng.submit(Request(rid=serve.WARM_RID, prompt=warm,
                           max_new_tokens=3,
                           rng=np.zeros((2,), np.uint32)))
        eng.run()
        eng.sched.pool.check_leaks()
        return eng

    def window_work(self, ticks, by_rid) -> dict:
        """As ``serve.Driver.window_work``, in LFM2's operations: per valid
        token the mixers, the router, four experts' products and the head;
        attention by live keys; padding rows and idle slots nothing."""
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
                flops += counts_lfm2.head_flops(z)
                if kind == "prefill":
                    if first:  # the prompt's last chunk just ran
                        flops += counts_lfm2.span_flops(z, start=0, stop=p)
                else:
                    flops += counts_lfm2.token_flops(z, position=p + j - 1)
                    rows += 1
                    keys += p + j
            if kind == "decode" and in_window:
                decode_launches.append((rows, keys))
        chunk = self.eng.sched.prefill_chunk
        for s in self.eng.sched.slots:  # prompts still mid-prefill
            if (s is not None and s.rid in by_rid
                    and emitted.get(s.rid, 0) == 0):
                done = min(len(by_rid[s.rid].prompt), s.chunk_cursor * chunk)
                flops += counts_lfm2.span_flops(z, start=0, stop=done)
        return {"model_flops": flops, "decode_launches": decode_launches}

    def gaps(self, control: bool = False) -> dict[str, float]:
        """As ``serve.Driver.gaps``, against ``reference/lfm2.py``: the
        sample goes through the reference together, a layer at a time."""
        import jax

        from yardstick.reference import lfm2

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
        toks = np.zeros((len(rows), -(-longest // 128) * 128), np.int32)
        for i, r in enumerate(rows):
            toks[i, :len(r)] = r
        got = jax.device_get(lfm2.served_gaps(
            self.seed, toks, np.asarray([len(r) for r in rows], np.int32),
            np.asarray([len(by_rid[rid].prompt) for rid in rids], np.int32),
            self.sizes, control=control))
        checked = sum(len(self.served[rid]) for rid in rids)
        out = {"served_logit_gap": float(np.max(got["gap"])),
               "served_logit_gap_mean": float(np.sum(got["gap"])) / checked,
               "checked_tokens": checked}
        if control:
            out["control_logit_gap"] = float(np.max(got["control_gap"]))
            out["control_logit_gap_mean"] = float(
                np.sum(got["control_gap"])) / checked
            out["choices_moved"] = int(got["choices_moved"])
            out["choices_checked"] = int(got["choices_checked"])
        return out
