"""The serving driver for Nemotron-H: ``drivers.serve.Driver``'s run (seeded
requests through ``ServeEngine.submit()`` and ``step(now)`` on the wall
clock, the window open once every slot decodes) over another model. What
differs is what is built (a ``TransformerConfig`` whose layers are a
Mamba-2 mixer, an attention mixer or a routed feed-forward alone, holding
this chip's share of the experts, and a bfloat16 tree from
``weights_nemotron``), how the window's work is counted
(``counts_nemotron``: of a token's six assignments, those the engine's
census says fell to held experts) and the reference the served tokens are
held to (``reference/nemotron_h.py``, the sampled requests through one
layer at a time).
"""

from __future__ import annotations

import numpy as np

from yardstick import counts_nemotron, weights_nemotron
from yardstick.drivers import serve


class Driver(serve.Driver):
    def __init__(self, cell, seed: int, devices, spans):
        self.cell, self.seed, self.devices, self.spans = (
            cell, int(seed), devices, spans)
        self.sizes = weights_nemotron.sizes_of(cell.config)
        self.mix = cell.traffic
        self.eng = None
        self.offered: list = []
        self.served: dict[int, list[int]] = {}
        self.finished: list[int] = []
        self.wrap_engine = None  # as in serve.Driver: tests only
        self.held_at_open = 0

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
        # first, and cheap: a program that lacks one of these sizes (a
        # mixer, a layer that is one half, no positions) stops here
        cfg = TransformerConfig(
            vocab_size=z["vocab"], num_layers=z["L"], num_heads=z["h"],
            d_model=z["d"], d_ff=z["ff"], max_len=z["positions"],
            causal=True, dtype=jnp.dtype(dep["compute_dtype"]),
            layers=z["layers"], norm="rmsnorm", norm_eps=z["eps"],
            ffn_gate="relu2", positions="none", num_kv_heads=z["kv"],
            override_head_dim=z["hd"], conv_kernel=z["taps"],
            ssm_heads=z["H"], ssm_head_dim=z["P"], ssm_groups=z["G"],
            ssm_state=z["N"], ssm_chunk=z["chunk"], routed_experts=z["E"],
            routed_top_k=z["k"], routed_d_ff=z["eff"],
            routed_d_ff_stored=z["eff_stored"], routed_first=z["first"],
            routed_count=z["held"], routed_scale=z["scale"],
            routed_norm_eps=1e-20, shared_d_ff=z["sff"])
        with jax.default_device(self.devices[0]):
            params = weights_nemotron.flax_tree(self.seed, z)
        eng = ServeEngine(
            cfg, params, slots=int(dep["slots"]),
            num_blocks=int(dep["num_blocks"]),
            block_size=int(dep["block_size"]),
            prefill_chunk=int(dep["prefill_chunk"]),
            temperature=float(dep["temperature"]))
        self.Request = Request
        # one throwaway request that takes both programs: two prefill
        # chunks (the second from the first's state), then decode
        chunk = int(dep["prefill_chunk"])
        warm = np.arange(chunk + 2, dtype=np.int32) % int(
            self.mix["vocab_below"])
        eng.submit(Request(rid=serve.WARM_RID, prompt=warm,
                           max_new_tokens=3,
                           rng=np.zeros((2,), np.uint32)))
        eng.run()
        eng.sched.pool.check_leaks()
        return eng

    def held_assignments(self) -> int:
        """The engine's running count of assignments to held experts."""
        return int(self.eng.health()["routed"]["held_assignments"])

    def run(self, seconds: float, window) -> dict:
        # the engine's census is a running sum: read it where the window
        # opens (the engine exists by then), and again in window_work
        opened = window.open

        def open_and_read():
            self.held_at_open = self.held_assignments()
            opened()

        window.open = open_and_read
        try:
            return super().run(seconds, window)
        finally:
            window.open = opened

    def window_work(self, ticks, by_rid) -> dict:
        """As ``serve.Driver.window_work``, in this model's operations: per
        valid token the mixers, the router, the shared expert and the
        head; attention by live keys; the routed experts' products by the
        assignments that fell to held experts in the window; padding rows
        and idle slots nothing."""
        z = self.sizes
        held = self.held_assignments() - self.held_at_open
        flops = held * counts_nemotron.expert_flops(z)
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
                flops += counts_nemotron.head_flops(z)
                if kind == "prefill":
                    if first:  # the prompt's last chunk just ran
                        flops += counts_nemotron.span_flops(z, start=0,
                                                            stop=p)
                else:
                    flops += counts_nemotron.token_flops(z,
                                                         position=p + j - 1)
                    rows += 1
                    keys += p + j
            if kind == "decode" and in_window:
                decode_launches.append((rows, keys))
        chunk = self.eng.sched.prefill_chunk
        for s in self.eng.sched.slots:  # prompts still mid-prefill
            if (s is not None and s.rid in by_rid
                    and emitted.get(s.rid, 0) == 0):
                done = min(len(by_rid[s.rid].prompt), s.chunk_cursor * chunk)
                flops += counts_nemotron.span_flops(z, start=0, stop=done)
        health = self.eng.health()
        return {"model_flops": flops, "decode_launches": decode_launches,
                "held_assignments": held,
                "pool_bytes": health["pool_bytes"],
                "state_bytes": health["state_bytes"]}

    def gaps(self, control: bool = False) -> dict[str, float]:
        """As ``serve.Driver.gaps``, against ``reference/nemotron_h.py``:
        the sample goes through the reference together, a layer at a
        time."""
        import jax

        from yardstick.reference import nemotron_h

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
        got = jax.device_get(nemotron_h.served_gaps(
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
