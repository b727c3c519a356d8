"""The serving driver for openPangu-Ultra-MoE: ``drivers.serve.Driver``'s
run (seeded requests through ``ServeEngine.submit()`` and ``step(now)`` on
the wall clock, the window open once every slot decodes) over another
model. What differs is what is built (a ``TransformerConfig`` whose layers
are latent attention over a dense or routed feed-forward, each half between
two norms, holding this chip's share of the experts, and a bfloat16 tree
from ``weights_pangu``), how the window's work is counted (``counts_pangu``:
of a token's eight assignments, those the engine's census says fell to held
experts) and the reference the served tokens are held to
(``reference/pangu_moe.py``, the sampled requests through one layer at a
time).
"""

from __future__ import annotations

import numpy as np

from yardstick import counts_pangu, harness, weights_pangu
from yardstick.drivers import serve, serve_nemotron


def model_config(z: dict, dtype):
    """The program's configuration of the sizes ``z``
    (``weights_pangu.sizes_of``)."""
    from distributed_tensorflow_guide_tpu.models.transformer import (
        LatentSizes,
        TransformerConfig,
    )

    return TransformerConfig(
        vocab_size=z["vocab"], num_layers=z["L"], num_heads=z["h"],
        d_model=z["d"], d_ff=z["ff"], max_len=z["positions"], causal=True,
        dtype=dtype, layers=z["layers"], norm="rmsnorm", norm_eps=z["eps"],
        ffn_gate="silu", positions="rotary", rope_theta=z["theta"],
        latent=LatentSizes(q_rank=z["q_rank"], kv_rank=z["kv_rank"],
                           nope=z["nope"], rope=z["rope"], value=z["v"]),
        sandwich_norm=True, routed_experts=z["E"], routed_top_k=z["k"],
        routed_d_ff=z["eff"], routed_first=z["first"],
        routed_count=z["held"], routed_scale=z["scale"],
        routed_norm_eps=1e-20, shared_d_ff=z["sff"])


class Driver(serve_nemotron.Driver):
    def __init__(self, cell, seed: int, devices, spans):
        self.cell, self.seed, self.devices, self.spans = (
            cell, int(seed), devices, spans)
        self.sizes = weights_pangu.sizes_of(cell.config)
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

        from distributed_tensorflow_guide_tpu.serve.engine import (
            Request,
            ServeEngine,
        )

        dep, z = self.cell.config["deployment"], self.sizes
        # first, and cheap: a program without latent attention or the
        # sandwich's norms stops here
        cfg = model_config(z, jnp.dtype(dep["compute_dtype"]))
        with jax.default_device(self.devices[0]):
            params = weights_pangu.flax_tree(self.seed, z)
        eng = ServeEngine(
            cfg, params, slots=int(dep["slots"]),
            num_blocks=int(dep["num_blocks"]),
            block_size=int(dep["block_size"]),
            prefill_chunk=int(dep["prefill_chunk"]),
            temperature=float(dep["temperature"]))
        self.Request = Request
        # one throwaway request that takes both programs: two prefill
        # chunks, then decode
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
        """As ``serve_nemotron.Driver.window_work``, in this model's
        operations (``counts_pangu``): per valid token the attention's
        projections, the feed-forwards, the router, the shared expert and
        the head; attention by live keys; the routed experts' products by
        the assignments that fell to held experts in the window."""
        z = self.sizes
        held = self.held_assignments() - self.held_at_open
        flops = held * counts_pangu.expert_flops(z)
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
                flops += counts_pangu.head_flops(z)
                if kind == "prefill":
                    if first:  # the prompt's last chunk just ran
                        flops += counts_pangu.span_flops(z, start=0, stop=p)
                else:
                    flops += counts_pangu.token_flops(z, position=p + j - 1)
                    rows += 1
                    keys += p + j
            if kind == "decode" and in_window:
                decode_launches.append((rows, keys))
        chunk = self.eng.sched.prefill_chunk
        for s in self.eng.sched.slots:  # prompts still mid-prefill
            if (s is not None and s.rid in by_rid
                    and emitted.get(s.rid, 0) == 0):
                done = min(len(by_rid[s.rid].prompt), s.chunk_cursor * chunk)
                flops += counts_pangu.span_flops(z, start=0, stop=done)
        return {"model_flops": flops, "decode_launches": decode_launches,
                "held_assignments": held,
                "pool_bytes": self.eng.health()["pool_bytes"]}

    def gaps(self, control: bool = False, faults: tuple = ()) -> dict:
        """As ``serve.Driver.gaps``, against ``reference/pangu_moe.py``:
        the sample goes through the reference together, a layer at a time;
        for each of ``faults`` (``reference.pangu_moe.FAULTS``) also the
        gaps of the token the faulty reference puts first, under
        ``<fault>_gap`` and ``<fault>_gap_mean``."""
        import jax

        from yardstick.reference import pangu_moe

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
        got = jax.device_get(pangu_moe.served_gaps(
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
            out["choices_moved"] = int(got["choices_moved"])
            out["choices_checked"] = int(got["choices_checked"])
        for fault in faults:
            out[f"{fault}_gap"] = float(np.max(got[fault]))
            out[f"{fault}_gap_mean"] = float(np.sum(got[fault])) / checked
        return out
