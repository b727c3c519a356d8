#!/usr/bin/env python
"""Minimal repro for the dense-attention >=1024-token compile failure.

Round 2-4 observed that the full GPT-2 pipeline with ``attn_impl="dense"``
failed to COMPILE on the v5 lite chip at seq >= 1024 under remat (jax
0.4.37), while the Pallas flash kernel ran. Not reproduced since.
``attn_impl="auto"`` papers over it; this script isolates the smallest
program that reproduces the failure so the root cause can be diagnosed
rather than worked around (VERDICT r4 missing #4).

Bisection axes, each a flag: sequence length, remat on/off, layers 1..N,
full model vs a single attention block, vocab head on/off. Run with
``--dump DIR`` to get the XLA HLO dump for the failing case.

Prints one JSON line per tried config:
    {"case": ..., "seq": N, "remat": b, "ok": b, "error": "...", "secs": t}
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.common import device_setup


def try_case(case: str, seq: int, remat: bool, layers: int,
             batch: int) -> dict:
    import dataclasses

    import jax
    import numpy as np

    from distributed_tensorflow_guide_tpu.models.transformer import (
        Transformer,
        gpt2_124m,
        make_lm_loss_fn,
    )

    cfg = dataclasses.replace(
        gpt2_124m(remat=remat, attn_impl="dense"), max_len=seq,
        num_layers=layers)
    if case == "block":
        # attention sub-layer only: embed -> 1 block -> mean (no vocab head)
        cfg = dataclasses.replace(cfg, num_layers=1)
    model = Transformer(cfg)
    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens[:1])["params"]

    # fused_ce=False: this repro must keep building the HISTORICAL failing
    # program (full-vocab logits head) — the fused-CE auto default would
    # silently rewrite the "head on" bisection axis on TPU, the one
    # platform the repro targets.
    loss_fn = make_lm_loss_fn(model, fused_ce=False)
    if case == "fwd":
        fn = jax.jit(lambda p, t: loss_fn(p, {"tokens": t})[0])
    else:  # fwd+bwd — the training path that failed
        fn = jax.jit(jax.grad(lambda p, t: loss_fn(p, {"tokens": t})[0]))

    t0 = time.time()
    out = fn(params, tokens)
    jax.block_until_ready(out)
    return {"secs": round(time.time() - t0, 1)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", type=int, nargs="+",
                    default=[512, 1024, 2048])
    ap.add_argument("--cases", nargs="+",
                    default=["fwd", "grad"],
                    choices=["fwd", "grad", "block"])
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--remat", choices=["on", "off", "both"], default="both")
    ap.add_argument("--dump", default="",
                    help="XLA dump dir (sets --xla_dump_to before import)")
    ap.add_argument("--fake-devices", type=int, default=0)
    args = ap.parse_args()

    if args.dump:
        import os

        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_dump_to={args.dump}").strip()
    device_setup(args.fake_devices)

    remats = {"on": [True], "off": [False], "both": [False, True]}[args.remat]
    for seq in args.seqs:
        for case in args.cases:
            for remat in remats:
                rec = {"case": case, "seq": seq, "remat": remat,
                       "layers": args.layers, "batch": args.batch}
                try:
                    rec.update(try_case(case, seq, remat, args.layers,
                                        args.batch), ok=True)
                except Exception as e:  # noqa: BLE001 — repro must survive
                    rec.update(
                        ok=False,
                        error=f"{type(e).__name__}: "
                              + " ".join(str(e).split())[:2000])
                    traceback.print_exc(file=sys.stderr)
                print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
