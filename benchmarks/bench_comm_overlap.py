#!/usr/bin/env python
"""Exposed-communication microbench: the ICI overlap layer's judge.

Times a transformer-LM data-parallel (or FSDP) train step three ways in
one process:

  * ``floor``   — a no-collective step (gradients applied unreduced):
                  same forward/backward/update compute, zero gradient
                  wire traffic. The compute floor.
  * ``off``     — the monolithic schedule (one pmean after the full
                  gradient tree / GSPMD's inferred FSDP schedule).
  * ``on``      — the overlap schedule (bucketed backward all-reduce /
                  manual per-leaf gather-scatter, parallel/overlap.py).

From those it reports the closed-form per-device ``comm_bytes``
(benchmarks/common.py ring models), the measured wire rate
``ici_gb_per_s = comm_bytes / (off − floor)`` with its
``ici_roofline_frac`` against the chip's ICI peak, and the
``exposed_comm_frac = (selected − floor) / selected`` — the fraction of
the step still spent with the ICI serialized against compute, i.e. what
the overlap schedule failed to hide. ``--overlap`` / ``--fsdp-prefetch``
pick which side is the HEADLINE value (one-variable battery rows:
``comm_overlap_*`` pins off, ``dp_overlap``/``fsdp_prefetch`` pin on);
the A/B itself always runs.

``--tune`` (DP mode) sweeps the gradient-bucket candidates on chip and
records the winner into the autotune table, after which every
``overlap=True`` DP call site picks it up. ``--xla-overlap`` applies the
async-collective libtpu flag set first (echoed as ``xla_overlap``).

Off-TPU this prints an explicit skip line (rc=0) — exposed-comm fractions
only mean something against a real interconnect; ``--fake-devices 8
--small`` runs the CPU liveness check the smoke suite uses.

NOTE on a single chip: world=1 makes every comm model zero and the three
steps near-identical — the row still runs (continuity), but the numbers
that matter need a real multi-chip data axis.
"""

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.common import (
    device_setup,
    dp_allreduce_bytes,
    fsdp_comm_bytes,
    ici_extras,
    report,
    time_steps,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["dp", "fsdp"], default="dp")
    ap.add_argument("--overlap", choices=["auto", "on", "off"],
                    default="off",
                    help="dp mode: which side is the headline value "
                         "(the on/off/floor A/B always runs)")
    ap.add_argument("--fsdp-prefetch", choices=["auto", "on", "off"],
                    default="off",
                    help="fsdp mode: which side is the headline value")
    ap.add_argument("--compress", choices=["off", "int8"], default="off",
                    help="dp mode: gradient wire representation for the "
                         "overlap ('on') side — 'int8' quantizes each "
                         "bucket to int8 around the psum with a shared "
                         "per-bucket f32 scale (quarter the grad bytes + "
                         "a 4-byte pmax side-channel per bucket); "
                         "numerics-changing, so never auto")
    ap.add_argument("--bucket-mb", type=float, default=None,
                    help="dp mode: explicit gradient-bucket budget in MiB "
                         "(default: autotune table, else the tested "
                         "static fallback)")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--d-ff", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=8192)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=64)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--tune", action="store_true",
                    help="dp mode: sweep the bucket candidates on chip and "
                         "record the winner into the autotune table first")
    ap.add_argument("--xla-overlap", action="store_true",
                    help="apply the async-collective libtpu flag set "
                         "(parallel/overlap.py XLA_OVERLAP_FLAGS) before "
                         "backend init; echoed in the JSON line")
    ap.add_argument("--small", action="store_true",
                    help="tiny CPU-liveness geometry")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="run off-TPU instead of skipping")
    ap.add_argument("--fake-devices", type=int, default=0)
    args = ap.parse_args()

    # device_setup FIRST (its XLA device-count flag must precede any
    # package import, which imports jax); the libtpu overlap flags only
    # need to land before the first backend USE, which is later
    device_setup(args.fake_devices)
    from distributed_tensorflow_guide_tpu.parallel import overlap as ov

    xla_overlap = ov.apply_xla_overlap_flags(args.xla_overlap or None)
    import jax
    import jax.numpy as jnp

    platform = jax.default_backend()
    on_tpu = platform == "tpu"
    if not on_tpu and not (args.fake_devices or args.allow_cpu):
        # explicit skip, not rc=1: the battery records it as skipped
        print(json.dumps({
            "metric": f"comm_overlap_{args.mode}",
            "value": None,
            "unit": "tokens/sec",
            "vs_baseline": None,
            "skipped": f"no TPU transport (backend={platform}); exposed-"
                       "comm fractions only mean something against a real "
                       "interconnect — use --fake-devices 8 --small for "
                       "the liveness check",
        }))
        return

    import numpy as np
    import optax
    from flax.training import train_state

    from jax import shard_map
    from distributed_tensorflow_guide_tpu.core.dist import initialize
    from distributed_tensorflow_guide_tpu.core.mesh import (
        MeshSpec,
        build_mesh,
    )
    from distributed_tensorflow_guide_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
        make_lm_loss_fn,
    )
    from distributed_tensorflow_guide_tpu.ops import autotune
    from distributed_tensorflow_guide_tpu.parallel.data_parallel import (
        DataParallel,
    )
    from distributed_tensorflow_guide_tpu.parallel.fsdp import FSDP

    initialize()
    L, D, F, H = args.layers, args.d_model, args.d_ff, args.heads
    V, S, B, iters = args.vocab, args.seq_len, args.global_batch, args.steps
    if args.small:
        L, D, F, H, V, S, B = 2, 64, 128, 4, 256, 32, 16
        iters = min(iters, 3)

    mesh = build_mesh(MeshSpec(data=-1))
    n_dev = mesh.devices.size
    if B % n_dev:
        sys.exit(f"--global-batch must divide by {n_dev} devices")

    # fused_ce pinned OFF: the loss path must not move with the comm knob
    # (the round-7 one-variable lesson — this bench A/Bs the SCHEDULE)
    cfg = TransformerConfig(
        vocab_size=V, num_layers=L, num_heads=H, d_model=D, d_ff=F,
        max_len=S, causal=True, dtype=jnp.float32)
    model = Transformer(cfg)
    loss_fn = make_lm_loss_fn(model, fused_ce=False)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, S), jnp.int32))["params"]
    grad_bytes = sum(l.size * np.dtype(l.dtype).itemsize
                     for l in jax.tree.leaves(params))

    rng = np.random.RandomState(0)
    tokens = rng.randint(0, V, (B, S)).astype(np.int32)

    def fresh_state():
        return train_state.TrainState.create(
            apply_fn=model.apply, params=params, tx=optax.sgd(1e-2))

    def timed(step, state, batch):
        dt, _ = time_steps(step, state, batch, warmup=args.warmup,
                           steps=iters)
        return dt / iters

    bucket_bytes = (int(args.bucket_mb * (1 << 20))
                    if args.bucket_mb else None)
    results: dict[str, float] = {}
    extras: dict = {"mode": args.mode, "world": n_dev,
                    "xla_overlap": xla_overlap,
                    "layers": L, "d_model": D, "seq_len": S,
                    "global_batch": B, "vocab": V,
                    "grad_bytes": int(grad_bytes)}

    # the compute floor (shared by both modes): a replicated-param sharded
    # step with gradients applied UNREDUCED (numerically wrong on purpose
    # — replicas diverge) — identical forward/backward/update compute,
    # zero gradient collectives; the single scalar metric pmean that
    # remains is noise-level traffic
    from jax.sharding import PartitionSpec as P

    import distributed_tensorflow_guide_tpu.collectives as cc

    def floor_body(state, batch):
        (loss, _), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, batch)
        state = state.apply_gradients(grads=grads)
        return state, {"loss": cc.pmean(loss, "data")}

    floor_step = jax.jit(shard_map(
        floor_body, mesh=mesh, in_specs=(P(), P("data")),
        out_specs=(P(), P()), check_vma=False))
    dp_repl = DataParallel(mesh)
    repl_batch = dp_repl.shard_batch({"tokens": tokens})
    results["floor"] = timed(floor_step, dp_repl.replicate(fresh_state()),
                             repl_batch)

    compress = ov.resolve_compress(args.compress)
    if compress and args.mode != "dp":
        sys.exit("--compress int8 rides the bucketed DP backward "
                 "(--mode dp)")
    if args.mode == "dp":
        headline = "on" if ov.resolve_overlap(args.overlap) else "off"
        if args.tune and on_tpu:
            dp_t = DataParallel(mesh)

            def measure(bb):
                dpb = DataParallel(mesh, overlap=True, bucket_bytes=bb)
                st = dpb.replicate(fresh_state())
                bt = dpb.shard_batch({"tokens": tokens})
                stp = dpb.make_train_step(loss_fn, donate=False)
                return timed(stp, st, bt)

            autotune.ensure_bucket_tuned(
                param_bytes=grad_bytes, world=dp_t.world,
                dtype=jnp.float32, measure=measure)
        dp_off = DataParallel(mesh)
        dp_on = DataParallel(mesh, overlap=True, bucket_bytes=bucket_bytes,
                             compress=args.compress)
        batch = repl_batch

        results["off"] = timed(dp_off.make_train_step(loss_fn, donate=False),
                               dp_off.replicate(fresh_state()), batch)
        step_on = dp_on.make_train_step(loss_fn, donate=False)
        results["on"] = timed(step_on, dp_on.replicate(fresh_state()), batch)
        comm_bytes = dp_allreduce_bytes(grad_bytes, n_dev)
        # modeled vs measured wire bytes for the ON side: the closed-form
        # ring model against what an abstract re-trace of the on-step
        # actually records at the collective wrappers (payloads ring-
        # adjusted the same way). Uncompressed they agree up to the two
        # scalar metric pmeans; int8 drops the grad term ~4x and adds the
        # per-bucket 4-byte scale pmax side-channel.
        with cc.trace_comm() as rec:
            jax.eval_shape(step_on, jax.eval_shape(fresh_state), batch)
        frac = (n_dev - 1) / n_dev
        traced = sum(2.0 * b * frac for b in rec.bytes.values())
        extras["grad_comm_bytes_modeled_on"] = round(
            dp_allreduce_bytes(grad_bytes, n_dev, compress=compress), 1)
        extras["comm_bytes_traced_on"] = round(traced, 1)
        extras["traced_payload_bytes_on"] = {
            key: int(v) for key, v in sorted(rec.bytes.items())}
        extras["bucket_bytes"] = dp_on.bucket_bytes or (
            autotune.bucket_bytes_for(
                param_bytes=grad_bytes, world=n_dev,
                dtype=np.int8 if compress else jnp.float32))
        extras["tuned"] = bool(args.tune and on_tpu)
    else:
        headline = "on" if ov.resolve_prefetch(args.fsdp_prefetch) else "off"

        def fsdp_side(prefetch):
            import flax.linen as nn

            f = FSDP(mesh, min_shard_size=2 ** 10, prefetch=prefetch)

            def init_fn():
                return nn.meta.unbox(model.init(
                    jax.random.PRNGKey(0),
                    jnp.zeros((1, S), jnp.int32)))["params"]

            p, sh = f.init_params(init_fn)
            st = train_state.TrainState.create(
                apply_fn=model.apply, params=p, tx=optax.sgd(1e-2))
            st_sh = f.state_shardings(st, sh)
            st = jax.device_put(st, st_sh)
            from jax.sharding import NamedSharding, PartitionSpec as P

            bt = jax.device_put({"tokens": tokens},
                                NamedSharding(mesh, P("data")))
            return f, f.make_train_step(loss_fn, st_sh, donate=False), st, bt

        f_off, step_off, st_off, bt = fsdp_side(False)
        _, step_on, st_on, _ = fsdp_side(True)

        results["off"] = timed(step_off, st_off, bt)
        results["on"] = timed(step_on, st_on, bt)

        sharded_bytes = sum(
            l.size * np.dtype(l.dtype).itemsize
            for l, sh in zip(jax.tree.leaves(params),
                             jax.tree.leaves(f_off.param_shardings(
                                 jax.eval_shape(lambda: params))))
            if any(s is not None for s in tuple(sh.spec)))
        comm_bytes = fsdp_comm_bytes(
            sharded_bytes, n_dev,
            replicated_grad_bytes=grad_bytes - sharded_bytes)
        extras["sharded_param_bytes"] = int(sharded_bytes)

    dt_sel = results[headline]
    comm_secs = max(results["off"] - results["floor"], 0.0)
    exposed = max(dt_sel - results["floor"], 0.0)
    n_tokens = B * S
    extras.update({
        "overlap": headline,
        "compress": args.compress,
        "secs_floor": round(results["floor"], 6),
        "secs_off": round(results["off"], 6),
        "secs_on": round(results["on"], 6),
        "tokens_per_sec_off": round(n_tokens / results["off"], 1),
        "tokens_per_sec_on": round(n_tokens / results["on"], 1),
        "exposed_comm_frac": round(exposed / dt_sel, 4) if dt_sel else None,
        "overlap_saving_frac": round(
            (results["off"] - results["on"]) / results["off"], 4)
        if results["off"] else None,
        **ici_extras(comm_bytes, comm_secs if comm_secs > 0 else None),
    })
    report(f"comm_overlap_{args.mode}", n_tokens / dt_sel, "tokens/sec",
           **extras)


if __name__ == "__main__":
    main()
