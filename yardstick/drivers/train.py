"""The training driver: a seeded stream of token batches through
``DataParallel.prefetch()`` into ``TrainLoop.run()`` over
``DataParallel.make_train_step(make_lm_loss_fn(Transformer(cfg)))``.

One ``TrainLoop`` is built and run once. Its first steps are set-up: step 1
compiles, and the three are what the reference follows (each step's loss,
the first gradient's per-leaf norms out of Adam's first moment, the
parameters' change after the three). Then the same loop, on the same feed,
is the window: steps until ``--seconds`` have passed, closed by
``block_until_ready`` on the last state. The host reads each step's loss one
step late, as a training script that logs does, so it runs one dispatch
ahead of the device and no further.
"""

from __future__ import annotations

import math
import time

from yardstick import compare, counts, weights

FOLLOWED = 3  # steps the reference follows


class _TimedFeed:
    """The iterator handed to ``TrainLoop``: a span around each ``next()``
    of the prefetch stage."""

    def __init__(self, it, spans):
        self.it, self.spans = iter(it), spans

    def __iter__(self):
        return self

    def __next__(self):
        with self.spans.span("input_wait"):
            return next(self.it)


class Driver:
    def __init__(self, cell, seed: int, devices, spans):
        self.cell, self.seed, self.devices, self.spans = (
            cell, int(seed), devices, spans)
        self.sizes = weights.sizes_of(cell.config)
        self.mix, self.generator = cell.traffic, cell.generator
        self.got: dict = {}
        self.loop = None
        # set by tests to break the timed path underneath (a wrapper
        # around the compiled step); never set by the command
        self.wrap_step = None

    # ---- the program, built the way examples/ and chip_smoke.py build it
    def build(self):
        import jax
        import jax.numpy as jnp
        import optax
        from flax.training import train_state
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from distributed_tensorflow_guide_tpu.core.mesh import (
            MeshSpec,
            build_mesh,
        )
        from distributed_tensorflow_guide_tpu.models.transformer import (
            Transformer,
            TransformerConfig,
            make_lm_loss_fn,
        )
        from distributed_tensorflow_guide_tpu.parallel.data_parallel import (
            DataParallel,
        )

        dep, z = self.cell.config["deployment"], self.sizes
        cfg = TransformerConfig(
            vocab_size=z["vocab"], num_layers=z["L"], num_heads=z["h"],
            d_model=z["d"], d_ff=z["ff"], max_len=z["positions"],
            causal=True, dtype=jnp.dtype(dep["compute_dtype"]),
            attn_impl=dep["attn_impl"])
        model = Transformer(cfg)
        dp = DataParallel(build_mesh(MeshSpec(data=-1), self.devices))
        opt = dict(dep["optimizer"])
        if opt.pop("name") != "adamw":
            raise ValueError("the train driver reads Adam's first moment: "
                             "optimizer.name must be adamw")
        self.opt = opt
        tx = optax.adamw(**opt)

        def make_state(seed):
            return train_state.TrainState.create(
                apply_fn=model.apply, params=weights.flax_tree(seed, z),
                tx=tx)

        replicated = NamedSharding(dp.mesh, P())
        state = jax.jit(make_state, out_shardings=replicated)(
            weights.seed_arg(self.seed))
        step = dp.make_train_step(
            make_lm_loss_fn(model, fused_ce=dep["fused_ce"]),
            donate=bool(dep["donate"]))

        b1 = opt["b1"]
        self.first_grad_norms = jax.jit(lambda st: weights.norms_of_flax(
            st.opt_state[0].mu, z) / (1.0 - b1))
        self.change_norms = jax.jit(
            lambda st, seed: weights.norms_of_flax(jax.tree.map(
                jnp.subtract, st.params, weights.flax_tree(seed, z)), z))
        feed = _TimedFeed(dp.prefetch(
            self.generator.batches(self.mix, self.seed),
            depth=int(self.mix["prefetch_depth"])), self.spans)
        return step, state, feed

    def run(self, seconds: float, window) -> dict:
        """``window`` has ``open()`` and ``close()``: the harness's clock
        for set-up and its profiler."""
        import jax

        from distributed_tensorflow_guide_tpu.ops.flash_attention import (
            fallback_stats,
        )
        from distributed_tensorflow_guide_tpu.train.hooks import BaseHook
        from distributed_tensorflow_guide_tpu.train.loop import TrainLoop

        step, state, feed = self.build()
        if self.wrap_step is not None:
            step = self.wrap_step(step)
        spans, driver = self.spans, self

        def dispatch(state, batch):
            with spans.span("dispatch"):
                return step(state, batch)

        class Phases(BaseHook):
            """Steps 1-3: read at once. After them the window: each loss
            read one step late, stop once the time is up."""

            def __init__(self):
                self.losses, self.late = [], None
                self.t_open = None
                self.window_losses = []

            def begin(self, loop):
                self.loop = loop

            def after_step(self, step_index, metrics):
                if step_index < FOLLOWED:
                    self.losses.append(float(metrics["loss"]))
                    if step_index == 0:
                        driver.got["grad_norms"] = jax.device_get(
                            driver.first_grad_norms(self.loop.state))
                    if step_index == FOLLOWED - 1:
                        driver.got["change_norms"] = jax.device_get(
                            driver.change_norms(
                                self.loop.state,
                                weights.seed_arg(driver.seed)))
                        driver.got["losses"] = list(self.losses)
                        window.open()
                        self.t_open = time.perf_counter()
                    return
                with spans.span("read_loss"):
                    if self.late is not None:
                        self.window_losses.append(float(self.late))
                self.late = metrics["loss"]
                if time.perf_counter() - self.t_open >= seconds:
                    self.loop.request_stop()

        phases = Phases()
        self.loop = TrainLoop(dispatch, state, feed, hooks=[phases])
        del state
        final = self.loop.run()
        with spans.span("fence"):
            jax.block_until_ready(final)
        t_close = time.perf_counter()
        window.close()
        phases.window_losses.append(float(phases.late))
        steps = len(phases.window_losses)
        window_s = t_close - phases.t_open
        tokens = steps * self.generator.tokens_per_step(self.mix)
        bad = sum(not math.isfinite(x) for x in phases.window_losses)
        return {
            "attempted": steps, "failed": bad,
            "end_to_end": {"train_tokens_per_s": tokens / window_s},
            "facts": {
                "window_s": window_s, "steps": steps, "tokens": tokens,
                "batch": int(self.mix["batch"]), "seq": int(self.mix["seq"]),
                "sizes": self.sizes, "program": "sm_step",
                "model_flops": steps * counts.lm_train_step_flops(
                    d=self.sizes["d"], ff=self.sizes["ff"],
                    layers=self.sizes["L"], vocab=self.sizes["vocab"],
                    batch=int(self.mix["batch"]), seq=int(self.mix["seq"])),
                "first_losses": self.got["losses"],
                "last_loss": phases.window_losses[-1],
                "fallbacks": {str(k): v
                              for k, v in fallback_stats().items()},
            },
        }

    def release(self) -> None:
        """Drop the program's state so the reference has the chip."""
        if self.loop is not None:
            self.loop.state = None
            self.loop = None

    # ---- the comparison, after the window
    def reference_readings(self, operands: str = "float32",
                           rows: slice | None = None,
                           frozen: bool = False) -> dict:
        import jax
        import numpy as np

        from yardstick.reference import gpt2

        batches = np.stack([
            self.generator.batch_at(self.mix, self.seed, i)["tokens"]
            for i in range(FOLLOWED)])
        if rows is not None:
            batches = batches[:, rows]
        z = self.sizes
        eps = float(weights.as_run(self.cell.config, "layer_norm_epsilon"))
        opt = self.opt

        def follow(seed, batches):
            return gpt2.train_readings(
                seed, list(batches), z, eps=eps, opt=opt, operands=operands,
                frozen=frozen)

        out = jax.device_get(jax.jit(follow)(
            weights.seed_arg(self.seed), batches))
        return {k: np.asarray(v) for k, v in out.items()}

    def check(self):
        numbers = compare.train_numbers(self.got, self.reference_readings())
        return compare.against_limits(
            numbers, compare.load_limits(self.cell.name))
