"""Training hooks — the MonitoredTrainingSession hook system, TPU-native.

Reference equivalents (all in
tensorflow/python/training/basic_session_run_hooks.py):
  LoggingTensorHook:169  → :class:`LoggingHook`
  StopAtStepHook:393     → :class:`StopAtStepHook`
  CheckpointSaverHook:524→ :class:`CheckpointHook` (train/checkpoint.py, orbax)
  StepCounterHook:674    → :class:`StepCounterHook`
  SummarySaverHook:793   → :class:`MetricsJSONLHook` (JSONL instead of TB protos)

Differences by design: hooks here never touch the device program (no
``before_run`` graph feeds — the step is a compiled SPMD function); they see
only host-side step numbers and already-fetched metric values. Only the chief
process writes (SURVEY.md §5 observability row).
"""

from __future__ import annotations

import json
import logging
import time
from pathlib import Path
from typing import Any, Mapping, Protocol

from distributed_tensorflow_guide_tpu.core.dist import is_chief
from distributed_tensorflow_guide_tpu.obs.metrics import (
    Registry,
    absorb_dispatch,
)

log = logging.getLogger("dtg.train")


class Hook(Protocol):
    """Lifecycle: begin() once, after_step() per step, end() once."""

    def begin(self, loop: "Any") -> None: ...  # noqa: E704

    def after_step(self, step: int, metrics: Mapping[str, float]) -> None: ...  # noqa: E704

    def end(self, step: int) -> None: ...  # noqa: E704


class BaseHook:
    def begin(self, loop) -> None:
        pass

    def after_step(self, step: int, metrics: Mapping[str, float]) -> None:
        pass

    def end(self, step: int) -> None:
        pass


class StopAtStepHook(BaseHook):
    """Signal the loop to stop at ``last_step``
    (tensorflow/python/training/basic_session_run_hooks.py:393)."""

    def __init__(self, last_step: int):
        self.last_step = last_step
        self._loop = None

    def begin(self, loop) -> None:
        self._loop = loop
        if loop.step >= self.last_step:  # resumed already-finished run
            loop.request_stop()

    def after_step(self, step: int, metrics) -> None:
        if step + 1 >= self.last_step:
            self._loop.request_stop()


class LoggingHook(BaseHook):
    """Log scalar metrics every N steps
    (tensorflow/python/training/basic_session_run_hooks.py:169)."""

    def __init__(self, every_steps: int = 100):
        self.every_steps = every_steps

    def after_step(self, step: int, metrics) -> None:
        if is_chief() and step % self.every_steps == 0:
            parts = ", ".join(f"{k}={float(v):.6g}" for k, v in metrics.items())
            log.info("step %d: %s", step, parts)


class StepCounterHook(BaseHook):
    """steps/sec + examples/sec — the guide's only quantitative signal
    (tensorflow/python/training/basic_session_run_hooks.py:674), extended with
    the BASELINE.json examples/sec/chip metric."""

    def __init__(self, every_steps: int = 100, batch_size: int | None = None,
                 n_chips: int = 1):
        self.every_steps = every_steps
        self.batch_size = batch_size
        self.n_chips = max(n_chips, 1)
        self._t0: float | None = None
        self._step0 = 0
        self.last_steps_per_sec: float | None = None
        self.last_examples_per_sec_per_chip: float | None = None

    def after_step(self, step: int, metrics) -> None:
        if step % self.every_steps:
            return
        now = time.perf_counter()
        if self._t0 is not None and step > self._step0:
            sps = (step - self._step0) / (now - self._t0)
            self.last_steps_per_sec = sps
            msg = f"{sps:.2f} steps/sec"
            if self.batch_size:
                eps = sps * self.batch_size / self.n_chips
                self.last_examples_per_sec_per_chip = eps
                msg += f", {eps:.1f} examples/sec/chip"
            if is_chief():
                log.info("step %d: %s", step, msg)
        self._t0, self._step0 = now, step


class TensorBoardHook(BaseHook):
    """Write scalar metrics as real TensorBoard event files — the closest
    sibling of SummarySaverHook
    (tensorflow/python/training/basic_session_run_hooks.py:793), using the
    dependency-free proto encoder in utils/tb_writer.py. Chief-only."""

    def __init__(self, logdir, every_steps: int = 1):
        self.logdir = logdir
        self.every_steps = every_steps
        self._writer = None

    def begin(self, loop) -> None:
        if self._writer is not None:  # elastic restart reuses hook instances
            self._writer.close()
            self._writer = None
        if is_chief():
            from distributed_tensorflow_guide_tpu.utils.tb_writer import (
                SummaryWriter,
            )

            self._writer = SummaryWriter(self.logdir)

    def after_step(self, step: int, metrics: Mapping[str, float]) -> None:
        if self._writer and step % self.every_steps == 0:
            self._writer.scalars(
                step, {k: float(v) for k, v in metrics.items()}
            )

    def end(self, step: int) -> None:
        if self._writer:
            self._writer.close()
            self._writer = None


class MetricsHook(BaseHook):
    """Opt-in bridge from the loop to the obs metrics plane: every step
    bumps ``dtg_train_steps_total`` and mirrors scalar metrics into
    gauges; every ``every_steps`` the loop's dispatch stats are absorbed
    and (optionally) the whole registry snapshot goes to a
    ``utils/tb_writer.SummaryWriter`` via ``log_metrics``. Reading a
    metric value syncs it to host — same cost as LoggingHook, and the
    reason this hook is opt-in rather than default."""

    def __init__(self, registry: Registry | None = None, *,
                 every_steps: int = 10, writer=None):
        self.registry = registry if registry is not None else Registry()
        self.every_steps = every_steps
        self.writer = writer
        self._loop = None

    def begin(self, loop) -> None:
        self._loop = loop

    def after_step(self, step: int, metrics) -> None:
        reg = self.registry
        reg.counter("dtg_train_steps_total",
                    "optimizer steps observed by MetricsHook").inc()
        for k, v in metrics.items():
            try:
                fv = float(v)
            except (TypeError, ValueError):
                continue
            reg.gauge(f"dtg_train_metric_{k}").set(fv)
        if step % self.every_steps:
            return
        stats = getattr(self._loop, "dispatch_stats", None)
        if stats is not None:
            absorb_dispatch(reg, stats)
        if self.writer is not None:
            self.writer.log_metrics(reg.snapshot(), step)

    def end(self, step: int) -> None:
        stats = getattr(self._loop, "dispatch_stats", None)
        if stats is not None:
            absorb_dispatch(self.registry, stats)
        if self.writer is not None:
            self.writer.log_metrics(self.registry.snapshot(), step)


class MetricsJSONLHook(BaseHook):
    """Append one JSON object per logged step to a file — the SummarySaverHook
    (tensorflow/python/training/basic_session_run_hooks.py:793) equivalent,
    with JSONL instead of TF summary protos so anything can consume it."""

    def __init__(self, path: str | Path, every_steps: int = 1):
        self.path = Path(path)
        self.every_steps = every_steps
        self._fh = None

    def begin(self, loop) -> None:
        if is_chief():
            self.path.parent.mkdir(parents=True, exist_ok=True)
            self._fh = self.path.open("a")

    def after_step(self, step: int, metrics) -> None:
        if self._fh and step % self.every_steps == 0:
            rec = {"step": step, "time": time.time()}
            rec.update({k: float(v) for k, v in metrics.items()})
            self._fh.write(json.dumps(rec) + "\n")
            self._fh.flush()

    def end(self, step: int) -> None:
        if self._fh:
            self._fh.close()
            self._fh = None
