"""Tracing/profiling — the guide has none; the TF runtime it drives ships a
timeline/profiler (the TF wheel bundles ``_pywrap_profiler_plugin.so``; the
reference itself never calls it, SURVEY.md §5 tracing row).

TPU-native: ``jax.profiler`` writes XPlane traces viewable in
TensorBoard/XProf. This module is a thin, dependency-free veneer:

* :func:`trace` — context manager around ``jax.profiler.trace`` (start/stop
  a trace into a logdir).
* :class:`ProfilerHook` — train-loop hook that traces steps
  ``[start_step, end_step)``; the TF sibling is ``tf.train.ProfilerHook``
  (tensorflow/python/training/basic_session_run_hooks.py).

Named host spans are ``obs/tracing.span``'s business: the program's own
spans (``dtg.loop.*``, ``dtg.prefetch.*``, ``dtg.engine.*``) land in any
trace started here, on the device events' clock.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import time
from pathlib import Path
from typing import Any, Callable, Iterator

import jax

from distributed_tensorflow_guide_tpu.obs import events as obs_events
from distributed_tensorflow_guide_tpu.train.hooks import BaseHook

log = logging.getLogger("dtg.profiling")


# -- dispatch / host-gap accounting ------------------------------------------
#
# The overlap layer's instrument: how many executable dispatches did a run
# issue, and how much host time elapsed BETWEEN them (batch fetch, hook
# work, Python overhead)? Dispatch is async, so host gap is not device
# idleness per se — but it is the only part of the gap the host can cause,
# and it is exactly what multi-step dispatch (fewer, fatter dispatches) and
# device prefetch (puts issued ahead) exist to shrink. Counting it makes
# the win measurable instead of asserted.


@dataclasses.dataclass
class DispatchStats:
    """Counters for a stream of compiled-step dispatches."""

    dispatches: int = 0
    steps: int = 0          # optimizer steps = dispatches * steps_per_call
    host_gap_s: float = 0.0  # host time between consecutive dispatches
    dispatch_s: float = 0.0  # host time inside dispatch calls (enqueue cost)

    def as_dict(self) -> dict:
        out = {
            "dispatches": self.dispatches,
            "opt_steps": self.steps,
            "host_gap_s": round(self.host_gap_s, 4),
            "dispatch_enqueue_s": round(self.dispatch_s, 4),
        }
        if self.dispatches:
            out["host_gap_ms_per_dispatch"] = round(
                1e3 * self.host_gap_s / self.dispatches, 3)
        return out


class DispatchRecorder:
    """Wrap a compiled ``(state, batch) -> (state, metrics)`` step so every
    call updates a :class:`DispatchStats` — composable with any loop that
    drives a step function (TrainLoop keeps its own inline accounting; this
    is the standalone instrument for benches and ad-hoc loops)."""

    def __init__(self, step_fn: Callable[[Any, Any], tuple[Any, Any]],
                 steps_per_call: int = 1,
                 stats: DispatchStats | None = None):
        self.step_fn = step_fn
        self.steps_per_call = steps_per_call
        self.stats = stats if stats is not None else DispatchStats()
        self._last_return: float | None = None

    def __call__(self, state, batch):
        t0 = time.perf_counter()
        if self._last_return is not None:
            self.stats.host_gap_s += t0 - self._last_return
        out = self.step_fn(state, batch)
        self._last_return = time.perf_counter()
        self.stats.dispatch_s += self._last_return - t0
        self.stats.dispatches += 1
        self.stats.steps += self.steps_per_call
        return out


@contextlib.contextmanager
def trace(logdir: str | Path, *, create_perfetto_link: bool = False) -> Iterator[None]:
    """Trace everything inside the block into ``logdir`` (XPlane format).

    View with ``tensorboard --logdir <logdir>`` (profile tab / XProf).
    """
    logdir = str(logdir)
    with jax.profiler.trace(logdir, create_perfetto_link=create_perfetto_link):
        yield
    log.info("profiler trace written to %s", logdir)


class ProfilerHook(BaseHook):
    """Trace steps ``[start_step, end_step)`` of the training loop into
    ``logdir``. Chief-only is NOT enforced: on multi-host, every host traces
    its own devices (XProf merges by host); pass ``chief_only=True`` to
    restrict."""

    def __init__(self, logdir: str | Path, start_step: int = 10,
                 end_step: int = 15, chief_only: bool = False,
                 recorder=None):
        if end_step <= start_step:
            raise ValueError("end_step must be > start_step")
        self.logdir = str(logdir)
        self.start_step = start_step
        self.end_step = end_step
        self.chief_only = chief_only
        self._active = False
        # observability (PR 14): profiler.start/profiler.stop instants
        # in the flight recorder bracket the XPlane trace window
        self.rec = recorder if recorder is not None else obs_events.current()

    def _obs(self, kind: str, step: int | None) -> None:
        if self.rec.enabled:
            self.rec.emit(kind, cat="train", actor="profiler",
                          payload={"logdir": self.logdir, "step": step})

    def _enabled(self) -> bool:
        if not self.chief_only:
            return True
        from distributed_tensorflow_guide_tpu.core.dist import is_chief

        return is_chief()

    def begin(self, loop) -> None:
        # covers start_step == loop's first step (incl. 0) and warm resumes
        # that land inside the window, where the arming after_step never runs
        if self._active:
            # elastic restart reuses hook instances and the crashed attempt
            # never ran end(); JAX allows one active trace, so close it out
            jax.profiler.stop_trace()
            self._active = False
            self._obs("profiler.stop", None)
        first = getattr(loop, "step", 0)
        if self._enabled() and self.start_step <= first < self.end_step:
            jax.profiler.start_trace(self.logdir)
            self._active = True
            self._obs("profiler.start", first)

    def after_step(self, step: int, metrics) -> None:
        # after_step(step) runs once step `step` is done; start the trace
        # after step start_step-1 so it covers [start_step, end_step).
        if not self._enabled():
            return
        if (not self._active and self.start_step <= step + 1 < self.end_step):
            jax.profiler.start_trace(self.logdir)
            self._active = True
            self._obs("profiler.start", step + 1)
        elif self._active and step + 1 >= self.end_step:
            jax.profiler.stop_trace()
            self._active = False
            self._obs("profiler.stop", step + 1)
            log.info("profiler trace for steps [%d, %d) written to %s",
                     self.start_step, self.end_step, self.logdir)

    def end(self, step: int) -> None:
        if self._active:  # loop stopped mid-window
            jax.profiler.stop_trace()
            self._active = False
            self._obs("profiler.stop", step)
