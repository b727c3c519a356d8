"""The training loop — MonitoredTrainingSession, TPU-native.

Reference equivalent: ``tf.train.MonitoredTrainingSession``
(tensorflow/python/training/monitored_session.py:428) driving
``while not sess.should_stop(): sess.run(train_op)`` with hooks.

Here the loop drives a *compiled SPMD step function* instead of a session:
``state, metrics = step_fn(state, batch)``. The function is expected to be
``jax.jit``-ed (the strategy layers in ``parallel/`` produce it); the loop
itself stays off the hot path — it only touches host-side Python between
dispatches, and fetches metric values asynchronously (they are jax.Arrays;
conversion blocks only when a hook actually reads them).

``steps_per_call > 1`` is the hot-path overlap mode — the TF
``steps_per_run`` knob threaded through the whole stack: ``step_fn`` is a
multi-step compiled program (``parallel/data_parallel.py _compile_step``
with ``stacked_batch=True, per_step_metrics=True``), each dispatch consumes
one stacked super-batch of ``k`` host batches (data/prefetch.py packs and
prefetches them), and the loop fans the scan's per-step metrics back out so
hooks still observe EVERY optimizer step — logging cadence, step counters
and JSONL records are unchanged from the single-step loop. What coarsens is
only stop granularity: a stop requested by a hook takes effect at the next
dispatch boundary, so a run may overshoot the requesting step by up to
``k - 1`` steps (sized so the common StopAtStepHook(n) with ``k | n``
overshoots by zero). Dispatch counts and the host time between dispatches
are accounted in ``dispatch_stats`` (utils/profiling.py) so the overlap
the mode buys is measurable.
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Iterable, Iterator, Sequence

from distributed_tensorflow_guide_tpu.obs import events as obs_events
from distributed_tensorflow_guide_tpu.obs.tracing import span
from distributed_tensorflow_guide_tpu.train.hooks import Hook

log = logging.getLogger("dtg.train")

StepFn = Callable[[Any, Any], tuple[Any, dict]]


class TrainLoop:
    """Drive ``step_fn`` over batches until a hook requests a stop.

    Unlike MonitoredTrainingSession there is no chief/non-chief split in the
    device program — every process executes the same compiled step; hooks
    internally no-op on non-chief processes where appropriate.

    With ``steps_per_call=k > 1``, ``data`` must yield one PACKED item per
    dispatch (leading axis = inner step, e.g. from
    ``DataParallel.prefetch(..., steps_per_call=k)`` or
    ``data/prefetch.py pack_stream``) and ``step_fn`` must be compiled with
    ``per_step_metrics=True`` so each metric carries the leading ``k`` axis
    the loop fans back out to hooks. A final short pack (fewer than ``k``
    stacked batches) is handed to ``tail_step_fn`` — a SINGLE-step compiled
    sibling of ``step_fn`` — one dispatch per straggler; without one the
    tail is dropped with a warning (pass ``drop_remainder=True`` upstream
    to make that explicit).
    """

    def __init__(
        self,
        step_fn: StepFn,
        state: Any,
        data: Iterable,
        hooks: Sequence[Hook] = (),
        start_step: int = 0,
        steps_per_call: int = 1,
        tail_step_fn: StepFn | None = None,
        step_deadline_s: float | None = None,
        data_deadline_s: float | None = None,
        watchdog_action: Any = "interrupt",
        watchdog_diag_path: Any = None,
        recorder: Any = None,
        online_tune: bool | None = None,
    ):
        if steps_per_call < 1:
            raise ValueError(
                f"steps_per_call must be >= 1, got {steps_per_call}")
        # online in-situ autotuning (round 21): True/False set the
        # process-wide autotune override (the tuning table is process
        # state, so the knob is too), None inherits DTG_ONLINE_TUNE. The
        # first dispatch's trace then sweeps unseen kernel keys in situ
        # on a sweep-capable backend; always a no-op on CPU.
        if online_tune is not None:
            from distributed_tensorflow_guide_tpu.ops import autotune
            autotune.set_online_tune(online_tune)
        self.step_fn = step_fn
        self.state = state
        self.data = data
        self.hooks = list(hooks)
        self.step = start_step
        self.steps_per_call = steps_per_call
        self.tail_step_fn = tail_step_fn
        # Watchdog deadlines (utils/watchdog.py): ``data_deadline_s`` bounds
        # one fetch from the data iterator, ``step_deadline_s`` bounds one
        # dispatch + hook fan-out (NOT device completion — dispatch is
        # async; a wedged device surfaces here at the next blocking metric
        # read, which the step guard covers). A trip dumps all-thread
        # stacks and converts the hang into a fail-fast WatchdogTimeout
        # (action="interrupt") or a process exit the multiprocess
        # supervisor restarts (action="kill").
        self.step_deadline_s = step_deadline_s
        self.data_deadline_s = data_deadline_s
        self.watchdog_action = watchdog_action
        self.watchdog_diag_path = watchdog_diag_path
        self._stop = False
        self.stop_reason: str | None = None
        self._last_return: float | None = None
        # observability: observe-only spans (obs/tracing.span) around the
        # data wait, the dispatch and the hooks of every step: on the
        # profiler's clock whenever a session runs, and in this recorder
        # when it is enabled. Resolved once; nothing recorded ever feeds
        # the compiled step (50-step bitwise parity pinned).
        self.rec = recorder if recorder is not None else obs_events.current()
        from distributed_tensorflow_guide_tpu.utils.profiling import (
            DispatchStats,
        )

        self.dispatch_stats = DispatchStats()

    def request_stop(self, reason: str = "hook") -> None:
        """Hook-callable stop signal (``sess.should_stop()`` equivalent).

        ``reason`` lets end-phase hooks adapt: PreemptionHook passes
        "preemption" so e.g. EvalHook skips its final full eval pass
        inside the SIGTERM grace window. Must be set identically on every
        host (the callers' stop decisions are collective-agreed) — end
        hooks run collectives, and a host-divergent reason would deadlock
        them. First stop wins; later calls don't overwrite the reason."""
        if not self._stop:
            self.stop_reason = reason
        self._stop = True

    @property
    def should_stop(self) -> bool:
        return self._stop

    # ---- internals ---------------------------------------------------------

    def _dispatch(self, step_fn, batch):
        """One compiled dispatch with host-gap/dispatch accounting."""
        import time

        t0 = time.perf_counter()
        if self._last_return is not None:
            self.dispatch_stats.host_gap_s += t0 - self._last_return
        with span(self.rec, "loop.dispatch", step=self.step):
            self.state, metrics = step_fn(self.state, batch)
        self._last_return = time.perf_counter()
        self.dispatch_stats.dispatch_s += self._last_return - t0
        self.dispatch_stats.dispatches += 1
        return metrics

    def _after_step(self, metrics) -> None:
        # where a hook that reads a metric's value waits for the device
        with span(self.rec, "loop.hooks", step=self.step):
            for h in self.hooks:
                h.after_step(self.step, metrics)
        self.step += 1
        self.dispatch_stats.steps += 1

    def _pack_len(self, batch) -> int:
        """Leading-axis length of a packed super-batch (= inner steps)."""
        import jax

        return int(jax.tree.leaves(batch)[0].shape[0])

    def _run_packed(self, batch) -> None:
        """Dispatch one packed item and fan per-step metrics to hooks."""
        import jax

        k = self._pack_len(batch)
        if k < self.steps_per_call:
            # short tail pack: one single-step dispatch per straggler
            if self.tail_step_fn is None:
                log.warning(
                    "dropping a tail pack of %d < steps_per_call=%d "
                    "batches (no tail_step_fn); pass drop_remainder=True "
                    "upstream to silence, or a tail_step_fn to run them",
                    k, self.steps_per_call)
                return
            for j in range(k):
                if self._stop:
                    return
                single = jax.tree.map(lambda x, j=j: x[j], batch)
                self._after_step(self._dispatch(self.tail_step_fn, single))
            return
        metrics = self._dispatch(self.step_fn, batch)
        if not jax.tree.leaves(metrics):  # metric-less step: nothing to slice
            for _ in range(k):
                self._after_step(metrics)
            return
        lead = {getattr(m, "shape", (None,))[0] if getattr(m, "ndim", 1)
                else None for m in jax.tree.leaves(metrics)}
        if lead != {k}:
            raise ValueError(
                f"steps_per_call={self.steps_per_call} needs per-step "
                f"metrics (leading axis {k}); got leading sizes {lead} — "
                "compile the step with per_step_metrics=True")
        # every inner step happened on device; hooks observe each in order
        # (stop requests coarsen to the dispatch boundary, documented above)
        for j in range(k):
            self._after_step(jax.tree.map(lambda x, j=j: x[j], metrics))

    def run(self) -> Any:
        """Run to completion; returns the final state.

        ``end`` hooks fire only on *clean* completion. On a crash the loop
        re-raises without finalizing: with async dispatch, ``self.state`` may
        already hold poisoned arrays from the failed step, and an end-of-run
        checkpoint of it would overwrite the last good resume point
        (train/elastic.py restores strictly pre-crash checkpoints instead).

        A hook may additionally define ``cleanup()``: it runs in a
        ``finally`` on BOTH paths — the place to release process-global
        resources (e.g. PreemptionHook's signal handlers) that must not
        outlive a crashed loop, while keeping state-finalizing work in
        ``end`` where crashes rightly skip it.
        """
        self._last_return = None
        wd = None
        if self.step_deadline_s or self.data_deadline_s:
            from distributed_tensorflow_guide_tpu.utils.watchdog import (
                Watchdog,
            )

            wd = Watchdog(name="train-loop", action=self.watchdog_action,
                          diag_path=self.watchdog_diag_path,
                          recorder=self.rec)
        try:
            try:
                # begin() inside the try: if a later hook's begin raises,
                # the finally still runs cleanup() for already-begun hooks
                # (e.g. PreemptionHook's process-wide signal handler)
                for h in self.hooks:
                    h.begin(self)
                it: Iterator = iter(self.data)
                rec = self.rec
                while not self._stop:
                    if wd and self.data_deadline_s:
                        wd.arm("data iterator", self.data_deadline_s)
                    try:
                        with span(rec, "loop.data_wait", step=self.step):
                            batch = next(it)
                    except StopIteration:
                        break
                    finally:
                        if wd:
                            wd.disarm()
                            wd.check()
                    if wd and self.step_deadline_s:
                        wd.arm("train step", self.step_deadline_s)
                    if self.steps_per_call > 1:
                        self._run_packed(batch)
                    else:
                        self._after_step(
                            self._dispatch(self.step_fn, batch))
                    if wd:
                        wd.disarm()
                        wd.check()
                for h in self.hooks:
                    h.end(self.step)
            finally:
                if wd is not None:
                    wd.close()
                for h in self.hooks:
                    cleanup = getattr(h, "cleanup", None)
                    if cleanup is not None:
                        cleanup()
            return self.state
        except KeyboardInterrupt:
            # an "interrupt"-action watchdog trip arrives as
            # KeyboardInterrupt wherever the main thread happens to be
            # executing — possibly a few bytecodes late, inside the
            # cleanup finally above, which is why this converter wraps the
            # WHOLE body: check() re-raises the clean fail-fast error; a
            # genuine Ctrl-C (no trip recorded) re-raises untouched
            if wd is not None:
                wd.check()
            raise
