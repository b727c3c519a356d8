"""Device prefetch + batch packing — the input half of the hot-path overlap
layer.

Reference equivalent: ``tf.data``'s ``MultiDeviceIterator`` /
``prefetch_to_device`` (tensorflow/python/data/ops/multi_device_iterator_ops.py)
— the piece that made MonitoredTrainingSession-era input pipelines overlap
host→device transfer with device compute. The guide itself fed everything
through ``feed_dict``, paying a synchronous host copy per step.

TPU-native shape of the same idea: ``jax.device_put`` onto a mesh
``NamedSharding`` is *asynchronous* — it returns as soon as the transfer is
enqueued. A bounded lookahead that issues the put for batch N+1 (and N+2,
at ``depth=3``) while the consumer's dispatched step N still computes is
therefore enough to hide the transfer; no thread is needed on top of the
C++ loader's own background prefetch ring (data/native_loader.py), which
already overlaps disk/shuffle/gather with everything here.

Two composable pieces:

* :func:`pack_batches` — stack ``k`` host batches into one
  ``steps_per_call`` super-batch (leading axis = inner step) for the
  multi-step compiled dispatch (parallel/data_parallel.py ``_compile_step``
  with ``stacked_batch=True``).
* :class:`DevicePrefetchIterator` — the double/triple-buffered device
  placement stage, with :class:`PrefetchStats` accounting so the overlap is
  *measured*, not asserted, and the spans ``prefetch.host_fetch`` and
  ``prefetch.put`` (obs/tracing.span) so a profiler session sees each
  fetch and each put beside the device's own events.

Donation safety: every batch becomes a FRESH device allocation (a
``device_put`` result); the iterator drops its own reference before the
batch is yielded, so a step compiled with the batch argument donated can
reuse those buffers freely — nothing here ever re-reads a yielded array.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Iterable, Iterator

import numpy as np

from distributed_tensorflow_guide_tpu.obs import events as obs_events
from distributed_tensorflow_guide_tpu.obs.tracing import span


@dataclasses.dataclass
class PrefetchStats:
    """Host-side accounting for one prefetch stream.

    ``host_wait_s`` is time blocked in the upstream host iterator —
    with the native loader's prefetch ring warm this stays near zero;
    ``put_s`` is time spent *issuing* transfers (not completing them:
    device_put is async); ``peak_ahead`` is the largest number of batches
    that were resident ahead of the consumer, i.e. proof the buffer
    actually double-buffers.
    """

    batches: int = 0
    host_wait_s: float = 0.0
    put_s: float = 0.0
    peak_ahead: int = 0
    max_host_wait_s: float = 0.0  # worst single upstream fetch (stall signal)

    def as_dict(self) -> dict:
        return {
            "prefetch_batches": self.batches,
            "prefetch_host_wait_s": round(self.host_wait_s, 4),
            "prefetch_put_s": round(self.put_s, 4),
            "prefetch_peak_ahead": self.peak_ahead,
            "prefetch_max_host_wait_s": round(self.max_host_wait_s, 4),
        }


def pack_batches(batches: list) -> Any:
    """Stack ``k`` same-structure host batches along a new leading axis.

    The result is the ``stacked_batch`` layout of the multi-step compiled
    step: leaf shape ``(k, per_step_batch, ...)``, consumed one slice per
    inner ``lax.scan`` step. Stacking happens on host (numpy): the packed
    batch crosses to the device as ONE transfer, which is the point — k
    small puts become one big one per dispatch.
    """
    if not batches:
        raise ValueError("pack_batches needs at least one batch")
    import jax

    return jax.tree.map(lambda *xs: np.stack(xs), *batches)


def pack_stream(source: Iterable, steps_per_call: int,
                *, drop_remainder: bool = True) -> Iterator[Any]:
    """Iterate ``source`` in packs of ``steps_per_call`` stacked batches.

    A tail shorter than ``steps_per_call`` cannot feed the fixed-length
    scan; ``drop_remainder=True`` (default) drops it, ``False`` yields the
    short stack (caller must handle it — e.g. TrainLoop's tail_step_fn
    unpacks and runs the stragglers one dispatch each).
    """
    if steps_per_call < 1:
        raise ValueError(f"steps_per_call must be >= 1, got {steps_per_call}")
    it = iter(source)
    while True:
        pack: list = []
        for _ in range(steps_per_call):
            try:
                pack.append(next(it))
            except StopIteration:
                break
        if len(pack) == steps_per_call:
            yield pack_batches(pack)
        else:
            if pack and not drop_remainder:
                yield pack_batches(pack)
            return


class DevicePrefetchIterator:
    """Keep up to ``depth`` batches resident on device ahead of the consumer.

    ``depth=2`` is classic double buffering (batch N+1 transfers while step
    N computes); ``depth=3`` additionally rides out one slow host batch.
    ``put_fn`` owns placement — pass the strategy's ``shard_batch`` (or its
    packed-batch sibling) so multi-process SPMD placement keeps working;
    the default is a plain ``jax.device_put`` onto ``sharding`` (or the
    backend default when that is None too).

    The refill happens on every ``__next__``: pop the head, then top the
    buffer back up — so the puts for the *next* batches are enqueued before
    the consumer dispatches its step, and the transfer overlaps that step's
    compute. This is the MultiDeviceIterator contract without a host
    thread; with the native loader upstream, its C++ prefetch ring is the
    thread.
    """

    def __init__(self, source: Iterable, *, sharding: Any = None,
                 depth: int = 2,
                 put_fn: Callable[[Any], Any] | None = None,
                 max_host_wait_s: float | None = None):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        if max_host_wait_s is not None and max_host_wait_s <= 0:
            raise ValueError(
                f"max_host_wait_s must be > 0, got {max_host_wait_s}")
        self._src = iter(source)
        self.depth = depth
        self.max_host_wait_s = max_host_wait_s
        self.stats = PrefetchStats()
        self._rec = obs_events.current()  # resolved once, as everywhere
        if put_fn is not None:
            self._put = put_fn
        else:
            import jax

            if sharding is not None:
                self._put = lambda b: jax.device_put(b, sharding)
            else:
                self._put = jax.device_put
        self._buf: deque = deque()
        self._exhausted = False

    def _fill(self) -> None:
        while len(self._buf) < self.depth and not self._exhausted:
            t0 = time.perf_counter()
            try:
                with span(self._rec, "prefetch.host_fetch"):
                    host_batch = next(self._src)
            except StopIteration:
                self._exhausted = True
                return
            t1 = time.perf_counter()
            self.stats.host_wait_s += t1 - t0
            self.stats.max_host_wait_s = max(self.stats.max_host_wait_s,
                                             t1 - t0)
            if (self.max_host_wait_s is not None
                    and t1 - t0 > self.max_host_wait_s):
                # fail-fast: a data stall becomes a recoverable error
                # instead of silently eating the run's wall-clock budget
                # (the in-flight-hang half is the TrainLoop watchdog's job —
                # this deadline catches slow-but-returning fetches)
                from distributed_tensorflow_guide_tpu.utils.watchdog import (
                    DataStallError,
                )

                raise DataStallError(
                    f"data iterator stalled: one fetch took "
                    f"{t1 - t0:.2f}s > max_host_wait_s="
                    f"{self.max_host_wait_s:g}s "
                    f"(after {self.stats.batches} batches)"
                )
            with span(self._rec, "prefetch.put"):
                self._buf.append(self._put(host_batch))
            t2 = time.perf_counter()
            self.stats.put_s += t2 - t1
            self.stats.peak_ahead = max(self.stats.peak_ahead,
                                        len(self._buf))

    def __iter__(self) -> "DevicePrefetchIterator":
        return self

    def __next__(self) -> Any:
        self._fill()
        if not self._buf:
            raise StopIteration
        batch = self._buf.popleft()
        self.stats.batches += 1
        # refill NOW so the next transfers are in flight before the caller
        # dispatches its step — this is the line that buys the overlap
        self._fill()
        return batch


def prefetch_to_device(source: Iterable, *, sharding: Any = None,
                       depth: int = 2,
                       put_fn: Callable[[Any], Any] | None = None,
                       steps_per_call: int = 1,
                       drop_remainder: bool = True,
                       max_host_wait_s: float | None = None,
                       ) -> DevicePrefetchIterator:
    """One-call assembly of the input overlap stage.

    ``steps_per_call > 1`` inserts :func:`pack_stream` upstream, so each
    yielded item is one stacked super-batch per multi-step dispatch, already
    on device. Host batches in, device batches out, in order.
    """
    if steps_per_call > 1:
        source = pack_stream(source, steps_per_call,
                             drop_remainder=drop_remainder)
    return DevicePrefetchIterator(source, sharding=sharding, depth=depth,
                                  put_fn=put_fn,
                                  max_host_wait_s=max_host_wait_s)
