"""Multi-host bootstrap — replaces the reference's cluster-bootstrap stack.

Reference call stack (SURVEY.md §3.1): ``bash run.sh`` spawns N+1 processes,
each builds ``tf.train.ClusterSpec`` and starts an in-process gRPC
``tf.train.Server`` (tensorflow/python/training/server_lib.py:96); PS
processes block in ``server.join()`` forever; the modern surface discovers
peers from the ``TF_CONFIG`` env JSON
(tensorflow/python/distribute/cluster_resolver/tfconfig_cluster_resolver.py:48).

TPU-native: that entire stack collapses to ``jax.distributed.initialize()``
per host (jax/_src/distributed.py) — a coordinator handshake over DCN after
which every host sees the global device set and runs the *same* SPMD program.
There is no PS process and no role flag.
"""

from __future__ import annotations

import dataclasses
import logging
import os

import jax

log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class DistConfig:
    """Multi-host coordination config.

    All fields optional: on TPU pods JAX auto-detects everything from the
    metadata server; on CPU/GPU clusters pass them explicitly or set
    JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID (the latter
    two are parsed by this framework via :meth:`from_env` and forwarded as
    kwargs — JAX itself only reads the coordinator address).
    """

    coordinator_address: str | None = None
    num_processes: int | None = None
    process_id: int | None = None

    @classmethod
    def from_env(cls) -> "DistConfig":
        """Read JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID.

        JAX itself only reads JAX_COORDINATOR_ADDRESS; the other two are
        this framework's convention and are parsed here and passed through
        as explicit kwargs.
        """
        nproc = os.environ.get("JAX_NUM_PROCESSES")
        pid = os.environ.get("JAX_PROCESS_ID")
        return cls(
            coordinator_address=os.environ.get("JAX_COORDINATOR_ADDRESS"),
            num_processes=int(nproc) if nproc is not None else None,
            process_id=int(pid) if pid is not None else None,
        )


_initialized = False


def retry_with_backoff(
    fn,
    *,
    attempts: int = 3,
    base_delay_s: float = 1.0,
    max_delay_s: float = 30.0,
    retry_on: tuple[type[BaseException], ...] = (RuntimeError, OSError),
    sleep=None,
    what: str = "operation",
):
    """Call ``fn()`` up to ``attempts`` times with exponential backoff.

    The coordinator handshake is the classic transient: process 0's
    listener may come up seconds after the peers dial in (the reference's
    run.sh had the same race and simply hung). Delay doubles per attempt
    from ``base_delay_s`` up to ``max_delay_s`` — deterministic, no
    jitter, so multi-process retries stay in lockstep with each other.
    The last failure re-raises unchanged.
    """
    if attempts < 1:
        raise ValueError(f"attempts must be >= 1, got {attempts}")
    import time

    sleep = time.sleep if sleep is None else sleep
    for attempt in range(attempts):
        try:
            return fn()
        except retry_on as e:
            if attempt + 1 >= attempts:
                raise
            delay = min(base_delay_s * 2 ** attempt, max_delay_s)
            log.warning(
                "%s failed (attempt %d/%d): %s — retrying in %.1fs",
                what, attempt + 1, attempts, e, delay,
            )
            sleep(delay)


def initialize(config: DistConfig | None = None) -> None:
    """Idempotent multi-host init. No-op for single-process runs.

    Single-process is detected when no coordinator is configured anywhere —
    the common case for tests and single-host benches.
    """
    global _initialized
    if _initialized:
        return
    # An explicitly passed config wins wholesale — env vars are only read
    # when no config is given (so stale JAX_* exports can't leak into an
    # explicit setup, and an explicit all-None config can't be promoted to a
    # multi-host handshake by the environment).
    explicit = config is not None
    config = config if explicit else DistConfig.from_env()
    coord, nproc, pid = (
        config.coordinator_address,
        config.num_processes,
        config.process_id,
    )
    # num_processes == 1 with no coordinator means "force single-process".
    # TPU_WORKER_HOSTNAMES with a single entry (e.g. "localhost" on a
    # single-host slice) is also a single-process run.
    multi_host_tpu = (not explicit) and "," in os.environ.get(
        "TPU_WORKER_HOSTNAMES", ""
    )
    if (coord is None and nproc is None and not multi_host_tpu) or (
        coord is None and nproc == 1
    ):
        log.debug("single-process run; skipping jax.distributed.initialize")
        return
    # The launcher's children need nothing more here: JAX reads
    # JAX_PLATFORMS and JAX_NUM_CPU_DEVICES from the environment at import.
    kwargs = {}
    if coord is not None:
        kwargs["coordinator_address"] = coord
    if nproc is not None:
        kwargs["num_processes"] = nproc
    if pid is not None:
        kwargs["process_id"] = pid
    # The handshake is retried with backoff: a coordinator that boots a few
    # seconds late (restarted chief, slow container) must not be fatal.
    # DTG_INIT_RETRIES=1 restores the old fail-immediately behavior.
    retry_with_backoff(
        lambda: jax.distributed.initialize(**kwargs),
        attempts=int(os.environ.get("DTG_INIT_RETRIES", "3")),
        base_delay_s=float(os.environ.get("DTG_INIT_BACKOFF_S", "1.0")),
        what="jax.distributed.initialize",
    )
    _initialized = True
    from distributed_tensorflow_guide_tpu.core.mesh import num_slices

    n_slices = num_slices()
    log.info(
        "distributed init: process %d/%d, %d local / %d global devices, "
        "%d slice(s)%s",
        jax.process_index(),
        jax.process_count(),
        jax.local_device_count(),
        jax.device_count(),
        n_slices,
        "" if n_slices == 1 else
        " — build_mesh will lay dcn_axis across slices (DCN), all other "
        "axes within-slice (ICI)",
    )


def reinitialize(config: DistConfig | None = None) -> None:
    """Tear down and re-run the coordinator handshake — the IN-PROCESS
    elastic-resize path: after a slice loss the surviving hosts re-form
    the cluster at the new (smaller) world size, and on slice return at
    the full one. (The relaunch-based resize — fresh processes per
    generation — lives in train/elastic_world.py and does not need this;
    this is for deployments that resize without relaunching.)

    Shutdown + initialize is *more* racy than first boot — the new
    coordinator only comes up after the old incarnation's port is
    released, and peers re-dial at slightly different times — so the
    whole cycle goes through :func:`retry_with_backoff`, governed by
    ``DTG_REINIT_RETRIES`` / ``DTG_REINIT_BACKOFF_S`` (mirroring the
    ``DTG_INIT_RETRIES`` / ``DTG_INIT_BACKOFF_S`` pair of first init).
    Unlike :func:`initialize` this is NOT idempotent: every call cycles
    the handshake, because a resize by definition changes the answer.

    With no coordinator configured anywhere (single-process), the cycle
    degrades to a best-effort shutdown — there is no cluster to re-form.
    """
    global _initialized
    explicit = config is not None
    config = config if explicit else DistConfig.from_env()
    coord, nproc, pid = (
        config.coordinator_address,
        config.num_processes,
        config.process_id,
    )

    def _shutdown() -> None:
        try:
            jax.distributed.shutdown()
        except Exception as e:  # not initialized / already torn down
            log.debug("jax.distributed.shutdown before reinit: %s", e)

    # Single-process detection MUST mirror initialize(): an env-driven TPU
    # pod (auto-detected coordinator, multi-entry TPU_WORKER_HOSTNAMES)
    # re-forms the cluster too — treating it as single-process would tear
    # the cluster down and never rebuild it. An explicit all-None config
    # keeps initialize()'s no-env-promotion guarantee.
    multi_host_tpu = (not explicit) and "," in os.environ.get(
        "TPU_WORKER_HOSTNAMES", ""
    )
    if (coord is None and nproc is None and not multi_host_tpu) or (
        coord is None and nproc == 1
    ):
        _shutdown()
        _initialized = False
        log.debug("single-process reinitialize: shutdown only")
        return
    # The flag drops BEFORE the cycle: if every retry fails, a caller that
    # catches and falls back to initialize() must not hit its idempotent
    # guard while the runtime is actually torn down.
    _initialized = False
    kwargs = {}
    if coord is not None:
        kwargs["coordinator_address"] = coord
    if nproc is not None:
        kwargs["num_processes"] = nproc
    if pid is not None:
        kwargs["process_id"] = pid

    def _cycle() -> None:
        _shutdown()
        jax.distributed.initialize(**kwargs)

    retry_with_backoff(
        _cycle,
        attempts=int(os.environ.get("DTG_REINIT_RETRIES", "3")),
        base_delay_s=float(os.environ.get("DTG_REINIT_BACKOFF_S", "1.0")),
        what="coordinator re-initialize (elastic resize)",
    )
    _initialized = True
    log.info(
        "elastic reinitialize: process %d/%d, %d global devices",
        jax.process_index(), jax.process_count(), jax.device_count(),
    )


def is_chief() -> bool:
    """Process 0 — the one that writes checkpoints/logs.

    Reference equivalent: ``is_chief=(task_index == 0)`` passed to
    ``MonitoredTrainingSession`` (tensorflow/python/training/monitored_session.py:428).
    Unlike the reference, chief-ness here affects only host-side IO; the
    device program is identical on every host.
    """
    return jax.process_index() == 0
