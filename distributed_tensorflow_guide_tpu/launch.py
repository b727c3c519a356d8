"""``python -m distributed_tensorflow_guide_tpu.launch`` — the run.sh killer.

Reference analogue (SURVEY.md §2 R9): every example ships a ``run.sh`` that
backgrounds 1 PS + N workers on localhost ports with ``--job_name`` /
``--task_index`` role flags, and supervises nothing — a crashed PS leaves
every worker hung on gRPC forever, and stale processes from the previous run
must be ``kill``-ed by hand.

The SPMD inversion: there are no roles, so the launcher spawns N *identical*
processes of the *same* command, differing only in ``JAX_PROCESS_ID``. It
synthesizes the coordinator env (the ``TF_CONFIG`` analogue —
tensorflow/python/distribute/cluster_resolver/tfconfig_cluster_resolver.py:48),
streams each child's output with a ``[p{k}]`` prefix, and supervises: on the
first nonzero exit the survivors get a grace period (peers blocked in a
collective on the dead rank never finish) and are then reaped, and the
launcher's exit code reflects the failure.

Usage::

    # 4-process CPU cluster, 2 virtual devices each (8 global devices):
    python -m distributed_tensorflow_guide_tpu.launch \
        --num-processes 4 --devices-per-process 2 --platform cpu \
        examples/mnist_sync_dp.py --steps 100

    # On a TPU pod each host runs the SAME command (no launcher needed);
    # this CLI is for single-host multi-process development and CI on
    # virtual CPU devices.

The launched script needs no flags parsing for topology: it just calls
``distributed_tensorflow_guide_tpu.core.dist.initialize()``, which reads the
env this launcher sets (core/dist.py DistConfig.from_env).
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

from .runtime.multiprocess import free_port, supervise


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m distributed_tensorflow_guide_tpu.launch",
        description="Spawn and supervise an N-process SPMD run on one host.",
    )
    p.add_argument("--num-processes", "-n", type=int, default=2)
    p.add_argument(
        "--devices-per-process", type=int, default=1,
        help="virtual CPU devices per process",
    )
    p.add_argument(
        "--platform", choices=["cpu"], default="cpu",
        help="children run on virtual CPU devices. A chip belongs to one "
        "process at a time, and N children started with one environment "
        "would all reach for the same chips: on a TPU host one process "
        "drives every local chip, and a pod runs the same command per host",
    )
    p.add_argument("--timeout", type=float, default=600.0,
                   help="wall-clock limit for the whole run (seconds)")
    p.add_argument("--failure-grace", type=float, default=10.0,
                   help="seconds survivors get after the first failure")
    p.add_argument("--coordinator-port", type=int, default=0,
                   help="0 = pick a free port")
    p.add_argument("--log-dir", type=Path, default=None,
                   help="also write per-process logs to DIR/p{k}.log")
    p.add_argument("--module", "-m", action="store_true",
                   help="treat the target as a module name (python -m)")
    p.add_argument("target", help="script path (or module with -m)")
    p.add_argument("args", nargs=argparse.REMAINDER,
                   help="arguments forwarded to the target")
    return p


def _child_env(ns: argparse.Namespace, coordinator: str, pid: int) -> dict:
    env = dict(os.environ)
    env["JAX_COORDINATOR_ADDRESS"] = coordinator
    env["JAX_NUM_PROCESSES"] = str(ns.num_processes)
    env["JAX_PROCESS_ID"] = str(pid)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_NUM_CPU_DEVICES"] = str(ns.devices_per_process)
    # Scrub a parent XLA_FLAGS device-count override that would fight the
    # per-process count above.
    env.pop("XLA_FLAGS", None)
    return env


def _stream(proc: subprocess.Popen, pid: int, log_file, lock: threading.Lock):
    """Tee one child's combined output to our stdout with a [p{k}] prefix."""
    for raw in proc.stdout:
        line = raw.decode("utf-8", "replace")
        with lock:
            sys.stdout.write(f"[p{pid}] {line}")
            sys.stdout.flush()
            if log_file is not None:
                log_file.write(line)
                log_file.flush()


def main(argv: list[str] | None = None) -> int:
    ns = _build_parser().parse_args(argv)
    if ns.args and ns.args[0] == "--":
        ns.args = ns.args[1:]
    port = ns.coordinator_port or free_port()
    coordinator = f"localhost:{port}"
    base_cmd = [sys.executable]
    base_cmd += ["-m", ns.target] if ns.module else [ns.target]
    base_cmd += ns.args

    if ns.log_dir is not None:
        ns.log_dir.mkdir(parents=True, exist_ok=True)

    procs: list[subprocess.Popen] = []
    logs = []
    lock = threading.Lock()
    threads = []
    print(
        f"launch: {ns.num_processes} processes, coordinator {coordinator}, "
        f"cmd: {' '.join(base_cmd)}",
        flush=True,
    )
    def _announce(bad: int, code: int) -> None:
        print(
            f"launch: process {bad} exited {code}; giving survivors "
            f"{ns.failure_grace:.0f}s grace",
            file=sys.stderr, flush=True,
        )

    timed_out = False
    try:
        # Spawning inside the try: if any open()/Popen in this loop fails
        # (e.g. unwritable --log-dir entry), the finally below reaps the
        # children already started instead of leaking them unsupervised.
        # Log file is opened BEFORE its child so a failure leaves no extra
        # untracked process.
        for pid in range(ns.num_processes):
            log_file = (
                open(ns.log_dir / f"p{pid}.log", "w", encoding="utf-8")
                if ns.log_dir is not None else None
            )
            logs.append(log_file)
            proc = subprocess.Popen(
                base_cmd,
                env=_child_env(ns, coordinator, pid),
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
            )
            procs.append(proc)
            t = threading.Thread(
                target=_stream, args=(proc, pid, log_file, lock), daemon=True
            )
            t.start()
            threads.append(t)

        timed_out = supervise(
            procs, timeout=ns.timeout, failure_grace=ns.failure_grace,
            on_first_failure=_announce,
        )
        if timed_out:
            print("launch: timeout; killed all", file=sys.stderr, flush=True)
    except KeyboardInterrupt:
        print("launch: interrupted; killing all", file=sys.stderr, flush=True)
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGINT)
        time.sleep(1.0)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        for t in threads:
            t.join(timeout=5.0)
        for f in logs:
            if f is not None:
                f.close()

    codes = [p.returncode for p in procs]
    ok = not timed_out and all(c == 0 for c in codes)
    print(f"launch: exit codes {codes}" + (" (timeout)" if timed_out else ""),
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
