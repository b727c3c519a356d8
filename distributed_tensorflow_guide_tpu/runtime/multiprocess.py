"""Multi-process test/launch harness — the ``MultiProcessRunner`` equivalent.

Reference analogue (SURVEY.md §4): TF's ``MultiProcessRunner``
(tensorflow/python/distribute/multi_process_runner.py:107) forks real
processes with a synthesized ``TF_CONFIG``, captures per-process logs, and
propagates subprocess failures — true multi-worker semantics on one machine.
The guide itself had only ``run.sh`` with *no* supervision: a dead PS hangs
every worker forever (SURVEY.md §5 failure-detection row).

This runner spawns real OS processes, each a separate JAX *controller*:
it synthesizes the coordinator env (the ``TF_CONFIG`` analogue), calls
``jax.distributed.initialize`` per process, runs the target function, and
returns its JSON result. Gloo-backed CPU collectives give genuine
cross-process ``psum`` semantics with zero TPU chips, so the same SPMD code
paths exercised here run unchanged on a multi-host pod slice.

Unlike ``run.sh`` the runner *supervises*: per-process exit codes, captured
stdout/stderr, a wall-clock timeout, and kill-the-rest-on-failure. Fault
injection = ``runner.kill(i)`` — the analogue of killing a PS process, but
detected instead of hanging.
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Sequence

_RESULT_SENTINEL = "DTG_MP_RESULT "

_BOOTSTRAP = r"""
import json, os, sys, importlib

spec = json.loads(os.environ["DTG_MP_SPEC"])
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_num_cpu_devices", spec["local_devices"])
jax.distributed.initialize(
    spec["coordinator"],
    num_processes=spec["num_processes"],
    process_id=spec["process_id"],
    initialization_timeout=spec["init_timeout"],
)
mod, _, fn = spec["target"].rpartition(":")
result = getattr(importlib.import_module(mod), fn)(*spec["args"])
print("DTG_MP_RESULT " + json.dumps(result), flush=True)
"""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def supervise(
    procs: Sequence[subprocess.Popen],
    *,
    timeout: float,
    failure_grace: float,
    on_first_failure: Callable[[int, int], None] | None = None,
) -> bool:
    """Poll a process group until all exit, the first failure's grace period
    expires, or the deadline hits; then kill and reap any stragglers.

    The shared supervision core for both the test runner (:class:`
    MultiProcessRunner`) and the CLI launcher (launch.py): the moment any
    process exits nonzero, ``on_first_failure(process_id, code)`` fires once
    and the survivors get ``failure_grace`` seconds (peers blocked in a
    collective on the dead rank never finish) before being killed. Returns
    True iff the wall-clock deadline was hit.
    """
    deadline = time.monotonic() + timeout
    fail_deadline = None
    timed_out = False
    while True:
        codes = [p.poll() for p in procs]
        if all(c is not None for c in codes):
            break
        now = time.monotonic()
        if fail_deadline is None and any(c not in (None, 0) for c in codes):
            if on_first_failure is not None:
                bad = next(
                    i for i, c in enumerate(codes) if c not in (None, 0)
                )
                on_first_failure(bad, codes[bad])
            fail_deadline = now + failure_grace
        if now >= deadline:
            timed_out = True
            break
        if fail_deadline is not None and now >= fail_deadline:
            break
        time.sleep(0.05)
    for p in procs:
        if p.poll() is None:
            p.kill()
            p.wait()
    return timed_out


@dataclasses.dataclass
class ProcessResult:
    process_id: int
    returncode: int | None  # None = still running / never finished
    stdout: str
    stderr: str
    result: Any = None  # target's JSON return value, if it finished

    @property
    def ok(self) -> bool:
        return self.returncode == 0


class MultiProcessError(RuntimeError):
    def __init__(self, msg: str, results: list[ProcessResult]):
        super().__init__(msg)
        self.results = results


class MultiProcessRunner:
    """Run ``target`` in N separate JAX controller processes.

    ``target``: a module-level callable (or ``"pkg.mod:fn"`` string) taking
    JSON-serializable ``args`` and returning a JSON-serializable value. Each
    process imports it fresh — exactly the between-graph-replication process
    model of the reference, minus the role split.
    """

    def __init__(
        self,
        target: Callable | str,
        num_processes: int,
        args: Sequence[Any] = (),
        *,
        local_devices_per_process: int = 1,
        timeout: float = 180.0,
        init_timeout: int = 60,
        env: dict[str, str] | None = None,
    ):
        if callable(target):
            # The bootstrap resolves `module:name` via a single getattr, so
            # anything that can't round-trip through an import path is
            # rejected up front: nested functions, class attributes, and
            # functions defined in __main__ (the subprocess's __main__ is the
            # bootstrap itself).
            if (
                "." in target.__qualname__
                or target.__module__ == "__main__"
            ):
                raise ValueError(
                    "target must be a module-level function importable as "
                    f"'pkg.mod:fn', got {target.__module__}:"
                    f"{target.__qualname__}"
                )
            target = f"{target.__module__}:{target.__qualname__}"
        self.target = target
        self.num_processes = num_processes
        self.args = list(args)
        self.local_devices = local_devices_per_process
        self.timeout = timeout
        self.init_timeout = init_timeout
        self.extra_env = env or {}
        self._procs: list[subprocess.Popen] = []
        self._files: list[tuple[Any, Any]] = []
        self._tmp = None

    def start(self) -> "MultiProcessRunner":
        coordinator = f"localhost:{free_port()}"
        self._tmp = tempfile.TemporaryDirectory(prefix="dtg_mp_")
        for pid in range(self.num_processes):
            spec = {
                "target": self.target,
                "args": self.args,
                "coordinator": coordinator,
                "num_processes": self.num_processes,
                "process_id": pid,
                "local_devices": self.local_devices,
                "init_timeout": self.init_timeout,
            }
            env = dict(os.environ)
            # Scrub the parent's single-controller device fakery, which would
            # fight the per-process JAX config — but an XLA_FLAGS the caller
            # passes explicitly via env= wins.
            env.pop("XLA_FLAGS", None)
            env.update(self.extra_env)
            env["DTG_MP_SPEC"] = json.dumps(spec)
            out = open(Path(self._tmp.name) / f"out_{pid}.txt", "w+")
            err = open(Path(self._tmp.name) / f"err_{pid}.txt", "w+")
            self._files.append((out, err))
            self._procs.append(
                subprocess.Popen(
                    [sys.executable, "-c", _BOOTSTRAP],
                    env=env,
                    stdout=out,
                    stderr=err,
                    cwd=os.getcwd(),
                )
            )
        return self

    def kill(self, process_id: int, sig: int = signal.SIGKILL) -> None:
        """Fault injection: kill one process mid-run."""
        self._procs[process_id].send_signal(sig)

    def join(
        self, *, raise_on_error: bool = True, failure_grace: float = 10.0
    ) -> list[ProcessResult]:
        """Supervise until all processes exit, the deadline hits, or a
        failure is detected.

        Prompt failure detection: the moment any process exits nonzero, the
        survivors get ``failure_grace`` seconds to finish (peers blocked in a
        collective on the dead rank never will) and are then killed — instead
        of hanging to the full timeout the way the reference's run.sh peers
        hang on a dead PS.
        """
        # Reap-on-failure supervision run.sh never had.
        timed_out = supervise(
            self._procs, timeout=self.timeout, failure_grace=failure_grace
        )
        results = []
        for pid, (p, (out, err)) in enumerate(zip(self._procs, self._files)):
            out.flush()
            err.flush()
            out.seek(0)
            err.seek(0)
            stdout, stderr = out.read(), err.read()
            out.close()
            err.close()
            value = None
            for line in stdout.splitlines():
                if line.startswith(_RESULT_SENTINEL):
                    value = json.loads(line[len(_RESULT_SENTINEL):])
            results.append(
                ProcessResult(pid, p.returncode, stdout, stderr, value)
            )
        self._tmp.cleanup()
        self._procs, self._files = [], []
        if raise_on_error and (timed_out or any(not r.ok for r in results)):
            bad = [r for r in results if not r.ok]
            detail = "\n".join(
                f"--- process {r.process_id} (exit {r.returncode}) ---\n"
                f"{r.stderr[-2000:]}"
                for r in bad
            )
            raise MultiProcessError(
                f"{'timeout; ' if timed_out else ''}"
                f"{len(bad)}/{len(results)} processes failed:\n{detail}",
                results,
            )
        return results


def run_multiprocess(
    target: Callable | str,
    num_processes: int,
    args: Sequence[Any] = (),
    **kw,
) -> list[ProcessResult]:
    """One-shot: start + join, raising on any process failure."""
    return MultiProcessRunner(target, num_processes, args, **kw).start().join()
