"""The engine's compiled step programs, kept beside JAX's persistent cache.

JAX's own persistent cache is keyed by the lowered module, so a process
that finds its program there has still traced the model and lowered it:
for a model of 48 unrolled layers that is most of what a program costs
(GPT-2 XL on the chip's host: 12-18 s of tracing a program, so a warm
set-up of four traced programs read 97.6 s, and of four loaded ones 31.2;
PERF.md section 6, PR 36). The engine has four programs since its prefill
program comes in three widths, so it keeps the EXECUTABLES:
``jax.experimental.serialize_executable`` writes one, and a later process
loads it without tracing anything.

A file's name holds everything the executable is a function of: the
package's own files (every one, byte for byte: the model, the kernels, the
tuned tiles), the versions of JAX, jaxlib and the backend, the device, the
flags the compiler reads from the environment, the program's name and
static arguments, and the arguments' tree, shapes and dtypes. Anything
that moves one of them moves the name, and the old file is simply never
read again. The files are written only where a cache directory is
configured (``jax_compilation_cache_dir``: ``core.device.
setup_compile_cache``), and only bytes this package wrote there are
unpickled. A file that does not load (cut short, another machine's) is
compiled again and written over; nothing here can fail a launch that the
plain ``jax.jit`` call would not fail.
"""

from __future__ import annotations

import hashlib
import logging
import os
import pickle
from pathlib import Path

import jax

log = logging.getLogger(__name__)

_PACKAGE = Path(__file__).resolve().parent.parent
#: environment variables the compiler or the package's kernels read
_ENV = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")
_fingerprint: str | None = None


def directory() -> str | None:
    """Where executables are kept: the persistent cache's directory, or
    None (then every program is the plain ``jax.jit`` call) where none is
    configured, and on the CPU: an executable for it is tied to the
    instruction set of the machine that compiled it, a cache directory may
    travel, and a program there is small."""
    where = jax.config.jax_compilation_cache_dir
    return where if where and jax.default_backend() != "cpu" else None


def fingerprint() -> str:
    """What every executable of this process depends on whatever the
    program: the package's files, the versions, the environment. Computed
    once a process (a few megabytes to hash)."""
    global _fingerprint
    if _fingerprint is None:
        import jaxlib

        h = hashlib.sha256()
        for path in sorted(_PACKAGE.rglob("*")):
            if path.is_file() and "__pycache__" not in path.parts:
                h.update(str(path.relative_to(_PACKAGE)).encode())
                h.update(path.read_bytes())
        h.update(repr((jax.__version__, jaxlib.__version__)).encode())
        h.update(repr(sorted(
            (k, v) for k, v in os.environ.items()
            if k in _ENV or k.startswith("DTG_"))).encode())
        table = os.environ.get("DTG_AUTOTUNE_TABLE")  # tiles kept elsewhere
        if table and os.path.isfile(table):
            h.update(Path(table).read_bytes())
        _fingerprint = h.hexdigest()
    return _fingerprint


def _name(program: str, static, args, device) -> str:
    leaves, tree = jax.tree.flatten(args)
    backend = jax.devices()[0].client if device is None else device.client
    h = hashlib.sha256(repr((
        fingerprint(), program, static, str(tree),
        [(tuple(a.shape), str(a.dtype)) for a in leaves],
        backend.platform, backend.platform_version,
        None if device is None else (device.id, device.device_kind),
    )).encode())
    return f"dtg-{program}-{h.hexdigest()[:40]}.executable"


def load_or_compile(jitted, args, *, program: str, static, device=None):
    """``jitted`` as it will be called with ``args`` (and with arguments of
    their shapes ever after): the executable kept for exactly this program
    where there is one, else compiled now and kept. ``static`` is whatever
    reaches the trace beside the arguments (the memo key of
    ``build_step_fns``). Without a cache directory, ``jitted`` itself."""
    where = directory()
    if where is None:
        return jitted
    from jax.experimental import serialize_executable

    path = Path(where) / _name(program, static, args, device)
    try:
        with open(path, "rb") as f:
            payload, in_tree, out_tree = pickle.load(f)
        return serialize_executable.deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=None if device is None else [device])
    except FileNotFoundError:
        pass
    except Exception:  # noqa: BLE001 - any unreadable file: compile again
        log.warning("could not load %s; compiling it again", path,
                    exc_info=True)
    compiled = jitted.lower(*args).compile()
    try:
        blob = pickle.dumps(serialize_executable.serialize(compiled))
        os.makedirs(where, exist_ok=True)
        tmp = path.with_suffix(f".{os.getpid()}.tmp")
        tmp.write_bytes(blob)
        os.replace(tmp, path)
    except Exception:  # noqa: BLE001 - the program runs all the same
        log.warning("could not keep %s", path, exc_info=True)
    return compiled
