"""The seam between the program and the machine it runs on: which device
JAX found, what that device can do at best, and where compiled programs are
kept between processes.

Everything here is asked of the machine, never assumed: a measurement path
calls :func:`require_tpu` and fails when there is no chip; every result line
carries :func:`device_fields`; a TPU whose ``device_kind`` the peaks table
does not know is an error, not a default.
"""

from __future__ import annotations

import dataclasses
import os
from pathlib import Path

#: The checkout this package was imported from (``<checkout>/<package>/core``).
CHECKOUT = Path(__file__).resolve().parents[2]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Per-chip peaks of one TPU generation, bytes and FLOPs per second."""

    bf16_flops: float
    hbm_bytes: float
    ici_bytes: float   # all links, one direction
    dcn_bytes: float   # this chip's share of its host's data-centre NICs
    pcie_bytes: float  # host <-> device, one direction


# Keyed on substrings of ``jax.devices()[0].device_kind`` (lower-cased), in
# match order. FLOP/s, HBM and ICI are the public spec sheets (Google Cloud
# documentation, "TPU v5e" and siblings: v5e 197 TFLOP/s bf16, 819 GB/s,
# 1,600 Gbit/s of interconnect). DCN and PCIe are ASSUMED classes (100/200
# Gbit NICs shared per host; PCIe Gen3/Gen4 x16) that no run of this
# repository has measured — a fraction against them is a model.
_V5E = Peaks(197e12, 819e9, 200e9, 12.5e9, 32e9)
_V6E = Peaks(918e12, 1638e9, 448e9, 25e9, 32e9)
PEAKS: dict[str, Peaks] = {
    "v5 lite": _V5E, "v5litepod": _V5E, "v5e": _V5E,
    "v5p": Peaks(459e12, 2765e9, 600e9, 25e9, 32e9),
    "v6 lite": _V6E, "v6e": _V6E,
    "v4": Peaks(275e12, 1228e9, 300e9, 25e9, 16e9),
    "v3": Peaks(123e12, 900e9, 82e9, 12.5e9, 16e9),
    "v2": Peaks(46e12, 700e9, 62e9, 12.5e9, 16e9),
}

#: The part this repository is sized for; off-chip models price against it.
REFERENCE_KIND = "TPU v5 lite"


def peaks_for(device_kind: str) -> Peaks:
    """The table row for a ``device_kind``; an unknown kind raises."""
    kind = device_kind.lower()
    for key, peaks in PEAKS.items():
        if key in kind:
            return peaks
    raise ValueError(
        f"no peaks for device_kind {device_kind!r}: add its spec-sheet row "
        "to core/device.py PEAKS (a roofline share against a guessed peak "
        "is worse than none)")


def attached_peaks() -> Peaks | None:
    """Peaks of the attached accelerator, ``None`` off-TPU (a fraction of a
    CPU "peak" would be noise). Raises for a TPU the table does not know."""
    import jax

    d = jax.devices()[0]
    if d.platform != "tpu":
        return None
    return peaks_for(d.device_kind)


def device_fields() -> dict:
    """What every result line says about where it ran."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def require_tpu():
    """The attached TPU devices, or ``SystemExit`` naming what JAX found
    instead. JAX itself falls back to the CPU with only a warning, so a
    path whose numbers mean something only on the chip asks here first."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"no TPU found: jax.devices()[0] is {devs[0].platform!r} "
            f"({devs[0].device_kind!r}, {len(devs)} device(s)); this path "
            "runs on the chip only")
    return devs


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this touches nothing. Otherwise the cache is ``<checkout>/.jax_cache``:
    the directory is part of every entry's key, so it is fixed by where the
    code is, never by the home directory, a pid or a time. Call before the
    first compile.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
