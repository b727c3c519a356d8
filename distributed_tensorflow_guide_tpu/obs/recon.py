"""Modeled-vs-measured reconciliation: CostVector x measured seconds.

The runtime consumer the PR-13 cost auditor never had: join a program's
statically derived cost vector (``analysis/cost.py`` — FLOPs, HBM bytes,
collective bytes) with a live measured duration and report achieved
GF/s / GB/s, per-resource roofline fractions, and which resource the
measurement says the program is bound by.

``reconcile`` duck-types its ``cost`` argument — a real
:class:`~distributed_tensorflow_guide_tpu.analysis.cost.CostVector`, or
any dict with the same keys (e.g. one loaded from a lint ``--json``
report) — and touches no device; only ``Roofline.from_env`` asks which
one is attached.

Non-guarantees: the cost vector is the *algorithmic* model (fusion
boundaries, undercounted while-bodies — see docs/analysis.md); the
roofline peaks are whatever the caller supplies. Fractions are evidence
for "where did the time go", not a compiler-grade profile.
"""

from __future__ import annotations

import dataclasses
import math
import os


@dataclasses.dataclass(frozen=True)
class Roofline:
    """Peak rates to reconcile against (bytes and flops per second).

    ``from_env`` takes FLOP/s and HBM from the peaks table's row for the
    attached TPU (core/device.py; an unknown TPU raises) — off-chip, from
    the row of the part the repo is sized for, which makes every fraction
    a model. ``DTG_PEAK_FLOPS`` / ``DTG_PEAK_HBM_BPS`` override them;
    ``DTG_PEAK_ICI_BPS`` / ``DTG_PEAK_PCIE_BPS`` opt those resources in.
    """

    peak_flops_s: float
    peak_hbm_bytes_s: float
    peak_ici_bytes_s: float | None = None
    #: host<->device transfer peak (KV spill tier d2h/h2d traffic);
    #: optional like ICI — absent means "don't reconcile swap bytes".
    peak_pcie_bytes_s: float | None = None

    @classmethod
    def from_env(cls) -> "Roofline":
        from distributed_tensorflow_guide_tpu.core import device

        peaks = (device.attached_peaks()
                 or device.peaks_for(device.REFERENCE_KIND))
        ici = os.environ.get("DTG_PEAK_ICI_BPS")
        pcie = os.environ.get("DTG_PEAK_PCIE_BPS")
        return cls(
            peak_flops_s=float(
                os.environ.get("DTG_PEAK_FLOPS", peaks.bf16_flops)),
            peak_hbm_bytes_s=float(
                os.environ.get("DTG_PEAK_HBM_BPS", peaks.hbm_bytes)),
            peak_ici_bytes_s=float(ici) if ici else None,
            peak_pcie_bytes_s=float(pcie) if pcie else None)


def _get(cost, name: str) -> float:
    if isinstance(cost, dict):
        if name == "hbm_bytes" and "hbm_bytes" not in cost:
            return (float(cost.get("hbm_bytes_read", 0.0))
                    + float(cost.get("hbm_bytes_written", 0.0)))
        if name == "collective_bytes_total" and name not in cost:
            cb = cost.get("collective_bytes", {})
            return float(sum(cb.values())) if isinstance(cb, dict) \
                else float(cb or 0.0)
        return float(cost.get(name, 0.0))
    return float(getattr(cost, name))


def reconcile(cost, measured_s: float, roof: Roofline) -> dict:
    """One program execution's modeled-vs-measured reconciliation.

    Returns achieved rates, per-resource roofline fractions, the
    roofline model's predicted time (max over resources), efficiency
    (model time / measured time — 1.0 means the measurement sits ON the
    roofline), and the binding resource."""
    if not (measured_s > 0 and math.isfinite(measured_s)):
        raise ValueError(f"measured_s must be finite > 0, "
                         f"got {measured_s!r}")
    flops = _get(cost, "flops")
    hbm = _get(cost, "hbm_bytes")
    coll = _get(cost, "collective_bytes_total")
    times = {"compute": flops / roof.peak_flops_s,
             "memory": hbm / roof.peak_hbm_bytes_s}
    ici_frac = None
    if roof.peak_ici_bytes_s:
        times["comm"] = coll / roof.peak_ici_bytes_s
        ici_frac = coll / measured_s / roof.peak_ici_bytes_s
    # pcie term (round 21, additive): only when the cost dict carries
    # swap/offload bytes AND the roofline has a pcie peak — absent either,
    # the output dict is unchanged key-for-key from the round-20 shape.
    pcie_frac = None
    pcie_bytes = (float(cost.get("pcie_bytes", 0.0) or 0.0)
                  if isinstance(cost, dict)
                  else float(getattr(cost, "pcie_bytes", 0.0) or 0.0))
    if roof.peak_pcie_bytes_s and pcie_bytes:
        times["pcie"] = pcie_bytes / roof.peak_pcie_bytes_s
        pcie_frac = pcie_bytes / measured_s / roof.peak_pcie_bytes_s
    model_time_s = max(times.values())
    bound = max(times, key=lambda k: times[k])
    out = {
        "measured_s": measured_s,
        "achieved_gflops_s": flops / measured_s / 1e9,
        "achieved_hbm_gb_s": hbm / measured_s / 1e9,
        "achieved_ici_gb_s": coll / measured_s / 1e9,
        "flops_frac": flops / measured_s / roof.peak_flops_s,
        "hbm_frac": hbm / measured_s / roof.peak_hbm_bytes_s,
        "ici_frac": ici_frac,
        "model_time_s": model_time_s,
        "efficiency": model_time_s / measured_s,
        "bound": bound,
    }
    if pcie_frac is not None:
        out["pcie_frac"] = pcie_frac
    return out
