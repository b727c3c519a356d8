"""Device-mesh construction — the TPU-native replacement for the reference's
cluster topology.

Reference equivalent: ``tf.train.ClusterSpec({"ps": [...], "worker": [...]})``
(tensorflow/python/training/server_lib.py:243) plus device placement via
``tf.train.replica_device_setter`` (tensorflow/python/training/device_setter.py:129).
The reference wires up a *role-typed* cluster: parameter-server tasks hold
variables, worker tasks compute.

On TPU there are no roles. Topology is a single ``jax.sharding.Mesh`` with
five named logical axes:

    data     — data parallelism (sync allreduce; replaces PS/worker split)
    model    — tensor parallelism (param sharding; Megatron-style)
    pipe     — pipeline parallelism (stage sharding + ppermute microbatches)
    context  — sequence/context parallelism (ring attention KV rotation)
    expert   — expert parallelism (MoE all_to_all token routing)

Axis sizes are *config*, not process roles: every host runs the same program
with the same MeshSpec (SPMD), and XLA lays collectives onto the ICI torus.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical logical axis order. Order matters for ICI locality under
# create_device_mesh: later (inner) axes — pipe/context/expert here — get the
# tightest physical rings. model sits second-outermost; configs that need
# nearest-neighbor tensor-parallel rings should keep the trailing axes at 1
# (size-1 dims are free) so model becomes the effective innermost axis.
AXES = ("data", "model", "pipe", "context", "expert")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape. ``-1`` means "fill with the remaining devices".

    The reference encodes topology as per-process CLI flags
    (``--job_name=ps --task_index=0`` ...) plus a bash launcher; here the
    whole topology is this one value, identical on every host.
    """

    data: int = -1
    model: int = 1
    pipe: int = 1
    context: int = 1
    expert: int = 1

    def resolve(self, n_devices: int) -> dict[str, int]:
        """Resolve -1 entries against the device count; validate the product."""
        sizes = {a: getattr(self, a) for a in AXES}
        for a, s in sizes.items():
            if s != -1 and s < 1:
                raise ValueError(f"axis {a!r} size must be -1 or >= 1, got {s}")
        fills = [a for a, s in sizes.items() if s == -1]
        if len(fills) > 1:
            raise ValueError(f"at most one axis may be -1, got {fills}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if fills:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[fills[0]] = n_devices // fixed
        if math.prod(sizes.values()) != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {math.prod(sizes.values())} devices, "
                f"have {n_devices}"
            )
        return sizes


def num_slices(devices: Sequence[jax.Device] | None = None) -> int:
    """Number of TPU slices the devices span (1 on single-slice / CPU).

    Multi-slice (Megascale / multi-pod) deployments expose
    ``device.slice_index``; within a slice links are ICI, across slices
    they are DCN — orders of magnitude slower, so the mesh layout must put
    exactly one low-traffic axis across that boundary."""
    devices = list(devices if devices is not None else jax.devices())
    return len({getattr(d, "slice_index", 0) for d in devices})


def _slice_groups(devices: Sequence) -> list[list]:
    groups: dict[int, list] = {}
    for d in devices:
        groups.setdefault(getattr(d, "slice_index", 0), []).append(d)
    return [groups[k] for k in sorted(groups)]


def valid_slice_counts(sizes: dict[str, int], dcn_axis: str = "data") -> list[int]:
    """Slice counts a ``dcn_axis`` of this size can span: its divisors.

    The programmatic answer to :func:`hybrid_device_array`'s divisibility
    error — callers picking a deployment shape (or an elastic supervisor
    deciding which reduced worlds are reachable) can query instead of
    parsing an exception message."""
    if dcn_axis not in AXES:
        raise ValueError(f"dcn_axis must be one of {AXES}, got {dcn_axis!r}")
    n = sizes[dcn_axis]
    return [k for k in range(1, n + 1) if n % k == 0]


def hybrid_device_array(
    sizes: dict[str, int],
    devices: Sequence,
    n_slices: int,
    dcn_axis: str = "data",
):
    """Device array for a multi-slice mesh: ``dcn_axis`` factors as
    (slice, within-slice) with the slice-spanning part OUTERMOST, every
    other axis entirely within a slice — so ``model``/``pipe``/``context``
    /``expert`` neighbors (and the within-slice part of ``data``) ride
    ICI, and only ``dcn_axis``'s outer loop crosses DCN.

    Prefers ``mesh_utils.create_hybrid_device_mesh`` (ICI-aware per-slice
    layout); falls back to per-slice reshape + stack when topology info is
    unavailable (fake/test devices) — slice grouping is preserved either
    way, which is the property that matters for DCN traffic.
    """
    if dcn_axis not in AXES:
        raise ValueError(f"dcn_axis must be one of {AXES}, got {dcn_axis!r}")
    if sizes[dcn_axis] % n_slices:
        raise ValueError(
            f"{n_slices} slices need axis {dcn_axis!r} divisible by the "
            f"slice count, got {sizes[dcn_axis]} — either resize "
            f"{dcn_axis!r} or pick another dcn_axis (axis {dcn_axis!r} "
            f"supports slice counts {valid_slice_counts(sizes, dcn_axis)}; "
            "see valid_slice_counts())"
        )
    per_slice = dict(sizes)
    per_slice[dcn_axis] //= n_slices
    inner = tuple(per_slice[a] for a in AXES)
    dcn = tuple(n_slices if a == dcn_axis else 1 for a in AXES)
    try:
        from jax.experimental import mesh_utils

        return mesh_utils.create_hybrid_device_mesh(
            inner, dcn, devices=list(devices)
        )
    except Exception as e:
        import logging

        logging.getLogger(__name__).warning(
            "create_hybrid_device_mesh failed (%s); falling back to "
            "per-slice reshape — slice grouping kept, per-slice ICI "
            "ordering may be suboptimal", e,
        )
        groups = _slice_groups(devices)
        arrs = [np.asarray(g, dtype=object).reshape(inner) for g in groups]
        return np.concatenate(arrs, axis=AXES.index(dcn_axis))


def build_mesh(
    spec: MeshSpec | None = None,
    devices: Sequence[jax.Device] | None = None,
    *,
    dcn_axis: str = "data",
) -> Mesh:
    """Build a ``jax.sharding.Mesh`` over ``devices`` (default: all).

    Uses ``mesh_utils.create_device_mesh`` so the logical mesh maps onto
    the physical ICI torus with nearest-neighbor rings per axis (critical
    for ppermute/psum bandwidth); on backends with no topology (CPU fake
    devices in tests) that is a plain reshape.

    Multi-slice deployments (``num_slices() > 1``) get the hybrid layout:
    ``dcn_axis`` (default ``data`` — one gradient allreduce per step is
    the cheapest thing to put on the slow network) spans slices, all other
    axes stay inside a slice on ICI. Without this, a naive reshape would
    silently scatter ``model``/``pipe`` neighbors across DCN.
    """
    spec = spec or MeshSpec()
    devices = list(devices if devices is not None else jax.devices())
    sizes = spec.resolve(len(devices))
    n_slices = num_slices(devices)
    if n_slices > 1:
        return Mesh(
            hybrid_device_array(sizes, devices, n_slices, dcn_axis), AXES
        )
    shape = tuple(sizes[a] for a in AXES)
    from jax.experimental import mesh_utils

    # No fallback: where create_device_mesh cannot map the logical axes
    # onto the chip's torus it raises, and so does this. Reshape order on
    # a real slice would run, with collectives on the wrong links.
    return Mesh(mesh_utils.create_device_mesh(shape, devices=devices), AXES)


def single_device_mesh(device: jax.Device | None = None) -> Mesh:
    """An all-ones (1x1x1x1x1) mesh — the Non-Distributed-Setup control
    (reference R2)."""
    device = device or jax.devices()[0]
    return Mesh(np.asarray([device]).reshape((1,) * len(AXES)), AXES)


def axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
