"""Machine views and their lowering to JAX shardings.

This replaces three layers of the reference at once:
  - `MachineView` (/root/reference/include/flexflow/machine_view.h:14-96) —
    the (ndims, dims, start, stride) device-grid a Legion index launch maps
    onto;
  - `FFMapper` (/root/reference/src/mapper/mapper.cc) — the Legion mapper
    that turns a MachineView hash into task placement;
  - per-op `create_input_partition` Legion partitions.

TPU-first design: there is ONE global `jax.sharding.Mesh` with named axes
(e.g. ("data", "model") or ("dp", "fsdp", "tp") — chosen by the strategy
search).  A MachineView for a parallel tensor is the assignment of mesh
axes to that tensor's parallel dims.  Lowering a view is just building a
`NamedSharding`; XLA SPMD then inserts all communication.  Views that the
reference would express with stride/offset device sets are normalized to
mesh-aligned shardings (the search only generates mesh-realizable views —
the reference similarly filters views, graph.h:205-210).
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_log = logging.getLogger("flexflow_tpu.parallel")


@dataclasses.dataclass(frozen=True)
class MachineView:
    """Assignment of mesh axes to a parallel tensor's dims.

    axes[i] is the tuple of mesh-axis names sharding dims[i] (the full
    dims tuple, replica dim included).  Empty tuple = dim not sharded.
    Axes on the replica dim mean the tensor is replicated across them
    (for weights this is the data-parallel axis).
    """

    axes: Tuple[Tuple[str, ...], ...]

    def used_axes(self) -> Tuple[str, ...]:
        out = []
        for a in self.axes:
            out.extend(a)
        return tuple(out)

    def __str__(self) -> str:
        return "View(" + ",".join("+".join(a) if a else "_" for a in self.axes) + ")"


def validate_view(view: MachineView, shape, mesh_axis_sizes: Dict[str, int]) -> None:
    """Check the view is consistent with the shape's degrees and the mesh."""
    if len(view.axes) != len(shape.dims):
        raise ValueError(
            f"view rank {len(view.axes)} != tensor rank {len(shape.dims)}"
        )
    seen = set()
    for dim, axes in zip(shape.dims, view.axes):
        prod = 1
        for ax in axes:
            if ax in seen:
                raise ValueError(f"mesh axis {ax!r} used twice in {view}")
            seen.add(ax)
            if ax not in mesh_axis_sizes:
                raise ValueError(f"unknown mesh axis {ax!r}")
            prod *= mesh_axis_sizes[ax]
        if prod != dim.degree:
            raise ValueError(
                f"axes {axes} (size {prod}) != degree {dim.degree} for dim {dim}"
            )


def assign_axes(shape, mesh_axis_sizes: Dict[str, int]) -> MachineView:
    """Normalize per-dim degrees onto named mesh axes (the view normalizer).

    Axis-preference heuristic keeps producer/consumer views aligned on
    the canonical (data, model, ...) mesh:
      - the leading data dim (logical dim 0) and replica dims consume
        axes in declaration order (the "data" axis first — replica dims
        on weights ARE data-parallel replication);
      - all other dims (channel/attribute/expert) consume axes in
        REVERSE declaration order, so a weight's out-channel dim lands
        on the same trailing "model" axis as the matching activation dim.
    The strategy search can always override views explicitly.
    """
    available = dict(mesh_axis_sizes)
    decl_order = list(mesh_axis_sizes.keys())

    def take(need: int, order) -> Tuple[str, ...]:
        order = list(order)
        # pass 1: a single axis of exactly this size (most views are
        # one-axis-per-dim; exact match avoids eating an axis another
        # dim needs)
        for ax in order:
            if ax in available and available[ax] == need:
                del available[ax]
                return (ax,)
        # pass 2: greedy multi-axis factoring
        chosen = []
        for ax in order:
            if ax not in available:
                continue
            size = available[ax]
            if need % size == 0:
                chosen.append(ax)
                del available[ax]
                need //= size
                if need == 1:
                    break
        if need != 1:
            for ax in chosen:
                available[ax] = mesh_axis_sizes[ax]
            raise ValueError(
                f"cannot factor degree onto mesh axes {mesh_axis_sizes} "
                f"(remaining {available}, still need {need})"
            )
        return tuple(chosen)

    axes_out = []
    logical_idx = 0
    for dim in shape.dims:
        if dim.degree <= 1:
            axes_out.append(())
            if not dim.is_replica_dim:
                logical_idx += 1
            continue
        if dim.is_replica_dim or logical_idx == 0:
            axes_out.append(take(dim.degree, decl_order))
        else:
            axes_out.append(take(dim.degree, reversed(decl_order)))
        if not dim.is_replica_dim:
            logical_idx += 1
    return MachineView(tuple(axes_out))


def view_to_spec(pt) -> PartitionSpec:
    """PartitionSpec over the *logical* dims (replica dims dropped —
    replication is expressed by omitting axes)."""
    view: Optional[MachineView] = pt.machine_view
    if view is None:
        return PartitionSpec()
    entries = []
    for dim, axes in zip(pt.shape.dims, view.axes):
        if dim.is_replica_dim:
            continue
        if not axes:
            entries.append(None)
        elif len(axes) == 1:
            entries.append(axes[0])
        else:
            entries.append(tuple(axes))
    while entries and entries[-1] is None:
        entries.pop()
    return PartitionSpec(*entries)


def view_to_sharding(pt, mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, view_to_spec(pt))


# ---------------------------------------------------------------------------
# Mesh construction
# ---------------------------------------------------------------------------

def make_mesh(
    axis_sizes: Dict[str, int],
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a named mesh over the given devices (default: all).

    On real TPU slices `jax.experimental.mesh_utils` picks an ICI-friendly
    device order; on CPU test meshes plain reshape is fine.  On the 2x2
    v5e host mesh_utils accepts every factorisation of all four chips
    but refuses device SUBSETS that are not a whole sub-box of the torus
    (AssertionError) — which elastic recompiles on survivors and tp
    replicas on a device pair legitimately pass.  Those fall back to
    enumeration order with a warning that names the refusal, never
    silently.
    """
    names = tuple(axis_sizes.keys())
    sizes = tuple(axis_sizes.values())
    n = int(np.prod(sizes)) if sizes else 1
    if devices is None:
        devices = jax.devices()
    if len(devices) < n:
        raise ValueError(f"need {n} devices for mesh {axis_sizes}, have {len(devices)}")
    devices = list(devices)[:n]
    if devices and devices[0].platform == "tpu" and n > 1:
        from jax.experimental import mesh_utils

        try:
            return Mesh(
                mesh_utils.create_device_mesh(sizes, devices=devices), names
            )
        except (AssertionError, NotImplementedError, ValueError) as e:
            _log.warning(
                "mesh_utils.create_device_mesh refused mesh %s over "
                "devices %s (%s: %s); using enumeration order — "
                "collectives may cross more ICI hops than necessary",
                dict(axis_sizes), [d.id for d in devices],
                type(e).__name__, e,
            )
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, names)


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
