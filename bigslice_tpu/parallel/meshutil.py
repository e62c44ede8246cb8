"""Shared mesh helpers: axis naming, topology facts, and the 2-D
(DCN × ICI) mesh builder.

Executors historically hard-assumed 1-D meshes (``mesh_axis`` returned
``axis_names[0]``). Multi-pod topologies are 2-D —
``Mesh(devices.reshape(D, I), ("dcn", "ici"))`` with chips of a pod
slice on the fast ICI axis and pods on the slow DCN axis — so every
executor-layer caller now routes through :class:`MeshTopology` (or the
generalized :func:`mesh_axis`), which hands back an axis designator
valid for BOTH shapes: jax accepts a *tuple* of axis names everywhere a
single name goes (``PartitionSpec``, ``psum``/``pmin``/``pmax``,
``all_to_all``, ``ppermute``, ``axis_index``), denoting the flattened
row-major device order — which matches ``mesh.devices.flat``, so a
kernel written against the tuple behaves bit-identically to the same
kernel on the flat 1-D mesh of the same devices.

The mesh SHAPE is a session-level knob: ``BIGSLICE_MESH_SHAPE=DxI``
forces a 2-D grid (forceable on CPU meshes via
``--xla_force_host_platform_device_count``); unset, real multi-slice /
multi-host TPU jobs auto-derive (D = slices-or-hosts, I = chips each)
and everything else stays 1-D — the chicken bit for the whole
hierarchical executor path.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

HIER_AXIS_NAMES = ("dcn", "ici")


def mesh_axis(mesh):
    """The shard-axis designator of a framework mesh: the single axis
    name for 1-D meshes (unchanged legacy contract), the tuple of axis
    names for multi-axis meshes — usable wherever jax takes an
    ``axis_name`` and in ``PartitionSpec``, meaning the flattened
    row-major device order (== ``mesh.devices.flat``)."""
    names = tuple(mesh.axis_names)
    return names[0] if len(names) == 1 else names


class MeshTopology:
    """Shape facts of a device mesh, the ONE helper every executor
    caller routes through instead of assuming ``axis_names[0]``.

    - ``axis``: the :func:`mesh_axis` designator (name or tuple).
    - ``is_hier``: True for a 2-D (dcn, ici) grid with BOTH extents > 1
      — the shape whose shuffles route through the hierarchical
      two-stage exchange (parallel/hier.py). A degenerate 2-D mesh
      (1×N or N×1) keeps flat routing: there is no second tier to
      amortize.
    - ``dcn_axis``/``ici_axis``/``ndcn``/``nici``: the hierarchy's
      named axes and extents (1-D meshes report ndcn=1, nici=nmesh —
      everything rides the one "ici-like" interconnect).
    - ``signature()``: repr-stable (axis names, shape) pair for compile
      digests and the AOT program-cache key — a 1-D and a 2-D program
      over the same devices must never collide.
    """

    def __init__(self, mesh):
        self.mesh = mesh
        self.axis_names: Tuple[str, ...] = tuple(mesh.axis_names)
        self.shape: Tuple[int, ...] = tuple(
            int(s) for s in mesh.devices.shape
        )
        self.nmesh = int(mesh.devices.size)
        self.axis = mesh_axis(mesh)
        self.is_hier = (
            len(self.shape) == 2
            and self.shape[0] > 1
            and self.shape[1] > 1
        )
        if len(self.shape) == 2:
            self.dcn_axis, self.ici_axis = self.axis_names
            self.ndcn, self.nici = self.shape
        else:
            self.dcn_axis = None
            self.ici_axis = self.axis_names[0]
            self.ndcn, self.nici = 1, self.nmesh

    def signature(self) -> tuple:
        return (self.axis_names, self.shape)


def mesh_shape_from_env() -> Optional[Tuple[int, int]]:
    """Parse ``BIGSLICE_MESH_SHAPE`` (``DxI``, e.g. ``2x4``); None when
    unset/empty, raises on malformed values (a silently-ignored typo
    would run the whole job on the wrong topology)."""
    spec = os.environ.get("BIGSLICE_MESH_SHAPE", "").strip()
    if not spec:
        return None
    parts = spec.lower().replace("×", "x").split("x")
    try:
        d, i = (int(p) for p in parts)
    except (TypeError, ValueError):
        raise ValueError(
            f"BIGSLICE_MESH_SHAPE={spec!r}: expected DxI (e.g. 2x4)"
        ) from None
    if d < 1 or i < 1:
        raise ValueError(
            f"BIGSLICE_MESH_SHAPE={spec!r}: extents must be >= 1"
        )
    return d, i


def structure_groups(devices, uniform: bool = True):
    """The device fleet's slice/host grouping on real TPU, as an
    ordered list of groups (first-seen order, members in
    ``jax.devices()`` order) — or None where no multi-group structure
    exists (CPU fleets only go 2-D via the explicit knob). One
    attribute grounds the WHOLE grouping: ``slice_index`` when every
    device carries it (multi-slice jobs), else ``process_index``
    (multi-host single-slice) — never mixed per device, which could
    collapse distinct pods into one group.

    ``uniform=True`` (the 2-D mesh builder's contract) additionally
    requires equal group sizes; ``uniform=False`` tolerates ragged
    groups — the elastic provider's degraded-fleet case, where a pod
    that lost a chip is exactly the point."""
    devices = list(devices)
    if not devices or getattr(devices[0], "platform", "") != "tpu":
        return None
    for attr in ("slice_index", "process_index"):
        groups: dict = {}
        ok = True
        for d in devices:
            key = getattr(d, attr, None)
            if key is None:
                ok = False
                break
            groups.setdefault(key, []).append(d)
        if not ok or len(groups) <= 1:
            continue
        if uniform and len({len(v) for v in groups.values()}) != 1:
            continue
        return list(groups.values())
    return None


def shape_device_mesh(devices=None,
                      shape: Optional[Tuple[int, int]] = None,
                      axis: str = "shards"):
    """Build the executor mesh over ``devices``: a 2-D
    ``Mesh(devices.reshape(D, I), ("dcn", "ici"))`` when a shape is
    known (explicit arg > ``BIGSLICE_MESH_SHAPE`` > the real-TPU
    topology probe), the legacy 1-D ``(axis,)`` mesh otherwise — the
    unset-knob path is bit-identical to what every prior session
    built. Device order is preserved: shard s of the 2-D grid is
    ``devices[s]`` row-major, exactly the 1-D placement."""
    import numpy as np
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if shape is None:
        shape = mesh_shape_from_env()
    if shape is None:
        # Probe-derived shapes REORDER the devices group-contiguously
        # (each grid row = one slice/host): jax.devices() may
        # interleave slices, and a raw reshape of that order would put
        # chips of different slices on one "ici" row — every ICI
        # collective would actually cross DCN. Explicit shapes (env /
        # arg) keep the caller's order: the operator asserts the
        # layout.
        groups = structure_groups(devices)
        if groups is None:
            return Mesh(np.array(devices), (axis,))
        devices = [d for g in groups for d in g]
        shape = (len(groups), len(groups[0]))
    d, i = shape
    if d * i != len(devices):
        raise ValueError(
            f"mesh shape {d}x{i} does not cover {len(devices)} devices"
        )
    return Mesh(np.array(devices).reshape(d, i), HIER_AXIS_NAMES)


def get_shard_map():
    """``jax.shard_map`` under the keyword surface the call sites use
    (``check_rep``, which jax now spells ``check_vma``)."""
    import jax

    def wrap(f, mesh, in_specs, out_specs, check_rep=False):
        return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=check_rep)

    return wrap
