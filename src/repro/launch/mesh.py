"""Production meshes and ParamDef placeholder-spec resolution.

Mesh shapes (TPU v5e pods, 256 chips each):
  single pod : (data=16, model=16)
  two pods   : (pod=2, data=16, model=16)  — "pod" extends data parallelism
               across the inter-pod (DCN/ICI) boundary.

Importing this module never touches jax device state; meshes are built by
functions only (the dry-run forces a 512-device host platform before any
jax import — see dryrun.py).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as PS


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The 256-device (or 512, multi-pod) production dry-run mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices=None) -> Mesh:
    """jax.make_mesh with every axis in Auto mode (the repo default)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def shard_rows(x, mesh: Mesh, axis_name: str) -> jax.Array:
    """Stage a host array onto ``mesh`` with its leading axis split over
    ``axis_name``: each device receives only its own rows, instead of the
    whole array landing on one device before shard_map reshards it."""
    return jax.device_put(x, NamedSharding(mesh, PS(axis_name)))


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Axes carrying the batch: ("pod", "data") on multi-pod meshes."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def resolve_spec(placeholder, cfg, mesh: Mesh, *, zero1: bool = False) -> PS:
    """Map a ParamDef placeholder tuple to a PartitionSpec.

    "T" -> model axis; "F" -> "data" if (cfg.fsdp or zero1) else replicated;
    "D" -> the dp axes; None -> replicated.
    """
    fsdp_axes = dp_axes(mesh)  # ("pod", "data") on multi-pod: a 400B model's
    # params+optimizer exceed one pod's HBM, so FSDP spans pods there
    if len(fsdp_axes) == 1:
        fsdp_axes = fsdp_axes[0]
    out = []
    for dim in placeholder:
        if dim == "T":
            out.append(cfg.tp_axis)
        elif dim == "F":
            out.append(fsdp_axes if (cfg.fsdp or zero1) else None)
        elif dim == "D":
            out.append(dp_axes(mesh))
        else:
            out.append(None)
    return PS(*out)


def resolve_spec_tree(placeholders, cfg, mesh: Mesh, *, zero1: bool = False):
    """Map resolve_spec over a placeholder pytree."""
    return jax.tree.map(
        lambda ph: resolve_spec(ph, cfg, mesh, zero1=zero1), placeholders,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            e is None or isinstance(e, (str, tuple)) for e in x))


def named(mesh: Mesh, spec: PS) -> NamedSharding:
    """Shorthand NamedSharding constructor."""
    return NamedSharding(mesh, spec)


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def fix_spec_for_shape(shape: Tuple[int, ...], spec: PS, mesh: Mesh) -> PS:
    """jax.jit requires dims divisible by their mesh-axis extents; when a
    config dimension (24 heads, 51866 vocab, ...) does not divide, relocate
    the axis to another still-unsharded dim of the same tensor that does
    (e.g. heads -> head_dim), else drop it (replicate).
    """
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = list(entries)
    for i, ax in enumerate(entries):
        if ax is None:
            continue
        if shape[i] % _axis_size(mesh, ax) == 0:
            continue
        out[i] = None
        for j in range(len(shape) - 1, -1, -1):
            if out[j] is None and j != i and shape[j] % _axis_size(mesh, ax) == 0 \
                    and shape[j] >= _axis_size(mesh, ax):
                out[j] = ax
                break
    return PS(*out)


def fix_spec_tree(sds_tree, spec_tree, mesh: Mesh):
    """Map fix_spec_for_shape over matching (shape, spec) pytrees."""
    return jax.tree.map(
        lambda sds, spec: fix_spec_for_shape(sds.shape, spec, mesh),
        sds_tree, spec_tree)
