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

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
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
    whole array landing on one device before shard_map reshards it.
    ``x`` must already have a length the axis divides; a host corpus of
    any length enters through :func:`stage_rows`."""
    return jax.device_put(x, NamedSharding(mesh, PS(axis_name)))


@functools.partial(jax.jit, static_argnums=1)
def _pad_rows(x, block: int):
    return jnp.pad(x, ((0, block - x.shape[0]), (0, 0)))


def stage_rows(corpus, mesh: Mesh, axis_name: str, block: int) -> jax.Array:
    """Stage a host corpus of N rows on ``mesh`` as a [P * block, d]
    float32 array split over ``axis_name``, rows past N zero: what
    :func:`shard_rows` gives for the corpus padded to P * block rows,
    without that padded host copy.  The entry for host corpora.

    Where P * block == N the caller's rows go to :func:`shard_rows` as
    they are.  Otherwise each device receives a row-slice view of them,
    and only a short block is padded, on its own device; a device whose
    rows all lie past N gets a block of zeros made there.  A float64 or
    strided corpus pays the one conversion ``np.ascontiguousarray``
    makes, and nothing more.  The caller's array is never donated, so
    it is unchanged."""
    x = np.ascontiguousarray(corpus, np.float32)
    N, d = x.shape
    P = mesh.shape[axis_name]
    if P * block == N:
        return shard_rows(x, mesh, axis_name)
    shape = (P * block, d)
    sharding = NamedSharding(mesh, PS(axis_name))
    shards = []
    for dev, idx in sharding.addressable_devices_indices_map(shape).items():
        rows = x[idx[0]]                 # fewer than block past N, or none
        part = jax.device_put(rows, dev)
        shards.append(part if len(rows) == block else _pad_rows(part, block))
    return jax.make_array_from_single_device_arrays(shape, sharding, shards)


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
