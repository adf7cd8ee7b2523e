"""Streaming corpus updates for the online serving tier.

Corpus residency follows the batch engine exactly: the corpus is chunked
into P blocks of ``block`` rows, device i owns block i (its *shard*) and
additionally holds the k blocks of its cyclic quorum as a resident
``[k, block, d]`` *stack* (slot s = block (i + A[s]) % P, the same layout
``quorum_gather`` produces).  A validity flag per row handles partially
filled blocks — appends land in empty block capacity, no resharding.

``replace_block`` writes the new data into the owner's shard and
propagates it to the block's k holder quorums with the *existing* cyclic
ppermute shifts — the same k-1 shifts that built the residency, one
collective round, O(k * N/P) bytes per device, no data-layer reshuffle,
no divergence (uniform SPMD: non-holders receive their unchanged
neighbors' blocks, which the stack invariant makes a no-op).  The
validity row rides along as an extra feature column so one permute moves
both.  ``append_block`` is ``replace_block`` into the first empty block
slot (tracked host-side).

All programs are jitted once per (mesh, P, block, d) and reused across
updates — the block id and row count are traced scalars.
"""

from __future__ import annotations

import functools
from typing import Callable, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PS

from ..core.allpairs import quorum_gather
from ..core.placement import Placement, placement_from_env, resolve_placement
from ..launch.mesh import shard_rows

__all__ = ["ServingState", "build_state", "update_fn", "replace_block",
           "register_dirty_listener", "unregister_dirty_listener"]

# Dirty-block listeners (DESIGN.md section 16.5): every streamed block
# update — replace, and append (which is a replace into empty capacity,
# see engine.ServingCorpus.append_block) — notifies the registered
# callbacks with the block id, so standing delta indexes
# (core.delta.DeltaIndex.mark_dirty) learn about churn at the moment it
# is applied, not by polling.
_DIRTY_LISTENERS: List[Callable[[int], None]] = []


def register_dirty_listener(fn: Callable[[int], None]) -> Callable[[int], None]:
    """Register a callback invoked with the block id after every
    streamed block update (replace or append) — the hook that marks
    standing ``core.delta.DeltaIndex`` objects dirty.  Returns ``fn``
    so it can be used as a decorator."""
    _DIRTY_LISTENERS.append(fn)
    return fn


def unregister_dirty_listener(fn: Callable[[int], None]) -> None:
    """Remove a callback added by :func:`register_dirty_listener`
    (no-op if it is not registered)."""
    try:
        _DIRTY_LISTENERS.remove(fn)
    except ValueError:
        pass


def _notify_dirty(b: int) -> None:
    for fn in list(_DIRTY_LISTENERS):
        fn(int(b))


class ServingState(NamedTuple):
    """Device-resident serving arrays (a pytree; host metadata lives in
    ``engine.ServingCorpus``).

    shard       : [P * block, d]  — block i is device i's owned chunk.
    valid       : [P * block]     — row validity of the owned chunks.
    stack       : [P * k, block, d] — per-device quorum stacks, device-major
                  (device i's slot s is row i*k + s).
    stack_valid : [P * k, block]  — validity rows aligned with ``stack``.
    """

    shard: jax.Array
    valid: jax.Array
    stack: jax.Array
    stack_valid: jax.Array


def _with_valid(shard: jax.Array, valid: jax.Array) -> jax.Array:
    """Append validity as a feature column so one permute carries both."""
    return jnp.concatenate([shard, valid.astype(shard.dtype)[:, None]], axis=1)


@functools.lru_cache(maxsize=32)
def _build_fn(mesh, axis_name: str, P: int, placement: Placement):
    """Jitted initial-residency program: shard -> quorum stack (one gather).
    ``placement`` supplies the shift structure (and is part of the program
    cache key — placements are hashable memoized value objects)."""
    sched = placement.schedule()

    def f(shard, valid):
        stacked = quorum_gather(_with_valid(shard, valid), sched, axis_name)
        return stacked[..., :-1], stacked[..., -1] > 0.5

    return jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(PS(axis_name), PS(axis_name)),
        out_specs=(PS(axis_name), PS(axis_name))))


@functools.lru_cache(maxsize=32)
def update_fn(mesh, axis_name: str, P: int, placement: Placement):
    """Jitted update program shared by replace and append.

    ``f(shard, valid, b, data, nvalid)``: the owner of block ``b``
    overwrites its shard with ``data`` (rows >= nvalid invalid), then the
    k cyclic shifts redistribute the updated shards — each holder of b
    receives the new block at its matching slot, every other slot arrives
    unchanged (the stack invariant: slot s on device i always holds block
    (i + A[s]) % P with A the placement's shifts), so the gather *is* the
    propagation.  Works for any shift-structured placement, including
    full replication (where every device is a holder).
    """
    sched = placement.schedule()

    def f(shard, valid, b, data, nvalid):
        i = jax.lax.axis_index(axis_name)
        block = shard.shape[0]
        new_valid = jnp.arange(block) < nvalid
        is_owner = i == b
        shard = jnp.where(is_owner, data, shard)
        valid = jnp.where(is_owner, new_valid, valid)
        stacked = quorum_gather(_with_valid(shard, valid), sched, axis_name)
        return shard, valid, stacked[..., :-1], stacked[..., -1] > 0.5

    spec = PS(axis_name)
    return jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(spec, spec, PS(), PS(), PS()),
        out_specs=(spec, spec, spec, spec)))


def build_state(corpus: np.ndarray, mesh, axis_name: str = "q",
                block: int | None = None, placement=None) -> ServingState:
    """Chunk ``corpus`` [N, d] into P blocks (zero-padded; padding rows
    invalid) and build the resident quorum stacks with one gather.
    ``block`` overrides the per-block row capacity (>= ceil(N/P)) to leave
    empty slots for streamed appends.  ``placement`` picks the residency
    layer (None defers to ``REPRO_PLACEMENT`` / auto == cyclic)."""
    P = mesh.shape[axis_name]
    plc = (placement_from_env(P) if placement is None
           else resolve_placement(placement, P))
    N, d = corpus.shape
    block = max(block or 1, 1, -(-N // P))
    pad = P * block - N
    shard = shard_rows(np.pad(np.asarray(corpus, np.float32),
                              ((0, pad), (0, 0))), mesh, axis_name)
    valid = shard_rows(np.arange(P * block) < N, mesh, axis_name)
    stack, stack_valid = _build_fn(mesh, axis_name, P, plc)(shard, valid)
    return ServingState(shard=shard, valid=valid, stack=stack,
                        stack_valid=stack_valid)


def replace_block(state: ServingState, mesh, axis_name: str, b: int,
                  data: np.ndarray, nvalid: int | None = None,
                  placement=None) -> ServingState:
    """Replace block ``b`` with ``data`` ([rows <= block, d]) and push it to
    the k holder quorums.  Rows beyond ``nvalid`` (default: data row count)
    are marked invalid; data is zero-padded to the block size.
    ``placement`` must match the one the state was built with (the stack
    layout is placement-defined)."""
    P = mesh.shape[axis_name]
    plc = (placement_from_env(P) if placement is None
           else resolve_placement(placement, P))
    block = state.shard.shape[0] // P
    rows, d = data.shape
    if rows > block:
        raise ValueError(f"data has {rows} rows; block capacity is {block}")
    nvalid = rows if nvalid is None else nvalid
    if not 0 <= nvalid <= rows:
        raise ValueError(f"nvalid={nvalid} outside [0, {rows}] — padding "
                         "rows must not be marked valid")
    full = np.zeros((block, d), np.float32)
    full[:rows] = np.asarray(data, np.float32)
    out = update_fn(mesh, axis_name, P, plc)(
        state.shard, state.valid,
        jnp.int32(b), jnp.asarray(full), jnp.int32(nvalid))
    _notify_dirty(b)
    return ServingState(*out)
