"""Thresholded sparse all-pairs similarity join over quorum placements.

The batch engine (core.allpairs, DESIGN.md section 2) always reduces dense
O(N^2) pair results back to blocks.  The canonical all-pairs workload in
practice is the *similarity join* — report only the pairs whose score
passes a threshold (Özkural & Aykanat's all-pairs similarity problem;
Ullman's "some pairs") — where most of the pairwise work is a cheap
rejection.  This module plugs :class:`ThresholdJoinEmitter` into the
unified pair-sweep runtime (core/sweep.py, DESIGN.md section 12) so the
join reuses the quorum schedule and every registered placement but emits
only the passing ``(i, j, score)`` triples (DESIGN.md section 11):

  1. **prefilter** — per-slot norm extrema give an upper bound on every
     block-pair tile's best score (``|x·y| <= |x||y|`` for dot; the norm
     interval gap for L2), so whole tiles whose bound misses the
     threshold are skipped before any pairwise work.
  2. **tile compute + threshold compaction** — each scheduled slot pair's
     [block, block] score tile is thresholded and the passing entries are
     cumsum-compacted into a fixed-capacity per-device buffer (jit-safe:
     shapes are static, the count is a traced scalar).  A fused Pallas
     kernel (kernels/pairwise_threshold.py) replaces the batched inner
     step via the ``batch_fn`` hook, mirroring the dense engine.
  3. **exactly-once emission** — the per-difference ownership rule
     (core.scheduler, DESIGN.md section 3.2) plus the engine dedup mask
     partition all unordered pairs across devices; self-pair tiles keep
     only the strict upper triangle, so every passing global pair
     ``i < j`` is reported by exactly one device.  An optional ppermute
     ring gather (:func:`ring_allgather_hits`) replicates the per-device
     sparse buffers everywhere while preserving that partition.

**Capacity / overflow contract** (DESIGN.md section 11.2): buffers hold
``capacity`` triples; ``count`` is always the *true* number of passing
pairs on the device, and entries past ``capacity`` are dropped — never
reordered or wrapped — so ``count > capacity`` (the overflow flag) is an
exact escalation signal and the kept prefix is valid either way.
:func:`similarity_join` implements the documented two-pass escalation:
re-run with doubled capacity until the overflow flag clears.

Execution modes are the runtime's (DESIGN.md section 4) and honor the
same ``REPRO_ALLPAIRS_MODE`` override: ``batched`` (all tiles in one
einsum + one compaction), ``overlap`` (tiles compact incrementally as
their later block lands, so XLA overlaps the remaining gather shifts),
``scan`` (serial per-pair carry; with the prefilter the ``lax.cond``
genuinely skips pruned tiles' compute — the configuration
BENCH_sparse.json measures).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..kernels.ref import IDX_SENTINEL, NEG_INF, PRECISION
from ..launch.mesh import make_mesh, stage_rows
from ..obs import trace as obs_trace
from . import env as env_mod
from . import sweep as sweep_mod
from .scheduler import PairSchedule
from .sweep import (ENGINE_MODES, SweepEmitter, mark_varying,
                    pair_mask_table)

__all__ = [
    "SparseHits",
    "JoinResult",
    "ThresholdJoinEmitter",
    "default_capacity",
    "pair_score_bounds",
    "quorum_allpairs_threshold",
    "ring_allgather_hits",
    "similarity_join",
    "brute_force_join",
    "threshold_with_gap",
    "threshold_for_selectivity",
    "JOIN_METRICS",
]

JOIN_METRICS = ("dot", "l2")

# global row ids ride through the quantized join kernel's one-hot matmul
# compaction as exact float32 integers, which caps its corpus size
# (DESIGN.md 11.2; the f32 kernel carries int32 ids)
MAX_ROWS_F32_EXACT = 1 << 24
# the starting capacity's three per-device buffers (12 B a slot) take at
# most this share of a device's memory (DESIGN.md 11.2)
START_MEMORY_SHARE = 1 / 1024
# a device's memory where the backend does not report it: a TPU v5e's
DEFAULT_DEVICE_BYTES = 16 << 30


class SparseHits(NamedTuple):
    """One device's compacted passing pairs (inside shard_map).

    vals  : [capacity] float32 — passing scores; slots >= min(count,
            capacity) hold ``NEG_INF``.
    i, j  : [capacity] int32 — global row ids with i < j; empty slots
            hold ``IDX_SENTINEL``.
    count : [] int32 — the TRUE number of passing pairs on this device
            (may exceed capacity; see the overflow contract above).
    stats : [2] int32 or None — with the fused kernel, (tile steps that
            emitted, hits dropped by a full slot row); None elsewhere.
    """

    vals: jax.Array
    i: jax.Array
    j: jax.Array
    count: jax.Array
    stats: Optional[jax.Array] = None


def _device_bytes() -> int:
    """The first device's memory, or a TPU v5e's where the backend does
    not report it (the CPU)."""
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", DEFAULT_DEVICE_BYTES))


def default_capacity(n_candidates: int) -> int:
    """Starting per-device buffer capacity (DESIGN.md section 11.2).

    ``REPRO_SPARSE_CAPACITY`` (documented in the README env-var table;
    validated through the core/env.py registry) overrides; otherwise 1/8
    of the device's candidate count, but no more slots than
    ``START_MEMORY_SHARE`` of the device's memory holds, rounded up to a
    lane-friendly multiple of 128 with a floor of 128.  Read at
    selection time like the other ``REPRO_*`` knobs, and only a *start*:
    :func:`similarity_join` doubles it until the overflow flag clears.
    """
    cap = env_mod.read_knob("REPRO_SPARSE_CAPACITY")
    if cap is not None:
        return int(cap)
    most = int(_device_bytes() * START_MEMORY_SHARE) // 12 // 128 * 128
    cap = max(128, min(-(-n_candidates // 8), most))
    return -(-cap // 128) * 128


def _norm_extrema(blk: jax.Array, valid: jax.Array):
    """(max, min) row norm over a block's valid rows; (0, +inf) when the
    block has none (which makes every bound below reject the tile)."""
    norms = jnp.sqrt(jnp.sum(blk * blk, axis=-1))
    return (jnp.max(jnp.where(valid, norms, 0.0), axis=-1),
            jnp.min(jnp.where(valid, norms, jnp.inf), axis=-1))


def _interval_bound(maxn_i, minn_i, maxn_j, minn_j, metric: str):
    """Tile score upper bound from two blocks' norm extrema — the single
    home of the DESIGN.md 11.1 derivation, shared by every mode.

    ``dot``: Cauchy-Schwarz, ``x·y <= max|x| * max|y|``.  ``l2`` (score
    = -|x-y|^2): reverse triangle inequality, ``|x-y| >= gap`` with gap
    the distance between the [min|x|, max|x|] norm intervals, so the
    score is at most ``-gap^2`` (an all-invalid block's +inf min norm
    yields a -inf bound: always skipped).
    """
    if metric == "dot":
        return maxn_i * maxn_j
    gap = jnp.maximum(jnp.maximum(minn_i - maxn_j, minn_j - maxn_i), 0.0)
    return -jnp.where(jnp.isinf(gap), jnp.inf, gap * gap)


def pair_score_bounds(quorum: jax.Array, valid: jax.Array,
                      lo_slots: jax.Array, hi_slots: jax.Array,
                      metric: str) -> jax.Array:
    """Upper bound on each scheduled tile's best score (DESIGN.md 11.1).

    quorum: [k, block, d]; valid: [k, block] row validity; lo/hi_slots:
    [n_pairs] slot ids.  Per-slot norm extrema feed
    :func:`_interval_bound`; a tile whose bound misses the threshold
    contains no passing pair and is skipped whole — the sparse engine's
    prefilter.
    """
    if metric not in JOIN_METRICS:
        raise ValueError(f"metric must be one of {JOIN_METRICS}, "
                         f"got {metric!r}")
    maxn, minn = _norm_extrema(quorum, valid)                    # [k]
    return _interval_bound(maxn[lo_slots], minn[lo_slots],
                           maxn[hi_slots], minn[hi_slots], metric)


def _tile_scores(bi: jax.Array, bj: jax.Array, metric: str) -> jax.Array:
    """[block, d] x [block, d] -> [block, block] under the join metric.

    The L2 score is ``2 x·y - |x|^2 - |y|^2 = -|x - y|^2`` — the same
    formula as the serving engine and the fused kernels, so float
    rounding (and therefore threshold membership) agrees across paths.
    """
    dot = jnp.dot(bi, bj.T, precision=PRECISION)
    if metric == "dot":
        return dot
    return (2.0 * dot - jnp.sum(bj * bj, axis=-1)[None, :]
            - jnp.sum(bi * bi, axis=-1)[:, None])


def _tile_emit(scores, keep, ga, gb, block: int):
    """Per-tile global-id planes + canonical (i < j) orientation.

    Blocks are disjoint row ranges, so the elementwise (min, max) of the
    two global ids orients every entry; the self-pair tile is restricted
    to the strict upper triangle by the caller, so i < j always holds.
    """
    r = lax.broadcasted_iota(jnp.int32, keep.shape, 0)
    s = lax.broadcasted_iota(jnp.int32, keep.shape, 1)
    gi = ga * block + r
    gj = gb * block + s
    return jnp.minimum(gi, gj), jnp.maximum(gi, gj)


def _scatter_hits(bufs, count, keep_flat, vals_flat, i_flat, j_flat,
                  capacity: int):
    """Cumsum-compact passing entries into the running (bufs, count).

    Positions are ``count + cumsum(keep) - 1``; entries at or past
    ``capacity`` are dropped by the scatter (``mode="drop"``) while the
    returned count still grows by the true passing total — the overflow
    contract.  jit-safe: every shape is static.
    """
    vbuf, ibuf, jbuf = bufs
    keep_i = keep_flat.astype(jnp.int32)
    pos = count + jnp.cumsum(keep_i) - 1
    pos = jnp.where(keep_flat, pos, capacity)        # parked out of range
    vbuf = vbuf.at[pos].set(vals_flat, mode="drop")
    ibuf = ibuf.at[pos].set(i_flat, mode="drop")
    jbuf = jbuf.at[pos].set(j_flat, mode="drop")
    return (vbuf, ibuf, jbuf), count + jnp.sum(keep_i)


def _empty_bufs(capacity: int, axis_name: str):
    """Varying-marked empty buffers (the scan carry / compaction init)."""
    return (mark_varying(jnp.zeros((capacity,), jnp.float32), axis_name),
            mark_varying(jnp.zeros((capacity,), jnp.int32), axis_name),
            mark_varying(jnp.zeros((capacity,), jnp.int32), axis_name))


def _finalize(bufs, count, capacity: int) -> SparseHits:
    """Sentinel-fill the unused tail so every mode returns the same
    padded layout: (NEG_INF, IDX_SENTINEL) past min(count, capacity)."""
    vbuf, ibuf, jbuf = bufs
    used = lax.broadcasted_iota(jnp.int32, (capacity,), 0) < count
    return SparseHits(
        vals=jnp.where(used, vbuf, NEG_INF),
        i=jnp.where(used, ibuf, jnp.int32(IDX_SENTINEL)),
        j=jnp.where(used, jbuf, jnp.int32(IDX_SENTINEL)),
        count=count,
    )


def _select_mode(schedule: PairSchedule, block: int,
                 batch_fn: Optional[Callable]) -> str:
    """The sparse engine's ``mode="auto"`` working set fed to the shared
    heuristic (core/sweep.py select_mode, DESIGN.md section 4): scores
    f32 + two i32 id planes per [n_pairs, block, block] tile entry."""
    return sweep_mod.select_mode(
        schedule, schedule.n_pairs * block * block * 12, batch_fn)


def _pair_meta(schedule: PairSchedule, axis_name: str, block: int,
               n_valid: Optional[int]):
    """Per-pair traced metadata on this device: global block ids, valid
    row counts, self-pair flags.  ``n_valid`` (static) marks trailing
    padding rows of the global [P * block] numbering invalid."""
    P = schedule.P
    i = lax.axis_index(axis_name)
    shifts = jnp.asarray(schedule.shifts, jnp.int32)
    gblocks = (i + shifts) % P                                    # [k]
    # slot ids stay host constants: the fused top-k kernels build their
    # item table from them (kernels/pairwise_topk.py directed_items)
    lo = schedule.pair_slots[:, 0].astype(np.int32)
    hi = schedule.pair_slots[:, 1].astype(np.int32)
    ga = gblocks[lo]
    gb = gblocks[hi]
    if n_valid is None:
        nv = jnp.full((schedule.k,), block, jnp.int32)
    else:
        nv = jnp.clip(n_valid - gblocks * block, 0, block).astype(jnp.int32)
    is_self = jnp.asarray(schedule.pair_diff == 0)
    return lo, hi, ga, gb, nv[lo], nv[hi], is_self, gblocks, nv


def _tile_keep(scores, thr, nv_lo, nv_hi, is_self):
    """Threshold + row-validity + self-pair strict-triangle mask."""
    r = lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    s = lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    keep = (scores >= thr) & (r < nv_lo) & (s < nv_hi)
    return keep & jnp.where(is_self, r < s, True)


class ThresholdJoinEmitter(SweepEmitter):
    """Fixed-capacity threshold compaction over the scheduled pairs
    (DESIGN.md sections 11, 12.2 — the similarity-join workload).

    Each active tile is scored, thresholded under the ownership rules
    (row validity, self-pair strict triangle, the engine dedup mask) and
    cumsum-compacted into per-device (vals, i, j) buffers under the
    overflow contract of DESIGN.md 11.2.  The norm-bound prefilter
    (DESIGN.md 11.1) deactivates whole tiles: up-front over the gathered
    stack in batched/scan modes (:meth:`prepare`), incrementally from
    per-slot extrema as blocks land in overlap mode
    (:meth:`overlap_slot`).
    """

    def __init__(self, schedule: PairSchedule, mask, thr, capacity: int,
                 metric: str, block: int, prefilter: bool, axis_name: str,
                 meta, nv, batch_fn=None):
        self.schedule = schedule
        self.mask = mask
        self.thr = thr
        self.capacity = capacity
        self.metric = metric
        self.block = block
        self.prefilter = prefilter
        self.axis_name = axis_name
        self.lo, self.hi, self.ga, self.gb, self.nv_lo, self.nv_hi, \
            self.is_self = meta
        self.nv = nv
        self.batch_fn = batch_fn
        self.active = self.mask > 0           # refined by prepare()

    @staticmethod
    def delta_retract(standing, stale, ctx=None):
        """Retract stale (i, j) rows from a standing sorted hit set
        (DESIGN.md section 16.3).  A global pair lives in exactly one
        tile, so removing the dirty tiles' old rows is an exact set
        difference — no other tile can have contributed them."""
        standing = np.asarray(standing, np.int64).reshape(-1, 2)
        stale = np.asarray(stale, np.int64).reshape(-1, 2)
        if not len(standing) or not len(stale):
            return standing
        key = standing[:, 0] << np.int64(32) | standing[:, 1]
        gone = stale[:, 0] << np.int64(32) | stale[:, 1]
        return standing[~np.isin(key, gone)]

    @staticmethod
    def delta_fold(standing, fresh, ctx=None):
        """Insert fresh (i, j) rows into a standing hit set and restore
        the canonical (lo, hi) lexsort order (DESIGN.md section 16.3) —
        rows are globally unique, so the union re-sorted is bit-equal
        to a from-scratch fold."""
        standing = np.asarray(standing, np.int64).reshape(-1, 2)
        fresh = np.asarray(fresh, np.int64).reshape(-1, 2)
        allr = np.concatenate([standing, fresh], axis=0)
        order = np.lexsort((allr[:, 1], allr[:, 0]))
        return allr[order]

    def prepare(self, quorum):
        """Norm-bound prefilter over the full gathered stack
        (batched/scan modes; DESIGN.md 11.1)."""
        if not self.prefilter:
            return
        valid = (lax.broadcasted_iota(
            jnp.int32, (self.schedule.k, self.block), 1) < self.nv[:, None])
        bounds = pair_score_bounds(quorum, valid, self.lo, self.hi,
                                   self.metric)
        self.active = self.active & (bounds >= self.thr)

    def batch(self, quorum):
        """One compaction over every tile.  The batched jnp step IS the
        ref oracle — one home for the threshold-membership
        compute/compaction (DESIGN.md 11.3), with a fused Pallas kernel
        swapping in through the same hook."""
        batch_fn = self.batch_fn
        if batch_fn is None:
            from ..kernels import ref as kref
            batch_fn = functools.partial(
                kref.pairwise_threshold, threshold=self.thr,
                capacity=self.capacity, block_rows=self.block,
                metric=self.metric)
        meta = jnp.stack([self.active.astype(jnp.int32),
                          self.is_self.astype(jnp.int32),
                          self.ga, self.gb, self.nv_lo, self.nv_hi],
                         axis=1)                           # [n_pairs, 6]
        vals, ei, ej, count, *stats = batch_fn(quorum, self.lo, self.hi,
                                               meta)
        return SparseHits(vals=vals, i=ei, j=ej,
                          count=count.reshape(()).astype(jnp.int32),
                          stats=stats[0] if stats else None)

    def scan_init(self):
        """Empty compaction buffers + zero true count (varying-marked)."""
        return (_empty_bufs(self.capacity, self.axis_name),
                mark_varying(jnp.int32(0), self.axis_name))

    def scan_items(self):
        """Per-pair (slots, active, self flag, block ids, valid counts)."""
        return (self.lo, self.hi, self.active, self.is_self, self.ga,
                self.gb, self.nv_lo, self.nv_hi)

    def scan_emit(self, carry, quorum, item):
        """Serial per-pair compaction; pruned/masked tiles skip their
        compute via ``lax.cond`` — with the prefilter this is a real
        FLOP saving, not just a masked multiply (the BENCH_sparse.json
        configuration)."""
        bufs, count = carry
        lo_p, hi_p, act_p, self_p, ga_p, gb_p, nvl_p, nvh_p = item

        def compute(c):
            bufs_c, cnt = c
            bi = jnp.take(quorum, lo_p, axis=0)
            bj = jnp.take(quorum, hi_p, axis=0)
            scores = _tile_scores(bi, bj, self.metric)
            keep = _tile_keep(scores, self.thr, nvl_p, nvh_p, self_p)
            ei, ej = _tile_emit(scores, keep, ga_p, gb_p, self.block)
            return _scatter_hits(bufs_c, cnt, keep.reshape(-1),
                                 scores.reshape(-1).astype(jnp.float32),
                                 ei.reshape(-1), ej.reshape(-1),
                                 self.capacity)

        return lax.cond(act_p, compute, lambda c: c, (bufs, count))

    def scan_finalize(self, carry):
        """Sentinel-fill the unused buffer tail (the shared layout)."""
        bufs, count = carry
        return _finalize(bufs, count, self.capacity)

    def overlap_begin(self):
        """Boxed (bufs, count) carry + the per-slot extrema list the
        incremental prefilter appends into."""
        return {"extrema": [],
                "carry": (_empty_bufs(self.capacity, self.axis_name),
                          mark_varying(jnp.int32(0), self.axis_name))}

    def overlap_slot(self, state, slot, blk):
        """Per-slot norm extrema, computed once at land time, feed the
        shared bound helper (DESIGN.md 11.1)."""
        if self.prefilter:
            vrow = (lax.broadcasted_iota(jnp.int32, (self.block,), 0)
                    < self.nv[slot])
            state["extrema"].append(_norm_extrema(blk, vrow))

    def overlap_emit(self, state, idx, bi, bj):
        """Score/compact one tile as soon as its later block lands, so
        XLA's latency-hiding scheduler overlaps the remaining ppermutes
        with tile compute (the sparse analog of the dense overlap mode,
        DESIGN.md section 4)."""
        l_s = int(self.schedule.pair_slots[idx, 0])
        h_s = int(self.schedule.pair_slots[idx, 1])
        act = self.mask[idx] > 0
        if self.prefilter:
            (mx_i, mn_i) = state["extrema"][l_s]
            (mx_j, mn_j) = state["extrema"][h_s]
            act = act & (_interval_bound(mx_i, mn_i, mx_j, mn_j,
                                         self.metric) >= self.thr)

        def compute(c, bi=bi, bj=bj, idx=idx):
            bufs_c, cnt = c
            scores = _tile_scores(bi, bj, self.metric)
            keep = _tile_keep(scores, self.thr, self.nv_lo[idx],
                              self.nv_hi[idx], self.is_self[idx])
            ei, ej = _tile_emit(scores, keep, self.ga[idx], self.gb[idx],
                                self.block)
            return _scatter_hits(bufs_c, cnt, keep.reshape(-1),
                                 scores.reshape(-1).astype(jnp.float32),
                                 ei.reshape(-1), ej.reshape(-1),
                                 self.capacity)

        state["carry"] = lax.cond(act, compute, lambda c: c, state["carry"])

    def overlap_finalize(self, state):
        """Sentinel-fill the unused buffer tail (the shared layout)."""
        bufs, count = state["carry"]
        return _finalize(bufs, count, self.capacity)


def quorum_allpairs_threshold(
    x: jax.Array,
    *,
    threshold: float,
    axis_name: str,
    capacity: int,
    schedule: PairSchedule | None = None,
    axis_size: int | None = None,
    placement=None,
    metric: str = "dot",
    mode: str = "auto",
    mask: jax.Array | None = None,
    n_valid: int | None = None,
    prefilter: bool = True,
    batch_fn: Callable[..., Tuple[jax.Array, ...]] | None = None,
) -> SparseHits:
    """Distributed thresholded similarity join (DESIGN.md section 11).

    Must run inside shard_map with ``x`` the local [block, d] shard.
    Emits every global pair ``i < j`` with ``score(x_i, x_j) >=
    threshold`` exactly once across devices (the per-difference ownership
    partition; self-pair tiles keep the strict upper triangle, the even-P
    d = P/2 orbit is deduplicated by ``mask`` exactly as in the dense
    engine).  Returns this device's :class:`SparseHits` under the
    capacity/overflow contract in the module docstring.

    ``placement`` / ``schedule`` / ``axis_size`` select the residency
    layer exactly as in :func:`core.allpairs.quorum_allpairs` (env
    ``REPRO_PLACEMENT`` consulted when both are None); a full-replication
    placement runs the same generic pipeline over its A = {0..P-1}
    shifts — no allgather special case, the join output is already
    sparse.  ``mode`` is the runtime's batched/overlap/scan surface of
    DESIGN.md section 4 (``REPRO_ALLPAIRS_MODE`` honored); ``prefilter``
    toggles the norm-bound tile skip (:func:`pair_score_bounds`);
    ``n_valid`` (static int) invalidates global rows >= n_valid (corpus
    padding); ``batch_fn(quorum, lo, hi, meta) -> (vals, i, j, count[,
    stats])`` is the fused-kernel hook (kernels.ops.pairwise_threshold),
    batched mode only.
    """
    if metric not in JOIN_METRICS:
        raise ValueError(f"metric must be one of {JOIN_METRICS}, "
                         f"got {metric!r}")
    sweep_mod.validate_mode(mode, batch_fn)
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    schedule, placement = sweep_mod.resolve_sweep_placement(
        schedule, axis_size, placement)
    if schedule is None:
        schedule = placement.schedule()

    block = x.shape[0]
    if mask is None:
        table = jnp.asarray(pair_mask_table(schedule))   # [P, n_pairs]
        mask = jnp.take(table, lax.axis_index(axis_name), axis=0)
    mask = mask.reshape(-1)

    if mode == "auto":
        mode = _select_mode(schedule, block, batch_fn)

    lo, hi, ga, gb, nv_lo, nv_hi, is_self, gblocks, nv = _pair_meta(
        schedule, axis_name, block, n_valid)
    thr = jnp.float32(threshold)

    emitter = ThresholdJoinEmitter(
        schedule, mask, thr, capacity, metric, block, prefilter, axis_name,
        (lo, hi, ga, gb, nv_lo, nv_hi, is_self), nv, batch_fn=batch_fn)
    return sweep_mod.pair_sweep(emitter, schedule=schedule,
                                axis_name=axis_name, mode=mode, x=x)


def ring_allgather_hits(hits: SparseHits, *, axis_name: str,
                        P: int) -> SparseHits:
    """Replicate every device's sparse buffers with a ppermute ring
    (DESIGN.md section 11.3).

    P - 1 single-step ``lax.ppermute`` shifts rotate each device's
    (vals, i, j, count) past every other device; arrivals are placed at
    their source device's row, so all devices end with the identical
    device-ordered [P, capacity] stack — the sparse analog of the dense
    engine's collectives (no ``all_gather``, matching the repo's
    shift-only data plane).  The pair-ownership partition guarantees the
    union of rows lists every passing pair exactly once.
    """
    i = lax.axis_index(axis_name)
    fields = [hits.vals, hits.i, hits.j, hits.count.reshape(1)]
    out = [jnp.zeros((P,) + f.shape, f.dtype).at[i].set(f) for f in fields]
    perm = [(j, (j + 1) % P) for j in range(P)]
    cur = fields
    for step in range(1, P):
        cur = [lax.ppermute(c, axis_name, perm) for c in cur]
        src = (i - step) % P
        out = [o.at[src].set(c) for o, c in zip(out, cur)]
    vals, ei, ej, count = out
    return SparseHits(vals=vals, i=ei, j=ej, count=count.reshape(P))


# ---------------------------------------------------------------------------
# Host-level driver: padding, program cache, capacity escalation
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class JoinResult:
    """Host-side similarity-join output (:func:`similarity_join`).

    i, j, scores : the passing pairs, sorted by (i, j); i < j, each pair
        exactly once.  ``counts`` is the per-device true passing totals,
        ``capacity`` the final per-device buffer size, ``escalations``
        how many capacity doublings the overflow contract forced, and
        ``overflow`` whether the final pass still overflowed (only with
        ``escalate=False`` — the kept pairs are then a valid prefix).
    """

    i: np.ndarray
    j: np.ndarray
    scores: np.ndarray
    counts: np.ndarray
    capacity: int
    escalations: int
    overflow: bool

    @property
    def n_pairs(self) -> int:
        """Number of passing pairs reported."""
        return int(self.i.shape[0])


@functools.lru_cache(maxsize=64)
def _join_fn(mesh, axis_name: str, N: int, block: int, threshold: float,
             metric: str, mode: str, capacity: int, prefilter: bool,
             use_kernel: bool, placement, slot: int | None = None):
    """Build (and cache) the jitted distributed join program — one trace
    per (mesh, shape, threshold, capacity, slot, ...) key, reused across
    escalation retries at the same capacity and repeated joins.  With
    ``use_kernel`` the program also returns the kernel's per-device
    ``stats`` [P, 2] (``slot``: its slot-row width)."""
    from jax.sharding import PartitionSpec as PS
    sched = placement.schedule()
    mask_table = jnp.asarray(pair_mask_table(sched))
    from ..kernels import ops as kops
    batch_fn = None
    if use_kernel:
        if mode not in ("batched", "auto"):
            raise ValueError(
                f"use_kernel needs the batched mode (got mode={mode!r}); "
                "the fused kernel only replaces the batched inner step")
        batch_fn = functools.partial(
            kops.pairwise_threshold, threshold=threshold, capacity=capacity,
            block_rows=block, metric=metric, slot=slot)

    def body(xb, mb):
        hits = quorum_allpairs_threshold(
            xb, threshold=threshold, axis_name=axis_name, capacity=capacity,
            schedule=sched, mask=mb, metric=metric, mode=mode,
            n_valid=N, prefilter=prefilter, batch_fn=batch_fn)
        out = (hits.vals, hits.i, hits.j, hits.count.reshape(1))
        if use_kernel:
            out += (hits.stats.reshape(1, 2),)
        return out

    spec = PS(axis_name)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec),
        out_specs=(spec,) * (5 if use_kernel else 4),
        check_vma=kops.shard_map_check_vma(use_kernel)))
    return lambda xs: fn(xs, mask_table)


# per join program key and starting capacity, the (capacity, slot) that
# sufficed last time: same-shaped joins start there, so a run of them
# escalates once at most (DESIGN.md 11.2)
_LAST_FIT: dict = {}
_LAST_FIT_KEYS = 64


def similarity_join(corpus, mesh, *, threshold: float, axis_name: str = "q",
                    metric: str = "dot", mode: str = "auto", placement=None,
                    capacity: int | None = None, prefilter: bool = True,
                    use_kernel: bool = False, escalate: bool = True,
                    max_doublings: int = 16,
                    quant: str | None = None) -> JoinResult:
    """All pairs of ``corpus`` rows with score >= threshold, exactly once.

    The host entry point (DESIGN.md section 11): stages the [N, d]
    corpus as P quorum blocks (:func:`launch.mesh.stage_rows`), runs
    :func:`quorum_allpairs_threshold` under the selected placement (None
    defers to ``REPRO_PLACEMENT``), and applies the two-pass capacity
    escalation — whenever any device's overflow flag is set, the
    per-device ``capacity`` doubles (and, where a fused-kernel step
    dropped hits, its slot row widens) and the join re-runs (a fresh jit
    at each capacity; the kept work is only the cheap rejected majority,
    which is the point of the workload).  With
    ``escalate=False`` an overflowing pass returns its valid prefix with
    ``overflow=True`` instead of retrying.  Without an explicit
    ``capacity`` a join starts at what sufficed for the last join of the
    same program, else at :func:`default_capacity`.

    ``use_kernel`` routes the batched inner step through the fused Pallas
    kernel (kernels/pairwise_threshold.py); ``prefilter`` toggles the
    norm-bound block-pair skip.  ``quant`` selects the quantized
    band-emit + exact-rescoring path (DESIGN.md section 17): ``"int8"``
    / ``"bf16"`` route through :func:`core.quant.quant_similarity_join`
    (bit-identical results; ``prefilter`` does not apply there — the
    certified band is the selectivity mechanism), ``"off"`` forces the
    pure f32 path, and None defers to ``REPRO_QUANT``.  Returns a
    :class:`JoinResult` with pairs sorted by (i, j).
    """
    if quant is None:
        from .quant import quant_from_env
        quant = quant_from_env()
    if quant != "off":
        from . import quant as quant_mod
        return quant_mod.quant_similarity_join(
            corpus, mesh, threshold=threshold, quant=quant,
            axis_name=axis_name, metric=metric, mode=mode,
            placement=placement, capacity=capacity, use_kernel=use_kernel,
            escalate=escalate, max_doublings=max_doublings)
    from ..kernels.pairwise_threshold import SLOT
    from .placement import placement_from_env, resolve_placement
    N = len(corpus)
    P = mesh.shape[axis_name]
    plc = (placement_from_env(P) if placement is None
           else resolve_placement(placement, P))
    block = -(-N // P)
    sched = plc.schedule()
    n_cand = sched.n_pairs * block * block
    if capacity is None:
        start = default_capacity(n_cand)
        key = (mesh, axis_name, N, block, float(threshold), metric, mode,
               prefilter, bool(use_kernel), plc, start)
        cap, slot = _LAST_FIT.get(key, (start, SLOT))
    else:
        key, cap, slot = None, int(capacity), SLOT

    escalations = 0
    # host spans of one join (DESIGN.md section 14.1): the device idles
    # in all but join.wait
    tr = obs_trace.get_tracer()
    with tr.span("sparse.join", N=N, P=P, metric=metric, mode=mode,
                 threshold=float(threshold), placement=plc.name):
        with tr.span("join.stage", rows=N, P=P, padded=int(P * block != N)):
            x = np.ascontiguousarray(corpus, np.float32)
        with tr.span("join.upload", rows=N, P=P):
            xs = jax.block_until_ready(stage_rows(x, mesh, axis_name, block))
        while True:
            with tr.span("join.dispatch", rows=N, P=P, capacity=cap):
                run = _join_fn(mesh, axis_name, N, block, float(threshold),
                               metric, mode, cap, prefilter, use_kernel, plc,
                               slot if use_kernel else None)
                out = run(xs)
            with tr.span("join.wait", rows=N, P=P):
                jax.block_until_ready(out)
            with tr.span("join.fetch", rows=N, P=P):
                vals, gi, gj, counts, *stats = (np.asarray(a) for a in out)
            counts = counts.reshape(-1)
            stats = stats[0].reshape(P, 2) if stats else np.zeros((P, 2),
                                                                  np.int32)
            wide = bool((counts > cap).any())
            short = bool((stats[:, 1] > 0).any())
            overflow = wide or short
            if (not overflow or not escalate
                    or escalations >= max_doublings):
                break
            cap = 2 * cap if wide else cap
            slot = 2 * slot if short else slot
            escalations += 1
        if key is not None and not overflow:
            _LAST_FIT.pop(key, None)
            _LAST_FIT[key] = (cap, slot)
            while len(_LAST_FIT) > _LAST_FIT_KEYS:
                _LAST_FIT.pop(next(iter(_LAST_FIT)))
        if tr:
            tr.count("sparse.tiles_scheduled", P * sched.n_pairs)
            tr.count("sparse.candidates", P * n_cand)
            if prefilter:
                tr.count("sparse.tiles_pruned",
                         _count_pruned_tiles(x, N, block, sched,
                                             float(threshold), metric))
            tr.count("sparse.escalations", escalations)
            if use_kernel:
                for dev in range(P):
                    tr.count("pairwise_threshold.emit_steps",
                             int(stats[dev, 0]), device=dev)
                    tr.count("pairwise_threshold.hits", int(counts[dev]),
                             device=dev)
        if overflow and escalate:
            raise RuntimeError(
                f"similarity join still overflows capacity {cap} (slot "
                f"{slot}) after {escalations} doublings; raise "
                "`capacity`/`max_doublings` or the threshold")

        with tr.span("join.sort", rows=N, P=P):
            vals = vals.reshape(P, -1)
            gi = gi.reshape(P, -1)
            gj = gj.reshape(P, -1)
            kept = np.minimum(counts - stats[:, 1], cap)
            ai = np.concatenate([gi[dev, :kept[dev]] for dev in range(P)])
            aj = np.concatenate([gj[dev, :kept[dev]] for dev in range(P)])
            av = np.concatenate([vals[dev, :kept[dev]] for dev in range(P)])
            order = np.lexsort((aj, ai))
            ai, aj, av = ai[order], aj[order], av[order]
    if tr:
        tr.count("sparse.pairs_emitted", int(ai.shape[0]))
    return JoinResult(i=ai, j=aj, scores=av, counts=counts, capacity=cap,
                      escalations=escalations, overflow=overflow)


def _count_pruned_tiles(x: np.ndarray, N: int, block: int,
                        sched: PairSchedule, threshold: float,
                        metric: str) -> int:
    # host-side replay of the DESIGN.md 11.1 interval bound over every
    # device's scheduled tiles — the sparse.tiles_pruned counter
    P = sched.P
    norms = np.zeros(P * block, np.float32)
    norms[:N] = np.sqrt(np.sum(x * x, axis=-1))
    norms = norms.reshape(P, block)
    valid = (np.arange(P * block).reshape(P, block) < N)
    maxn = np.where(valid, norms, 0.0).max(axis=-1)
    minn = np.where(valid, norms, np.inf).min(axis=-1)
    pruned = 0
    for i in range(P):
        for s in range(sched.n_pairs):
            a = (i + int(sched.shifts[sched.pair_slots[s, 0]])) % P
            b = (i + int(sched.shifts[sched.pair_slots[s, 1]])) % P
            if metric == "dot":
                bound = maxn[a] * maxn[b]
            else:
                gap = max(minn[a] - maxn[b], minn[b] - maxn[a], 0.0)
                bound = -np.inf if np.isinf(gap) else -(gap * gap)
            if bound < threshold:
                pruned += 1
    return pruned


def _pair_score_matrix(corpus: np.ndarray, metric: str) -> np.ndarray:
    """Host-side [N, N] score matrix with the engine's f32 formulas."""
    if metric not in JOIN_METRICS:
        raise ValueError(f"metric must be one of {JOIN_METRICS}, "
                         f"got {metric!r}")
    c = np.asarray(corpus, np.float32)
    s = c @ c.T
    if metric == "l2":
        n2 = (c * c).sum(-1)
        s = 2.0 * s - n2[None, :] - n2[:, None]
    return s


def brute_force_join(corpus: np.ndarray, threshold: float,
                     metric: str = "dot"):
    """Dense O(N^2) oracle: all (i, j, score) with i < j and score >=
    threshold, sorted by (i, j).  Scores use the same float32 formula as
    the engine (DESIGN.md section 11.3) so membership agrees away from
    exact-threshold ties; tests pick thresholds with a guaranteed gap."""
    s = _pair_score_matrix(corpus, metric)
    iu, ju = np.triu_indices(s.shape[0], k=1)
    keep = s[iu, ju] >= threshold
    return iu[keep], ju[keep], s[iu, ju][keep]


def threshold_with_gap(scores, selectivity: float,
                       min_gap: float = 1e-4) -> float:
    """A threshold passing ~``selectivity`` of ``scores`` (any shape),
    placed at the midpoint of a score gap wider than ``min_gap`` near
    that quantile, so float-rounding differences between engine paths
    cannot flip membership (DESIGN.md section 11.3).  The single home of
    the gap-placement idiom — the pairwise wrapper below and the serving
    selfcheck both use it."""
    flat = np.sort(np.asarray(scores, np.float32).reshape(-1))[::-1]
    target = max(1, min(len(flat) - 2, int(round(selectivity * len(flat)))))
    # widen the search until an adjacent gap exceeds min_gap
    for off in range(0, len(flat) - 1):
        for idx in (target - off, target + off):
            if 0 < idx < len(flat):
                gap = flat[idx - 1] - flat[idx]
                if gap > min_gap:
                    return float((flat[idx - 1] + flat[idx]) / 2.0)
    raise ValueError("no score gap wide enough for a robust threshold")


def threshold_for_selectivity(corpus: np.ndarray, selectivity: float,
                              metric: str = "dot",
                              min_gap: float = 1e-4) -> float:
    """A join threshold passing ~``selectivity`` of all unordered pairs
    of ``corpus`` rows — :func:`threshold_with_gap` over the upper
    triangle of the pairwise score matrix (DESIGN.md section 11.3)."""
    s = _pair_score_matrix(corpus, metric)
    iu, ju = np.triu_indices(s.shape[0], k=1)
    return threshold_with_gap(s[iu, ju], selectivity, min_gap)


# ---------------------------------------------------------------------------
# Selfcheck (subprocess entry point — tests/test_sparse.py sweeps this)
# ---------------------------------------------------------------------------

def selfcheck_main(nblocks: int | None = None,
                   modes: Sequence[str] = ENGINE_MODES + ("kernel",),
                   placement: str | None = None) -> None:
    """Distributed sparse-join selfcheck, mirroring core.selfcheck
    (DESIGN.md section 11.5).

    Run as ``XLA_FLAGS=--xla_force_host_platform_device_count=<P> python
    -m repro.core.sparse [P] [modes] [placement]``.  Asserts index-level
    pair-set equality with the dense brute-force oracle for every
    requested mode (incl. the fused ``kernel`` batched path), both
    metrics, prefilter on/off, plus the ring-gather replication and the
    overflow/escalation contract.
    """
    from .placement import placement_from_env, resolve_placement

    devs = jax.devices()
    Pn = nblocks or len(devs)
    assert len(devs) >= Pn, f"need {Pn} devices, have {len(devs)}"
    plc = (placement_from_env(Pn) if placement is None
           else resolve_placement(placement, Pn))
    mesh = make_mesh((Pn,), ("q",), devices=devs[:Pn])
    block, d = 8, 16
    rng = np.random.default_rng(0)
    N = Pn * block - 3          # ragged tail: exercises row validity
    corpus = rng.normal(size=(N, d)).astype(np.float32)
    # two low-norm block spans make whole tiles prunable for `dot`
    corpus[: 2 * block] *= 0.05

    for metric in JOIN_METRICS:
        thr = threshold_for_selectivity(corpus, 0.08, metric)
        wi, wj, wv = brute_force_join(corpus, thr, metric)
        label = f"P={Pn} metric={metric}"
        for m in modes:
            mode, uk = ("batched", True) if m == "kernel" else (m, False)
            for pf in (True, False):
                res = similarity_join(corpus, mesh, threshold=thr,
                                      metric=metric, mode=mode,
                                      placement=plc, use_kernel=uk,
                                      prefilter=pf)
                np.testing.assert_array_equal(
                    res.i, wi, err_msg=f"{label} mode={m} prefilter={pf}")
                np.testing.assert_array_equal(
                    res.j, wj, err_msg=f"{label} mode={m} prefilter={pf}")
                np.testing.assert_allclose(
                    res.scores, wv, rtol=1e-5, atol=1e-5,
                    err_msg=f"{label} mode={m} prefilter={pf}")

    # overflow contract: a capacity below the busiest device's true count
    # must flag, keep a valid prefix, and escalate back to the full answer
    thr = threshold_for_selectivity(corpus, 0.08, "dot")
    wi, wj, _ = brute_force_join(corpus, thr, "dot")
    base = similarity_join(corpus, mesh, threshold=thr, placement=plc)
    np.testing.assert_array_equal(base.i, wi)
    np.testing.assert_array_equal(base.j, wj)
    mx = int(base.counts.max())
    assert mx >= 2, (mx, "corpus too small to exercise overflow")
    cap_small = max(1, mx // 2)
    low = similarity_join(corpus, mesh, threshold=thr, capacity=cap_small,
                          placement=plc, escalate=False)
    assert low.overflow and (low.counts > cap_small).any(), low.counts
    got = set(zip(low.i.tolist(), low.j.tolist()))
    assert got <= set(zip(wi.tolist(), wj.tolist())) and len(got) == len(low.i)
    esc = similarity_join(corpus, mesh, threshold=thr, capacity=cap_small,
                          placement=plc)
    assert esc.escalations >= 1, esc.escalations
    np.testing.assert_array_equal(esc.i, wi)
    np.testing.assert_array_equal(esc.j, wj)

    # ppermute ring gather: every device ends with the identical stack
    from jax.sharding import PartitionSpec as PS
    sched = plc.schedule()
    xs = stage_rows(corpus, mesh, "q", -(-N // Pn))
    mask_table = jnp.asarray(pair_mask_table(sched))
    cap = esc.capacity

    def body(xb, mb):
        hits = quorum_allpairs_threshold(
            xb, threshold=thr, axis_name="q", capacity=cap, schedule=sched,
            mask=mb, n_valid=N)
        g = ring_allgather_hits(hits, axis_name="q", P=Pn)
        return (hits.vals, hits.i, hits.count.reshape(1),
                g.vals[None], g.i[None], g.count[None])

    spec = PS("q")
    out = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec),
        out_specs=(spec,) * 6))(xs, mask_table)
    lv, li, lc, gv, gi_, gc = (np.asarray(a) for a in out)
    lv, li = lv.reshape(Pn, cap), li.reshape(Pn, cap)
    for dev in range(Pn):           # every device's gathered copy agrees
        np.testing.assert_array_equal(gv[dev], lv)
        np.testing.assert_array_equal(gi_[dev], li)
        np.testing.assert_array_equal(gc[dev], lc.reshape(Pn))

    sel = len(wi) / max(1, N * (N - 1) // 2)
    print(f"sparse selfcheck OK: P={Pn} placement={plc.describe()} "
          f"modes={','.join(modes)} hits={len(wi)} "
          f"selectivity={100 * sel:.1f}% capacity={esc.capacity}")


if __name__ == "__main__":
    import sys
    selfcheck_main(
        int(sys.argv[1]) if len(sys.argv) > 1 else None,
        tuple(sys.argv[2].split(",")) if len(sys.argv) > 2
        else ENGINE_MODES + ("kernel",),
        sys.argv[3] if len(sys.argv) > 3 else None)
