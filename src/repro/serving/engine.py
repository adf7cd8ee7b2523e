"""The online query engine: cover-routed fused top-k over quorum stacks.

A query microbatch ``[Q, d]`` is broadcast to the cover devices
(serving/cover.py); each scores it against its resident ``[k, block, d]``
quorum stack under the dedup mask (so every corpus row scores exactly
once), selects a local top-k, and a ppermute tree merge combines the
per-device lists into the global ``[Q, topk]`` result in ceil(log2 P)
rounds (DESIGN.md section 9).  In this harness all P devices run the SPMD
program — non-cover devices contribute sentinel-only lists; a production
router would simply not send them the query.

Selection is everywhere by the total order **(-score, global index)** via
two-key ``lax.sort``, so results are deterministic and bit-identical
across execution modes, the fused kernel, and the brute-force oracle —
ties break toward the smaller corpus index.

Local scoring is a *slot sweep* on the unified pair-sweep runtime
(core/sweep.py, DESIGN.md section 12): the work items are the k resident
slots (``sweep.slot_items``) instead of the schedule's slot pairs, the
stack is already resident (no gather), and the runtime's shared mode
surface applies (DESIGN.md section 4):

  * ``batched`` — one einsum over the whole stack + a single top-k over
    k*block candidates (fastest; O(Q * k * block) score memory).  An
    optional ``batch_fn`` (kernels/query_score.py via kernels.ops) fuses
    slot gather + scoring + dedup mask + the running top-k in one Pallas
    launch.
  * ``overlap`` — per-slot scoring unrolled with a tournament (pairwise
    tree) merge: slot scores are independent, so the log2(k)-deep merge
    exposes slot-level parallelism to the scheduler instead of the scan
    mode's k-long serial carry chain.
  * ``scan``    — lax.scan over slots with a running [Q, topk] carry
    (lowest memory; the correctness oracle).
  * ``auto``    — the shared heuristic (``REPRO_ALLPAIRS_MODE`` override
    first, then batched while the score working set fits the
    ``REPRO_BATCH_BYTES_LIMIT`` budget, else overlap when k >= 3, else
    scan).
"""

from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as PS

from ..core import sweep as sweep_mod
from ..core.allpairs import mark_varying
from ..core.placement import (Placement, get_placement, placement_from_env,
                              resolve_placement)
from ..core.scheduler import PairSchedule
from ..core.sparse import default_capacity
from ..core.sweep import SweepEmitter, merge_topk, slot_items, topk_by_score
from ..kernels.ref import IDX_SENTINEL, NEG_INF, QUERY_METRICS as METRICS
from ..kernels.ref import PRECISION, varying_like
from ..obs import trace as obs_trace
from .cover import build_cover
from .stream import ServingState, build_state, replace_block

__all__ = [
    "IDX_SENTINEL",
    "topk_by_score",
    "merge_topk",
    "tree_merge_topk",
    "quantize_pow2",
    "quorum_query_topk",
    "quorum_query_threshold",
    "QueryTopKEmitter",
    "QueryThresholdEmitter",
    "ServingCorpus",
]


def quantize_pow2(n: int, floor: int = 1) -> int:
    """Round ``n`` up to the smallest power of two >= max(n, floor).

    The program-cache quantizer (DESIGN.md section 15.2): request-shape
    parameters (``topk``, range-query ``capacity``, packed microbatch
    width) are bucketed onto powers of two before they become jit
    program-cache keys, so heterogeneous traffic compiles O(log N)
    programs instead of one per observed value — and capacity
    escalation (doubling) maps onto the *same* bucket set instead of
    flooding the LRU with one entry per escalated size.
    """
    n = max(int(n), int(floor), 1)
    return 1 << (n - 1).bit_length()



def _scores(queries: jax.Array, blk: jax.Array, metric: str) -> jax.Array:
    """[Q, d] x [block, d] -> [Q, block] under the chosen metric.

    ``l2`` scores are ``2 q.x - |x|^2 - |q|^2`` (= -|q - x|^2); the oracle
    and the fused kernel use the identical formula so float rounding, and
    therefore ranking, agree everywhere.
    """
    dot = jnp.dot(queries, blk.T, precision=PRECISION)
    if metric == "dot":
        return dot
    if metric == "l2":
        return (2.0 * dot - jnp.sum(blk * blk, axis=-1)[None, :]
                - jnp.sum(queries * queries, axis=-1)[:, None])
    raise ValueError(f"metric must be one of {METRICS}, got {metric!r}")


def tree_merge_topk(vals, idx, *, axis_name: str, P: int, topk: int):
    """Recursive-doubling merge: after ceil(log2 P) ppermute rounds every
    device holds the global top-k.  Round r pulls the running list from
    device i + 2^r; windows overlap when P is not a power of two, which
    the index dedup in :func:`core.sweep.merge_topk` absorbs exactly."""
    tr = obs_trace.get_tracer()
    shift = 1
    while shift < P:
        perm = [(j, (j - shift) % P) for j in range(P)]
        if tr:  # per hop: the running (vals, idx) candidate payload
            tr.count("comm.ppermute.merge_hops")
            tr.count("comm.ppermute.merge_bytes",
                     obs_trace.nbytes_of(vals) + obs_trace.nbytes_of(idx))
        ov = lax.ppermute(vals, axis_name, perm)
        oi = lax.ppermute(idx, axis_name, perm)
        vals, idx = merge_topk(vals, idx, ov, oi, topk)
        shift *= 2
    return vals, idx


def _select_mode(schedule: PairSchedule, queries, block: int, batch_fn) -> str:
    """The query engine's ``mode="auto"`` working set fed to the shared
    heuristic (core/sweep.py select_mode): the [Q, k*block] score tensor
    (x2 for the sort copy)."""
    Q = queries.shape[0]
    itemsize = jnp.dtype(queries.dtype).itemsize
    return sweep_mod.select_mode(
        schedule, 2 * Q * schedule.k * block * itemsize, batch_fn)


def _query_geometry(schedule: PairSchedule, axis_name: str, block: int,
                    mask_row, stack_valid):
    """Shared per-device geometry of both query paths: global row ids
    [k, block] and the cover-dedup x validity mask [k, block]."""
    P = schedule.P
    i = lax.axis_index(axis_name)
    gblocks = (i + jnp.asarray(schedule.shifts, jnp.int32)) % P      # [k]
    gidx = gblocks[:, None] * block + jnp.arange(block, dtype=jnp.int32)
    mask = (mask_row[:, None] > 0) & stack_valid                     # [k, block]
    return gidx, mask


class QueryTopKEmitter(SweepEmitter):
    """Per-row top-k selection over the resident slot sweep (DESIGN.md
    sections 9.2, 12.2 — the serving top-k workload).

    Each slot's [Q, block] score tile is masked (cover dedup x row
    validity) and folded into a running [Q, topk] (value, index) list
    under the (-score, index) total order; the three modes fold
    differently (single sort / serial merge / tournament merge) but
    select identically.
    """

    def __init__(self, schedule: PairSchedule, queries, mask, gidx,
                 topk: int, metric: str, batch_fn=None):
        self.schedule = schedule
        self.queries = queries
        self.mask = mask
        self.gidx = gidx
        self.topk = topk
        self.metric = metric
        self.batch_fn = batch_fn

    def items(self):
        """Slot sweep: one work item per resident slot."""
        return slot_items(self.schedule.k)

    def batch(self, quorum):
        """One einsum over the whole stack + a single top-k over all
        k*block candidates (or the fused kernel via ``batch_fn``)."""
        k, block = quorum.shape[0], quorum.shape[1]
        if self.batch_fn is not None:
            return self.batch_fn(quorum, self.queries,
                                 self.mask.astype(jnp.float32), self.gidx)
        s = jnp.einsum("qd,sbd->qsb", self.queries, quorum,
                       precision=PRECISION)
        if self.metric == "l2":
            s = (2.0 * s - jnp.sum(quorum * quorum, axis=-1)[None]
                 - jnp.sum(self.queries * self.queries, axis=-1)[:, None, None])
        elif self.metric != "dot":
            raise ValueError(
                f"metric must be one of {METRICS}, got {self.metric!r}")
        s = jnp.where(self.mask[None], s, NEG_INF)
        Q = self.queries.shape[0]
        midx = jnp.where(self.mask, self.gidx, IDX_SENTINEL)
        flat_idx = jnp.broadcast_to(midx[None], (Q, k, block))
        return topk_by_score(s.reshape(Q, k * block),
                             flat_idx.reshape(Q, k * block), self.topk)

    def scan_init(self):
        """Sentinel-filled [Q, topk] running lists, varying like the
        per-device mask the body folds in."""
        Q = self.queries.shape[0]
        return varying_like(
            (jnp.full((Q, self.topk), NEG_INF, jnp.float32),
             jnp.full((Q, self.topk), IDX_SENTINEL, jnp.int32)),
            self.queries, self.mask, self.gidx)

    def scan_items(self):
        """(slot, mask row, global-id row) per resident slot."""
        k = self.schedule.k
        return (jnp.arange(k, dtype=jnp.int32), self.mask, self.gidx)

    def scan_emit(self, carry, quorum, item):
        """Merge one slot's masked scores into the running list."""
        cv, ci = carry
        slot, vrow, grow = item
        blk = jnp.take(quorum, slot, axis=0)
        Q, block = self.queries.shape[0], blk.shape[0]
        s = jnp.where(vrow[None], _scores(self.queries, blk, self.metric),
                      NEG_INF)
        g = jnp.broadcast_to(jnp.where(vrow, grow, IDX_SENTINEL)[None],
                             (Q, block))
        return merge_topk(cv, ci, s, g, self.topk)

    def overlap_begin(self):
        """The per-slot candidate lists the tournament merge folds."""
        return []

    def overlap_emit(self, lists, idx, bi, bj):
        """Select each slot's local top-k as its scores materialize."""
        Q, block = self.queries.shape[0], bi.shape[0]
        s = jnp.where(self.mask[idx][None],
                      _scores(self.queries, bi, self.metric), NEG_INF)
        g = jnp.broadcast_to(
            jnp.where(self.mask[idx], self.gidx[idx], IDX_SENTINEL)[None],
            (Q, block))
        lists.append(topk_by_score(s, g, self.topk))

    def overlap_finalize(self, lists):
        """Pairwise tournament merge: log2(k) depth instead of the scan
        mode's serial carry chain."""
        while len(lists) > 1:
            nxt = []
            for j in range(0, len(lists) - 1, 2):
                nxt.append(merge_topk(*lists[j], *lists[j + 1], self.topk))
            if len(lists) % 2:
                nxt.append(lists[-1])
            lists = nxt
        return lists[0]


def quorum_query_topk(
    queries: jax.Array,
    stack: jax.Array,
    stack_valid: jax.Array,
    mask_row: jax.Array,
    *,
    topk: int,
    axis_name: str,
    schedule: PairSchedule,
    mode: str = "auto",
    metric: str = "dot",
    batch_fn: Callable[..., Tuple[jax.Array, jax.Array]] | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Score a query microbatch against the corpus; global top-k per query.

    Must run inside shard_map over ``axis_name``.  Args (per device):
      queries     : [Q, d] replicated microbatch.
      stack       : [k, block, d] resident quorum stack (stream.py layout).
      stack_valid : [k, block] bool row validity for the stack.
      mask_row    : [k] this device's cover dedup mask row
                    (CoverPlan.mask_table, sharded; zero off-cover).
      batch_fn    : optional fused local step — called as
                    ``batch_fn(stack, queries, mask [k, block], gidx
                    [k, block]) -> (vals [Q, topk], idx [Q, topk])``
                    (kernels.ops.query_topk); implies ``batched``.

    Returns (scores [Q, topk], global corpus indices [Q, topk]); ties
    break toward smaller indices, missing candidates are (NEG_INF,
    IDX_SENTINEL).  Identical on every device after the tree merge.
    """
    sweep_mod.validate_mode(mode, batch_fn)
    k, block, d = stack.shape
    mask_row = mask_row.reshape(-1)  # accept [1, k] shard_map leftovers
    if mode == "auto":
        mode = _select_mode(schedule, queries, block, batch_fn)

    gidx, mask = _query_geometry(schedule, axis_name, block, mask_row,
                                 stack_valid)
    emitter = QueryTopKEmitter(schedule, queries, mask, gidx, topk, metric,
                               batch_fn=batch_fn)
    vals, idx = sweep_mod.pair_sweep(emitter, schedule=schedule,
                                     axis_name=axis_name, mode=mode,
                                     stack=stack)
    return tree_merge_topk(vals, idx, axis_name=axis_name, P=schedule.P,
                           topk=topk)


def _compact_rows(vbuf, ibuf, cnt, keep, vals, idx, capacity: int):
    """Append each query row's kept entries to its (vbuf, ibuf) prefix.

    keep/vals/idx: [Q, M] candidates; positions are per-row
    ``cnt + cumsum(keep) - 1`` and entries past ``capacity`` drop while
    ``cnt`` grows by the true kept total — the same overflow contract as
    the batch sparse engine (core/sparse.py, DESIGN.md section 11.2).
    """
    keep_i = keep.astype(jnp.int32)
    pos = cnt[:, None] + jnp.cumsum(keep_i, axis=1) - 1
    pos = jnp.where(keep, pos, capacity)
    rows = lax.broadcasted_iota(jnp.int32, pos.shape, 0)
    vbuf = vbuf.at[rows, pos].set(vals.astype(vbuf.dtype), mode="drop")
    ibuf = ibuf.at[rows, pos].set(idx.astype(jnp.int32), mode="drop")
    return vbuf, ibuf, cnt + jnp.sum(keep_i, axis=1)


def _select_threshold_mode(schedule: PairSchedule, queries,
                           block: int) -> str:
    """``mode="auto"`` for the thresholded query path — the same shared
    heuristic and working set as the top-k path, minus the
    (inapplicable) fused kernel arm."""
    return _select_mode(schedule, queries, block, None)


class QueryThresholdEmitter(SweepEmitter):
    """Per-query fixed-capacity threshold compaction over the resident
    slot sweep (DESIGN.md sections 11.4, 12.2 — the range-query
    workload).

    Each slot's passing (score, index) entries are cumsum-compacted into
    [Q, capacity] buffers under the overflow contract of DESIGN.md 11.2;
    the adapter appends the other devices' prefixes with a ppermute ring
    gather afterwards.
    """

    def __init__(self, schedule: PairSchedule, queries, mask, gidx,
                 thr, capacity: int, metric: str, axis_name: str):
        self.schedule = schedule
        self.queries = queries
        self.mask = mask
        self.gidx = gidx
        self.thr = thr
        self.capacity = capacity
        self.metric = metric
        self.axis_name = axis_name

    def items(self):
        """Slot sweep: one work item per resident slot."""
        return slot_items(self.schedule.k)

    def _init_bufs(self):
        """Sentinel-filled [Q, capacity] buffers + zero counts
        (varying-marked)."""
        Q = self.queries.shape[0]
        vbuf = mark_varying(jnp.full((Q, self.capacity), NEG_INF,
                                     jnp.float32), self.axis_name)
        ibuf = mark_varying(jnp.full((Q, self.capacity), IDX_SENTINEL,
                                     jnp.int32), self.axis_name)
        cnt = mark_varying(jnp.zeros((Q,), jnp.int32), self.axis_name)
        return vbuf, ibuf, cnt

    def batch(self, quorum):
        """One einsum over the whole stack + a single compaction."""
        k, block = quorum.shape[0], quorum.shape[1]
        Q = self.queries.shape[0]
        vbuf, ibuf, cnt = self._init_bufs()
        s = jnp.einsum("qd,sbd->qsb", self.queries, quorum,
                       precision=PRECISION)
        if self.metric == "l2":
            s = (2.0 * s - jnp.sum(quorum * quorum, axis=-1)[None]
                 - jnp.sum(self.queries * self.queries, axis=-1)[:, None, None])
        keep = (s >= self.thr[:, None, None]) & self.mask[None]
        return _compact_rows(
            vbuf, ibuf, cnt, keep.reshape(Q, k * block),
            s.reshape(Q, k * block),
            jnp.broadcast_to(self.gidx[None], (Q, k, block)
                             ).reshape(Q, k * block),
            self.capacity)

    def scan_init(self):
        """Empty per-query compaction buffers."""
        return self._init_bufs()

    def scan_items(self):
        """(slot, mask row, global-id row) per resident slot."""
        k = self.schedule.k
        return (jnp.arange(k, dtype=jnp.int32), self.mask, self.gidx)

    def scan_emit(self, carry, quorum, item):
        """Compact one slot's passing entries into the running buffers."""
        vb, ib, c = carry
        slot, mrow, grow = item
        blk = jnp.take(quorum, slot, axis=0)
        Q, block = self.queries.shape[0], blk.shape[0]
        s = _scores(self.queries, blk, self.metric)
        keep = (s >= self.thr[:, None]) & mrow[None]
        g = jnp.broadcast_to(grow[None], (Q, block))
        return _compact_rows(vb, ib, c, keep, s, g, self.capacity)

    def overlap_begin(self):
        """Per-slot (scores, keep, ids) lists for the single deferred
        compaction."""
        return {"s": [], "keep": [], "g": []}

    def overlap_emit(self, state, idx, bi, bj):
        """Score one slot as it lands; compaction is deferred so the
        slot scores stay independent for the scheduler."""
        Q, block = self.queries.shape[0], bi.shape[0]
        s = _scores(self.queries, bi, self.metric)
        state["s"].append(s)
        state["keep"].append((s >= self.thr[:, None]) & self.mask[idx][None])
        state["g"].append(jnp.broadcast_to(self.gidx[idx][None], (Q, block)))

    def overlap_finalize(self, state):
        """One compaction over every slot's concatenated candidates."""
        vbuf, ibuf, cnt = self._init_bufs()
        return _compact_rows(
            vbuf, ibuf, cnt, jnp.concatenate(state["keep"], axis=1),
            jnp.concatenate(state["s"], axis=1),
            jnp.concatenate(state["g"], axis=1), self.capacity)


def quorum_query_threshold(
    queries: jax.Array,
    stack: jax.Array,
    stack_valid: jax.Array,
    mask_row: jax.Array,
    *,
    threshold: jax.Array,
    capacity: int,
    axis_name: str,
    schedule: PairSchedule,
    mode: str = "auto",
    metric: str = "dot",
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Range query: every corpus row scoring >= threshold, per query.

    The sparse sibling of :func:`quorum_query_topk` (DESIGN.md section
    11.4): the same cover-routed local scoring under the dedup mask —
    each valid corpus row is scored by exactly one device — but instead
    of a top-k selection, passing rows are cumsum-compacted into
    fixed-capacity [Q, capacity] buffers, and a **ppermute ring gather**
    (P - 1 single-step shifts) appends every other device's passing
    prefix, so all devices end with the identical global result, sorted
    by ascending corpus index.

    Must run inside shard_map.  ``threshold`` is a traced f32 scalar or a
    per-query ``[Q]`` vector (one compiled program serves any threshold
    values at a given capacity — the per-query form is what lets the
    continuous batcher pack requests with different thresholds into one
    launch, DESIGN.md section 15.2).  Returns
    ``(scores [Q, capacity], indices [Q, capacity], count [Q])``; count
    is each query's TRUE passing total — ``count > capacity`` flags
    overflow (escalate per DESIGN.md 11.2; overflowing buffers keep a
    valid but device-order-dependent subset), and slots past
    ``min(count, capacity)`` hold (NEG_INF, IDX_SENTINEL) sentinels.
    """
    sweep_mod.validate_mode(mode, None)
    if metric not in METRICS:
        raise ValueError(f"metric must be one of {METRICS}, got {metric!r}")
    k, block, d = stack.shape
    Q = queries.shape[0]
    mask_row = mask_row.reshape(-1)
    if mode == "auto":
        mode = _select_threshold_mode(schedule, queries, block)

    P = schedule.P
    gidx, mask = _query_geometry(schedule, axis_name, block, mask_row,
                                 stack_valid)
    thr = jnp.broadcast_to(jnp.asarray(threshold, jnp.float32), (Q,))
    emitter = QueryThresholdEmitter(schedule, queries, mask, gidx, thr,
                                    capacity, metric, axis_name)
    vbuf, ibuf, cnt = sweep_mod.pair_sweep(emitter, schedule=schedule,
                                           axis_name=axis_name, mode=mode,
                                           stack=stack)

    # ppermute ring gather: append every other device's passing prefix
    tr = obs_trace.get_tracer()
    perm = [(j, (j + 1) % P) for j in range(P)]
    cur = (vbuf, ibuf, cnt)
    slot_iota = lax.broadcasted_iota(jnp.int32, (Q, capacity), 1)
    for _ in range(1, P):
        if tr:  # per hop: the three ring buffers (vals, idx, count)
            tr.count("comm.ppermute.ring_hops")
            tr.count("comm.ppermute.ring_bytes",
                     sum(obs_trace.nbytes_of(c) for c in cur))
        cur = tuple(lax.ppermute(c, axis_name, perm) for c in cur)
        rv, ri, rc = cur
        valid_in = slot_iota < jnp.minimum(rc, capacity)[:, None]
        vbuf, ibuf, _unclamped = _compact_rows(vbuf, ibuf, cnt, valid_in,
                                               rv, ri, capacity)
        cnt = cnt + rc        # true totals, not the clamped append

    # canonical order: ascending corpus index (sentinels sort last)
    ibuf, vbuf = lax.sort((ibuf, vbuf), num_keys=1)
    return vbuf, ibuf, cnt


@functools.lru_cache(maxsize=64)
def threshold_fn(mesh, axis_name: str, capacity: int, mode: str,
                 metric: str, placement: Placement | None = None):
    """Build (and cache) the jitted distributed range-query program.

    Returns ``f(queries [Q, d], threshold, state) -> (scores [Q,
    capacity], idx [Q, capacity], count [Q])`` — cached per capacity
    like :func:`query_fn`; the threshold (scalar or per-query ``[Q]``
    vector) is a traced operand, so one compiled program serves every
    threshold value (DESIGN.md 11.4).  Callers are expected to
    pre-quantize ``capacity`` through :func:`quantize_pow2` so the LRU
    holds one entry per power-of-two bucket, not one per observed
    capacity (DESIGN.md section 15.2).
    """
    P = mesh.shape[axis_name]
    if placement is None:
        placement = get_placement("cyclic", P)
    sched = placement.schedule()
    plan = build_cover(P, placement)
    mask_table = jnp.asarray(plan.mask_table())          # [P, k]

    def body(queries, thr, stack, stack_valid, mask_row):
        vals, idx, cnt = quorum_query_threshold(
            queries, stack, stack_valid, mask_row, threshold=thr,
            capacity=capacity, axis_name=axis_name, schedule=sched,
            mode=mode, metric=metric)
        return vals[None], idx[None], cnt[None]   # [1, ...] per device

    spec = PS(axis_name)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(PS(), PS(), spec, spec, spec),
        out_specs=(spec, spec, spec)))

    def run(queries, threshold, state: ServingState):
        thr = jnp.broadcast_to(jnp.asarray(threshold, jnp.float32),
                               (queries.shape[0],))
        vals, idx, cnt = fn(queries, thr, state.stack,
                            state.stack_valid, mask_table)
        return vals[0], idx[0], cnt[0]      # all device copies identical

    return run


@functools.lru_cache(maxsize=64)
def query_fn(mesh, axis_name: str, topk: int, mode: str, metric: str,
             use_kernel: bool, placement: Placement | None = None):
    """Build (and cache) the jitted distributed query program.

    Returns ``f(queries [Q, d], state) -> (scores [Q, topk], idx [Q,
    topk])`` — re-jits only per microbatch shape, like nbody.forces_fn.
    ``placement`` selects the residency layer (None = cyclic; pass a
    memoized Placement — it is part of the program cache key).  The
    serving data plane is the generic shift pipeline for every placement
    (full replication degenerates to a one-device cover over an
    everything-resident stack; no allgather special case needed).
    """
    P = mesh.shape[axis_name]
    if placement is None:
        placement = get_placement("cyclic", P)
    sched = placement.schedule()
    plan = build_cover(P, placement)
    mask_table = jnp.asarray(plan.mask_table())          # [P, k]
    from ..kernels import ops as kops
    batch_fn = None
    if use_kernel:
        if mode not in ("batched", "auto"):
            raise ValueError(
                f"use_kernel needs the batched mode (got mode={mode!r}); "
                "the fused kernel only replaces the batched local step")
        batch_fn = functools.partial(kops.query_topk, topk=topk,
                                     metric=metric)

    def body(queries, stack, stack_valid, mask_row):
        vals, idx = quorum_query_topk(
            queries, stack, stack_valid, mask_row, topk=topk,
            axis_name=axis_name, schedule=sched, mode=mode, metric=metric,
            batch_fn=batch_fn)
        return vals[None], idx[None]        # [1, Q, topk] per device

    spec = PS(axis_name)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(PS(), spec, spec, spec),
        out_specs=(spec, spec),
        check_vma=kops.shard_map_check_vma(use_kernel)))

    def run(queries, state: ServingState):
        vals, idx = fn(queries, state.stack, state.stack_valid, mask_table)
        return vals[0], idx[0]              # all device copies identical

    return run


class ServingCorpus:
    """Host-side handle: resident corpus state + cached query programs.

    >>> corpus = ServingCorpus.build(vectors, mesh)
    >>> scores, ids = corpus.query(q, topk=8)
    >>> corpus.replace_block(3, new_vectors)     # streamed, no reshuffle
    >>> corpus.append_block(more_vectors)        # lands in empty capacity
    """

    def __init__(self, mesh, axis_name: str, state: ServingState,
                 filled: np.ndarray, placement: Placement | None = None):
        self.mesh = mesh
        self.axis_name = axis_name
        self.state = state
        self.filled = filled                 # [P] valid-row count per block
        self.P = mesh.shape[axis_name]
        self.placement = (get_placement("cyclic", self.P)
                          if placement is None
                          else resolve_placement(placement, self.P))
        self.block = state.shard.shape[0] // self.P
        self.d = state.shard.shape[1]
        self.schedule = self.placement.schedule()
        self.plan = build_cover(self.P, self.placement)
        self.quant = None        # QuantServing when built with quant != off

    @classmethod
    def build(cls, corpus: np.ndarray, mesh, axis_name: str = "q",
              block: int | None = None, placement=None,
              quant: str | None = None) -> "ServingCorpus":
        """``block`` (optional) reserves a larger per-block row capacity
        than ceil(N/P), leaving empty slots for streamed appends.
        ``placement`` picks the residency layer (a Placement or spec
        name); None defers to ``REPRO_PLACEMENT`` (default auto ==
        cyclic).  ``quant`` additionally keeps a quantized resident
        stack (core/quant.py QuantServing; DESIGN.md section 17.4) the
        :meth:`query` path scores against with certified exact
        rescoring — ``"int8"``/``"bf16"`` enable it, ``"off"`` stays
        pure f32, None defers to ``REPRO_QUANT``."""
        P = mesh.shape[axis_name]
        plc = (placement_from_env(P) if placement is None
               else resolve_placement(placement, P))
        state = build_state(np.asarray(corpus, np.float32), mesh, axis_name,
                            block=block, placement=plc)
        block = state.shard.shape[0] // P
        N = corpus.shape[0]
        filled = np.clip(N - block * np.arange(P), 0, block).astype(np.int64)
        out = cls(mesh, axis_name, state, filled, placement=plc)
        from ..core.quant import QuantServing, quant_from_env
        qmode = quant_from_env() if quant is None else quant
        if qmode != "off":
            rows = np.zeros((P * block, corpus.shape[1]), np.float32)
            rows[:N] = np.asarray(corpus, np.float32)
            out.quant = QuantServing(qmode, mesh, axis_name, out.schedule,
                                     block, rows)
        return out

    @property
    def n_valid(self) -> int:
        """Total valid corpus rows across all blocks."""
        return int(self.filled.sum())

    def query(self, queries, *, topk: int, mode: str = "auto",
              metric: str = "dot", use_kernel: bool = False):
        """queries [Q, d] -> (scores [Q, topk], global row ids [Q, topk]).

        The compiled program is keyed on the power-of-two bucket
        ``quantize_pow2(topk)`` rather than the raw ``topk`` (DESIGN.md
        section 15.2) and the result is sliced back to ``topk`` columns
        — exact by the prefix property of the (-score, index) total
        order: the first k entries of a top-K list *are* the top-k list.
        Heterogeneous k therefore share one program per bucket.

        Each call is a ``serving.query`` host span around the dispatch
        (it does not wait for the device) and, with tracing on, a
        ``serving.queries`` counter (DESIGN.md section 14).

        A corpus built with ``quant != "off"`` scores against its
        quantized resident stack and rescores the certified candidates
        exactly (core/quant.py serving_query; DESIGN.md section 17.4) —
        bit-identical results; the fused f32 kernel does not apply
        there."""
        if topk < 1:
            raise ValueError(f"topk must be >= 1, got {topk}")
        if self.quant is not None:
            if use_kernel:
                raise ValueError(
                    "use_kernel applies to the f32 serving path only; "
                    "the quantized path has no fused kernel (rebuild "
                    "with quant='off' for kernel queries)")
            from ..core.quant import serving_query
            return serving_query(self, queries, topk=topk, mode=mode,
                                 metric=metric)
        kq = quantize_pow2(topk)
        run = query_fn(self.mesh, self.axis_name, kq, mode, metric,
                       use_kernel, self.placement)
        q = jnp.asarray(queries, jnp.float32)
        tr = obs_trace.get_tracer()
        with tr.span("serving.query", Q=int(q.shape[0]), topk=topk,
                     mode=mode, metric=metric, P=self.P):
            out = run(q, self.state)
        if tr:
            tr.count("serving.queries", int(q.shape[0]))
        if kq == topk:
            return out
        return out[0][:, :topk], out[1][:, :topk]

    def query_threshold(self, queries, *, threshold,
                        capacity: int | None = None, mode: str = "auto",
                        metric: str = "dot", escalate: bool = True,
                        max_doublings: int = 16):
        """Range query: every corpus row with score >= threshold, per query.

        queries [Q, d] -> ``(scores [Q, cap], global row ids [Q, cap],
        count [Q])``, each query's hits sorted by ascending corpus index
        with (NEG_INF, IDX_SENTINEL) sentinels past ``count``
        (:func:`quorum_query_threshold`, DESIGN.md section 11.4).
        ``threshold`` is a scalar or a per-query ``[Q]`` vector (the
        packed-batch form, DESIGN.md section 15.2).

        ``capacity`` defaults to the ``REPRO_SPARSE_CAPACITY``-aware
        heuristic; the *program* capacity ``cap`` is its
        :func:`quantize_pow2` bucket (clamped to the corpus size), so
        returned buffers may be wider than requested and the compiled
        programs stay on the power-of-two bucket ladder — escalation
        doubles along that same ladder instead of flooding the LRU with
        one ``threshold_fn`` entry per observed capacity (DESIGN.md
        sections 11.2, 15.2).  Under the overflow contract doubling
        continues until every query's true ``count`` fits (capped at
        the corpus size); with ``escalate=False`` the first pass
        returns as-is — ``count > cap`` then marks a truncated query.
        The compiled program is cached per capacity bucket, never per
        threshold.
        """
        total_rows = self.P * self.block
        cap_req = (int(capacity) if capacity is not None
                   else min(default_capacity(total_rows), total_rows))
        cap = min(quantize_pow2(cap_req), total_rows)
        q = jnp.asarray(queries, jnp.float32)
        escalations = 0
        tr = obs_trace.get_tracer()
        with tr.span("serving.query_threshold", Q=int(q.shape[0]),
                     mode=mode, metric=metric, P=self.P):
            while True:
                run = threshold_fn(self.mesh, self.axis_name, cap, mode,
                                   metric, self.placement)
                vals, idx, cnt = run(q, threshold, self.state)
                if not escalate:    # the caller reads the counts
                    break
                counts = np.asarray(cnt)
                if (not (counts > cap).any() or cap >= total_rows
                        or escalations >= max_doublings):
                    break
                cap = min(2 * cap, total_rows)
                escalations += 1
        if tr:
            tr.count("serving.queries", int(q.shape[0]))
            tr.count("serving.threshold_escalations", escalations)
        if escalate and (counts > cap).any():
            raise RuntimeError(
                f"thresholded query still overflows capacity {cap} after "
                f"{escalations} doublings; raise `capacity` or the "
                "threshold")
        return vals, idx, cnt

    def _check_block_data(self, data, what: str) -> np.ndarray:
        """Validate streamed block payloads at the handle layer: ``data``
        must be ``[rows, d]`` with ``rows <= block`` — the docstring
        contract of :meth:`replace_block`/:meth:`append_block` — so
        oversized or misshapen updates fail here with the block capacity
        in the message instead of deep inside ``stream.replace_block``
        (DESIGN.md section 9.4)."""
        arr = np.asarray(data, np.float32)
        if arr.ndim != 2 or arr.shape[1] != self.d:
            raise ValueError(
                f"{what} data must be a [rows, {self.d}] array (the "
                f"corpus embedding dim), got shape {arr.shape}")
        if arr.shape[0] > self.block:
            raise ValueError(
                f"{what} data has {arr.shape[0]} rows but the block "
                f"capacity is {self.block}; split the update or rebuild "
                "with a larger `block` (ServingCorpus.build)")
        return arr

    def replace_block(self, b: int, data, nvalid: int | None = None) -> None:
        """Replace block ``b`` in place (streamed to its k holder
        quorums).  ``data`` must be ``[rows <= block capacity, d]`` —
        validated here (DESIGN.md section 9.4)."""
        if not 0 <= b < self.P:
            raise ValueError(f"block id {b} out of range [0, {self.P})")
        data = self._check_block_data(data, f"replace_block({b})")
        self.state = replace_block(self.state, self.mesh, self.axis_name,
                                   b, data, nvalid,
                                   placement=self.placement)
        self.filled[b] = (data.shape[0] if nvalid is None else nvalid)
        if self.quant is not None:
            self.quant.update_block(b, data, int(self.filled[b]))

    def append_block(self, data) -> int:
        """Stream ``data`` (rows <= block capacity, validated at this
        layer) into the first empty block slot; returns the block id it
        landed in."""
        data = self._check_block_data(data, "append_block")
        empty = np.nonzero(self.filled == 0)[0]
        if empty.size == 0:
            raise ValueError(
                "corpus full: no empty block slot; grow the quorum axis "
                "(launch.elastic.rescale) to add capacity")
        b = int(empty[0])
        self.replace_block(b, data)
        return b
