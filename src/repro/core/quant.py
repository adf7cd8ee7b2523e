"""Quantized int8/bf16 scoring with error-bounded exact rescoring
(DESIGN.md section 17).

The all-pairs workloads in this repo score f32 row blocks.  This module
adds a *quantized working set*: each quorum block is stored int8 (per
block symmetric scale) or bf16, shrinking both the resident bytes per
device and the ppermute gather payload, while every workload still
returns **bit-exact f32 answers** via a certified error bound plus a
cheap host-side rescoring pass:

  * :func:`quantize_corpus` builds a :class:`QuantizedCorpus` — the
    quantized codes plus the per-block ``scale``/``delta`` and per-row
    ``l1``/``sq`` side arrays that travel with the codes as one
    :class:`QuantBlocks` pytree through ``quorum_gather`` /
    ``quorum_scatter`` (core/sweep.py's pytree data plane).
  * The quantized tile score obeys ``|score_q - score_f32| <=
    eps(i, j)`` with eps derived from the per-block deltas and row L1
    norms (kernels/ref.py quant_eps_tile; DESIGN.md section 17.2) —
    dot and (via the exact stored ``sq`` norms) l2.
  * :func:`quant_similarity_join` emits the widened band ``score_q >=
    threshold - eps`` on device and rescores every emitted pair in f32
    on the host; :func:`quant_knn_graph` and :func:`serving_query` keep
    quantized top-M lists, certify the k-th/M-th margin against the
    bound, double M until certified, and rescore the certified
    candidate set — all three match their f32 oracles bit-exactly.

``REPRO_QUANT`` (core/env.py) selects the mode (``off``/``int8``/
``bf16``) wherever a workload's ``quant=None`` default defers to the
environment (:func:`quant_from_env`).
"""

from __future__ import annotations

import dataclasses
import functools
import sys
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..kernels.ref import (FP_REL, IDX_SENTINEL, NEG_INF, PRECISION,
                           QUERY_METRICS, quant_eps_tile, varying_like)
from . import env as env_mod
from . import sweep as sweep_mod
from .knn import KNN_METRICS, KnnResult, _merge_lists
from .scheduler import PairSchedule
from .sparse import (JOIN_METRICS, JoinResult, MAX_ROWS_F32_EXACT,
                     SparseHits, _empty_bufs, _finalize, _pair_meta,
                     _scatter_hits, _tile_emit, default_capacity)
from .sweep import (ENGINE_MODES, SweepEmitter, mark_varying,
                    pair_mask_table, quorum_scatter)

__all__ = [
    "QUANT_DTYPES",
    "QuantBlocks",
    "QuantizedCorpus",
    "quant_from_env",
    "quantize_corpus",
    "quant_itemsize",
    "corpus_bytes_per_device",
    "eps_pairs",
    "eps_rows_upper",
    "eps_queries",
    "QuantThresholdEmitter",
    "QuantKnnEmitter",
    "quorum_allpairs_threshold_q",
    "quorum_allpairs_knn_q",
    "quant_similarity_join",
    "quant_knn_graph",
    "QuantServing",
    "serving_query",
]

#: the quantized storage modes (``REPRO_QUANT`` minus ``off``)
QUANT_DTYPES: Tuple[str, ...] = ("int8", "bf16")


class QuantBlocks(NamedTuple):
    """The per-device quantized working set as one pytree (DESIGN.md
    section 17.1) — the unit ``quorum_gather`` stacks leaf-wise, so the
    side arrays ride the same ppermute shifts as the codes.

    q     : [block, d] quantized codes (int8 or bfloat16)
    scale : [1] f32 per-block dequant scale (1.0 for bf16)
    delta : [1] f32 per-block worst-case elementwise error bound
    l1    : [block] f32 L1 norms of the ORIGINAL f32 rows
    sq    : [block] f32 exact squared L2 norms of the original rows
    """

    q: jax.Array
    scale: jax.Array
    delta: jax.Array
    l1: jax.Array
    sq: jax.Array


def quant_from_env() -> str:
    """The ``REPRO_QUANT`` knob value, defaulting to ``"off"`` (core/
    env.py registry; DESIGN.md section 17.5) — consulted by every
    workload whose ``quant=None`` argument defers to the environment."""
    val = env_mod.read_knob("REPRO_QUANT")
    return "off" if val is None else str(val)


def quant_itemsize(mode: str) -> int:
    """Bytes per stored element under a quant mode (DESIGN.md section
    17.1): 1 for int8, 2 for bf16, 4 for the f32 baseline (``off``)."""
    if mode == "int8":
        return 1
    if mode == "bf16":
        return 2
    if mode == "off":
        return 4
    raise ValueError(
        f"quant mode must be one of {('off',) + QUANT_DTYPES}, "
        f"got {mode!r}")


@dataclasses.dataclass(frozen=True)
class QuantizedCorpus:
    """Host-side quantized corpus (:func:`quantize_corpus`; DESIGN.md
    section 17.1).

    ``q`` is the [nblocks * block, d] quantized code matrix (int8, or
    bfloat16 via ml_dtypes), ``scale``/``delta`` the [nblocks] f32
    per-block dequant scales and elementwise error bounds, ``l1``/``sq``
    the [nblocks * block] f32 L1 norms and exact squared norms of the
    *original* rows; ``n_valid`` marks the trailing padding rows.
    """

    mode: str
    q: np.ndarray
    scale: np.ndarray
    delta: np.ndarray
    l1: np.ndarray
    sq: np.ndarray
    block: int
    n_valid: int

    def device_arrays(self):
        """The five leaves as jnp arrays in :class:`QuantBlocks` order
        (host [nblocks*block, ...] / [nblocks] shapes, ready for
        per-leaf ``PartitionSpec(axis)`` sharding)."""
        return (jnp.asarray(self.q), jnp.asarray(self.scale),
                jnp.asarray(self.delta), jnp.asarray(self.l1),
                jnp.asarray(self.sq))


def quantize_corpus(x: np.ndarray, nblocks: int, block: int,
                    mode: str) -> QuantizedCorpus:
    """Quantize a padded [nblocks * block, d] f32 matrix per block
    (DESIGN.md section 17.1).

    int8: symmetric per-block maxabs scaling — ``scale = maxabs / 127``,
    ``q = clip(rint(x / scale), -127, 127)``, worst-case elementwise
    error ``delta = scale / 2`` (round-to-nearest); all-zero blocks
    (corpus padding) get scale 1 and delta 0 so they never pollute the
    row-level bound maxima.  bf16: a dtype cast — ``scale = 1``,
    ``delta = maxabs * 2^-8`` (bfloat16's 8-bit mantissa step at the
    block's magnitude).  ``l1``/``sq`` are computed from the *original*
    f32 rows with the same reduction the f32 engines use, so the l2
    identity ``2 dot - |x|^2 - |y|^2`` stays exact up to the dot term.
    """
    if mode not in QUANT_DTYPES:
        raise ValueError(
            f"quant mode must be one of {QUANT_DTYPES}, got {mode!r}")
    x = np.asarray(x, np.float32)
    total, d = x.shape
    if total != nblocks * block:
        raise ValueError(
            f"expected [{nblocks * block}, d] padded rows, got {x.shape}")
    xb = x.reshape(nblocks, block, d)
    maxabs = np.abs(xb).max(axis=(1, 2)).astype(np.float32)   # [nblocks]
    if mode == "int8":
        scale = np.where(maxabs > 0, maxabs / 127.0, 1.0).astype(np.float32)
        q = np.clip(np.rint(xb / scale[:, None, None]), -127, 127)
        q = q.astype(np.int8).reshape(total, d)
        delta = np.where(maxabs > 0, scale / 2.0, 0.0).astype(np.float32)
    else:  # bf16 — the cast IS the quantizer (ml_dtypes via jnp)
        q = np.asarray(jnp.asarray(x).astype(jnp.bfloat16))
        scale = np.ones((nblocks,), np.float32)
        delta = (maxabs * np.float32(2.0 ** -8)).astype(np.float32)
    l1 = np.abs(x).sum(axis=1).astype(np.float32)
    sq = (x * x).sum(axis=1).astype(np.float32)
    return QuantizedCorpus(mode=mode, q=q, scale=scale, delta=delta,
                           l1=l1, sq=sq, block=block, n_valid=total)


def corpus_bytes_per_device(N: int, d: int, P: int, k: int,
                            mode: str) -> int:
    """Resident working-set bytes per device for an N x d corpus under
    P blocks with k resident slots (DESIGN.md section 17.1) — the
    formula ``benchmarks/bench_memory.py`` and BENCH_quant.json report.

    f32 (``off``): ``k * block * d * 4``.  Quantized: each resident
    block adds its code matrix plus the side arrays that ride the
    gather — ``k * (block * d * itemsize + 8 + 8 * block)`` (scale +
    delta f32 scalars, l1 + sq f32 rows).
    """
    block = -(-N // P)
    if mode == "off":
        return k * block * d * 4
    item = quant_itemsize(mode)
    return k * (block * d * item + 8 + 8 * block)


# ---------------------------------------------------------------------------
# Host-side certified error bounds (DESIGN.md section 17.2)
# ---------------------------------------------------------------------------

def _eps_terms(delta_r, l1_r, delta_c, l1_c, dim: int):
    # the shared scalar/vector eps body: quantization cross terms plus
    # the fp32 accumulation allowance (kernels/ref.py FP_REL)
    return (delta_r * l1_c + delta_c * l1_r
            + 3.0 * dim * delta_r * delta_c
            + FP_REL * (l1_r * l1_c + 1.0))


def eps_pairs(qc: QuantizedCorpus, ai: np.ndarray, aj: np.ndarray,
              metric: str) -> np.ndarray:
    """Per-pair certified bound ``|score_q(i, j) - score_f32(i, j)| <=
    eps`` for explicit global row-id vectors (DESIGN.md section 17.2) —
    the host-side twin of kernels/ref.py ``quant_eps_tile``; l2 doubles
    the dot bound (the norms are stored exactly)."""
    dim = qc.q.shape[1]
    bi = np.asarray(ai, np.int64) // qc.block
    bj = np.asarray(aj, np.int64) // qc.block
    eps = _eps_terms(qc.delta[bi].astype(np.float64), qc.l1[ai],
                     qc.delta[bj].astype(np.float64), qc.l1[aj], dim)
    return np.asarray(2.0 * eps if metric == "l2" else eps, np.float64)


def eps_rows_upper(qc: QuantizedCorpus, metric: str,
                   n: Optional[int] = None) -> np.ndarray:
    """Per-row upper bound over *any* partner row: ``|score_q(r, c) -
    score_f32(r, c)| <= eps_rows_upper[r]`` for every valid c
    (DESIGN.md section 17.2) — the k-NN certification margin.  Maxing
    ``delta``/``l1`` over all blocks is safe because all-zero padding
    blocks carry delta 0 and l1 0 (:func:`quantize_corpus`)."""
    n = qc.n_valid if n is None else int(n)
    dim = qc.q.shape[1]
    max_l1 = float(qc.l1[:n].max()) if n else 0.0
    max_delta = float(qc.delta.max())
    bi = np.arange(n, dtype=np.int64) // qc.block
    eps = _eps_terms(qc.delta[bi].astype(np.float64), qc.l1[:n],
                     np.float64(max_delta), np.float64(max_l1), dim)
    return np.asarray(2.0 * eps if metric == "l2" else eps, np.float64)


def eps_queries(qc: QuantizedCorpus, queries: np.ndarray,
                metric: str, n: Optional[int] = None) -> np.ndarray:
    """Per-query certified bound for f32 queries against the quantized
    corpus (DESIGN.md section 17.4): only the corpus side is quantized,
    so the bound drops the query-delta terms — ``max_delta * |q|_1 +
    FP_REL * (|q|_1 * max_l1 + 1)`` (l2 doubled)."""
    n = qc.n_valid if n is None else int(n)
    queries = np.asarray(queries, np.float32)
    max_l1 = float(qc.l1[:n].max()) if n else 0.0
    max_delta = float(qc.delta.max())
    l1_q = np.abs(queries).sum(axis=1).astype(np.float64)
    eps = max_delta * l1_q + FP_REL * (l1_q * max_l1 + 1.0)
    return np.asarray(2.0 * eps if metric == "l2" else eps, np.float64)


# ---------------------------------------------------------------------------
# Traced tile helpers (shared by all modes; DESIGN.md section 17.3)
# ---------------------------------------------------------------------------

def _q_scores_eps(fi, fj, s_lo, s_hi, d_lo, d_hi, l1_i, l1_j, sq_i, sq_j,
                  metric: str):
    # the single traced home of the quantized tile score + bound:
    # dequantized-dot in f32, exact stored norms for l2 (bit-parity with
    # kernels/ref.py pairwise_threshold_q / pairwise_topk_q)
    dots = jnp.dot(fi, fj.T, precision=PRECISION,
                   preferred_element_type=jnp.float32) \
        * (s_lo * s_hi)
    if metric == "l2":
        scores = (2.0 * dots - sq_j[None, :]) - sq_i[:, None]
    else:
        scores = dots
    eps = quant_eps_tile(d_lo, d_hi, l1_i, l1_j, dim=fi.shape[1],
                         metric=metric)
    return scores, eps


def _q_tile_take(quorum: QuantBlocks, lo_p, hi_p):
    # one pair's two quantized blocks + side rows out of the gathered
    # stack (traced slot indices — the scan mode's per-item gather)
    scale = quorum.scale.reshape(-1)
    delta = quorum.delta.reshape(-1)
    fi = jnp.take(quorum.q, lo_p, axis=0).astype(jnp.float32)
    fj = jnp.take(quorum.q, hi_p, axis=0).astype(jnp.float32)
    return (fi, fj, jnp.take(scale, lo_p), jnp.take(scale, hi_p),
            jnp.take(delta, lo_p), jnp.take(delta, hi_p),
            jnp.take(quorum.l1, lo_p, axis=0),
            jnp.take(quorum.l1, hi_p, axis=0),
            jnp.take(quorum.sq, lo_p, axis=0),
            jnp.take(quorum.sq, hi_p, axis=0))


def _q_tile_pair(bi: QuantBlocks, bj: QuantBlocks):
    # overlap mode hands per-slot QuantBlocks trees; normalize the
    # scalar leaves (shape () after slot indexing, (1,) on the shard)
    return (bi.q.astype(jnp.float32), bj.q.astype(jnp.float32),
            jnp.asarray(bi.scale).reshape(()),
            jnp.asarray(bj.scale).reshape(()),
            jnp.asarray(bi.delta).reshape(()),
            jnp.asarray(bj.delta).reshape(()),
            bi.l1, bj.l1, bi.sq, bj.sq)


def _q_tile_keep(scores, eps, thr, nv_lo, nv_hi, is_self):
    # the widened-band membership mask: emit everything the bound cannot
    # exclude; ownership rules identical to sparse._tile_keep
    r = lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    s = lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    keep = (scores >= thr - eps) & (r < nv_lo) & (s < nv_hi)
    return keep & jnp.where(is_self, r < s, True)


def _q_cand_planes(fi, fj, s_lo, s_hi, sq_i, sq_j, metric: str, active,
                   is_self, ga, gb, nv_lo, nv_hi, block_rows: int):
    # both orientations' masked quantized candidate planes for one tile
    # (the quantized twin of knn._item_candidates; exact stored norms)
    dots = jnp.dot(fi, fj.T, precision=PRECISION,
                   preferred_element_type=jnp.float32) \
        * (s_lo * s_hi)
    if metric == "l2":
        t_lo = (2.0 * dots - sq_j[None, :]) - sq_i[:, None]
        t_hi = (2.0 * dots - sq_i[:, None]) - sq_j[None, :]
    else:
        t_lo = t_hi = dots
    block = fi.shape[0]
    sent = jnp.int32(IDX_SENTINEL)
    r = lax.broadcasted_iota(jnp.int32, (block, block), 0)
    s = lax.broadcasted_iota(jnp.int32, (block, block), 1)
    keep = active & (s < nv_hi) & jnp.where(is_self, r != s, True)
    cv_l = jnp.where(keep, t_lo, NEG_INF)
    ci_l = jnp.where(keep, gb * block_rows + s, sent)
    keep_t = (active & jnp.logical_not(is_self) & (r < nv_lo)).T
    cv_h = jnp.where(keep_t, t_hi.T, NEG_INF)
    ci_h = jnp.where(keep_t, (ga * block_rows + r).T, sent)
    return cv_l, ci_l, cv_h, ci_h


# ---------------------------------------------------------------------------
# Emitters (DESIGN.md section 17.3)
# ---------------------------------------------------------------------------

class QuantThresholdEmitter(SweepEmitter):
    """Widened-band threshold compaction over quantized tiles (DESIGN.md
    section 17.3).

    Identical to sparse.ThresholdJoinEmitter except the tile score is
    the dequantized dot and the membership test is the certified band
    ``score_q >= threshold - eps`` — every true hit is provably inside
    the band, so the host's f32 rescoring pass recovers the exact join.
    No norm-bound prefilter: the band itself is the selectivity control
    (a pruned-but-true tile would break soundness).
    """

    def __init__(self, schedule: PairSchedule, mask, thr, capacity: int,
                 metric: str, block: int, axis_name: str, meta,
                 batch_fn=None):
        self.schedule = schedule
        self.mask = mask
        self.thr = thr
        self.capacity = capacity
        self.metric = metric
        self.block = block
        self.axis_name = axis_name
        self.lo, self.hi, self.ga, self.gb, self.nv_lo, self.nv_hi, \
            self.is_self = meta
        self.batch_fn = batch_fn
        self.active = self.mask > 0

    def batch(self, quorum: QuantBlocks):
        """One compaction over every tile — the batched jnp step IS the
        ref oracle (kernels/ref.py pairwise_threshold_q), with the fused
        Pallas kernel swapping in through ``batch_fn``."""
        meta = jnp.stack([self.active.astype(jnp.int32),
                          self.is_self.astype(jnp.int32),
                          self.ga, self.gb, self.nv_lo, self.nv_hi],
                         axis=1)                           # [n_pairs, 6]
        if self.batch_fn is not None:
            vals, ei, ej, count = self.batch_fn(quorum, self.lo, self.hi,
                                                meta)
        else:
            from ..kernels import ref as kref
            vals, ei, ej, count = kref.pairwise_threshold_q(
                quorum.q, quorum.scale.reshape(-1),
                quorum.delta.reshape(-1), quorum.l1, quorum.sq,
                self.lo, self.hi, meta, threshold=self.thr,
                capacity=self.capacity, block_rows=self.block,
                metric=self.metric)
        return SparseHits(vals=vals, i=ei, j=ej,
                          count=count.reshape(()).astype(jnp.int32))

    def scan_init(self):
        """Empty compaction buffers + zero true count (varying-marked)."""
        return (_empty_bufs(self.capacity, self.axis_name),
                mark_varying(jnp.int32(0), self.axis_name))

    def scan_items(self):
        """Per-pair (slots, active, self flag, block ids, valid counts)."""
        return (self.lo, self.hi, self.active, self.is_self, self.ga,
                self.gb, self.nv_lo, self.nv_hi)

    def scan_emit(self, carry, quorum: QuantBlocks, item):
        """Serial per-pair band compaction (``lax.cond`` skips masked
        tiles' compute, as in the f32 engine)."""
        bufs, count = carry
        lo_p, hi_p, act_p, self_p, ga_p, gb_p, nvl_p, nvh_p = item

        def compute(c):
            bufs_c, cnt = c
            parts = _q_tile_take(quorum, lo_p, hi_p)
            scores, eps = _q_scores_eps(*parts, self.metric)
            keep = _q_tile_keep(scores, eps, self.thr, nvl_p, nvh_p,
                                self_p)
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
        """Boxed (bufs, count) carry the unrolled sweep threads."""
        return {"carry": (_empty_bufs(self.capacity, self.axis_name),
                          mark_varying(jnp.int32(0), self.axis_name))}

    def overlap_emit(self, state, idx, bi: QuantBlocks, bj: QuantBlocks):
        """Band-compact one tile as soon as its later block lands."""
        act = self.mask[idx] > 0

        def compute(c, bi=bi, bj=bj, idx=idx):
            bufs_c, cnt = c
            scores, eps = _q_scores_eps(*_q_tile_pair(bi, bj), self.metric)
            keep = _q_tile_keep(scores, eps, self.thr, self.nv_lo[idx],
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


class QuantKnnEmitter(SweepEmitter):
    """Per-row quantized top-M selection over the scheduled pairs
    (DESIGN.md section 17.3) — knn.KnnEmitter with the dequantized tile
    score and exact stored norms; the host certifies the resulting
    lists against the row bounds and rescores the candidates exactly.
    """

    def __init__(self, schedule: PairSchedule, mask, topk: int, metric: str,
                 block: int, axis_name: str, meta, batch_fn=None):
        self.schedule = schedule
        self.mask = mask
        self.topk = topk
        self.metric = metric
        self.block = block
        self.axis_name = axis_name
        self.lo, self.hi, self.ga, self.gb, self.nv_lo, self.nv_hi, \
            self.is_self = meta
        self.batch_fn = batch_fn

    def batch(self, quorum: QuantBlocks):
        """Every tile in one batched accumulation — the batched jnp step
        IS the ref oracle (kernels/ref.py pairwise_topk_q), fused kernel
        via ``batch_fn``."""
        meta = jnp.stack([(self.mask > 0).astype(jnp.int32),
                          self.is_self.astype(jnp.int32),
                          self.ga, self.gb, self.nv_lo, self.nv_hi],
                         axis=1)                           # [n_pairs, 6]
        if self.batch_fn is not None:
            return self.batch_fn(quorum, self.lo, self.hi, meta)
        from ..kernels import ref as kref
        return kref.pairwise_topk_q(
            quorum.q, quorum.scale.reshape(-1), quorum.sq,
            self.lo, self.hi, meta, topk=self.topk,
            block_rows=self.block, metric=self.metric)

    def scan_init(self):
        """Sentinel-filled per-slot running lists (varying-marked)."""
        k = self.schedule.k
        shape = (k, self.block, self.topk)
        return (mark_varying(jnp.full(shape, NEG_INF, jnp.float32),
                             self.axis_name),
                mark_varying(jnp.full(shape, jnp.int32(IDX_SENTINEL)),
                             self.axis_name))

    def scan_items(self):
        """Per-pair (slots, mask, self flag, block ids, valid counts)."""
        return (self.lo, self.hi, self.mask, self.is_self, self.ga,
                self.gb, self.nv_lo, self.nv_hi)

    def scan_emit(self, carry, quorum: QuantBlocks, item):
        """Merge one quantized tile's two candidate planes into the
        running per-slot lists."""
        vals, idx = carry
        lo_p, hi_p, m_p, self_p, ga_p, gb_p, nvl_p, nvh_p = item
        fi, fj, s_lo, s_hi, _dl, _dh, _l1i, _l1j, sq_i, sq_j = \
            _q_tile_take(quorum, lo_p, hi_p)
        cv_l, ci_l, cv_h, ci_h = _q_cand_planes(
            fi, fj, s_lo, s_hi, sq_i, sq_j, self.metric, m_p > 0, self_p,
            ga_p, gb_p, nvl_p, nvh_p, self.block)
        mv, mi = _merge_lists(jnp.take(vals, lo_p, axis=0),
                              jnp.take(idx, lo_p, axis=0), cv_l, ci_l,
                              self.topk)
        vals = vals.at[lo_p].set(mv)
        idx = idx.at[lo_p].set(mi)
        mv2, mi2 = _merge_lists(jnp.take(vals, hi_p, axis=0),
                                jnp.take(idx, hi_p, axis=0), cv_h, ci_h,
                                self.topk)
        return (vals.at[hi_p].set(mv2), idx.at[hi_p].set(mi2))

    def overlap_begin(self):
        """Boxed per-slot running lists the unrolled sweep updates."""
        return {"carry": self.scan_init()}

    def overlap_emit(self, state, item_idx, bi: QuantBlocks,
                     bj: QuantBlocks):
        """Merge one quantized tile as soon as its later block lands."""
        lo_s = int(self.schedule.pair_slots[item_idx, 0])
        hi_s = int(self.schedule.pair_slots[item_idx, 1])
        vals, idx = state["carry"]
        fi, fj, s_lo, s_hi, _dl, _dh, _l1i, _l1j, sq_i, sq_j = \
            _q_tile_pair(bi, bj)
        cv_l, ci_l, cv_h, ci_h = _q_cand_planes(
            fi, fj, s_lo, s_hi, sq_i, sq_j, self.metric,
            self.mask[item_idx] > 0, self.is_self[item_idx],
            self.ga[item_idx], self.gb[item_idx], self.nv_lo[item_idx],
            self.nv_hi[item_idx], self.block)
        mv, mi = _merge_lists(vals[lo_s], idx[lo_s], cv_l, ci_l, self.topk)
        vals = vals.at[lo_s].set(mv)
        idx = idx.at[lo_s].set(mi)
        if lo_s != hi_s:  # self tile: one contribution, hi plane sentinel
            mv2, mi2 = _merge_lists(vals[hi_s], idx[hi_s], cv_h, ci_h,
                                    self.topk)
            vals = vals.at[hi_s].set(mv2)
            idx = idx.at[hi_s].set(mi2)
        state["carry"] = (vals, idx)

    def overlap_finalize(self, state):
        """The per-slot running lists, ready for the scatter merge."""
        return state["carry"]


# ---------------------------------------------------------------------------
# Mode selection + device-level entry points (DESIGN.md section 17.3)
# ---------------------------------------------------------------------------

def _gather_payload_bytes(block: int, d: int, mode: str) -> int:
    # per-shift ppermute payload of one QuantBlocks tree: codes + the
    # scale/delta scalars + the l1/sq rows (the obs/comm.py predictor
    # mirrors this formula for its quant accounting)
    return block * d * quant_itemsize(mode) + 8 + 8 * block


def _join_mode_q(schedule: PairSchedule, block: int, d: int, mode_q: str,
                 batch_fn) -> str:
    """The quantized join's ``mode="auto"`` working set fed to the
    shared heuristic (core/sweep.py select_mode; DESIGN.md section
    17.3): the f32 score+id planes per tile plus the smaller resident
    quantized stack."""
    return sweep_mod.select_mode(
        schedule,
        schedule.n_pairs * block * block * 12
        + schedule.k * _gather_payload_bytes(block, d, mode_q), batch_fn)


def _knn_mode_q(schedule: PairSchedule, block: int, d: int, mode_q: str,
                batch_fn) -> str:
    """The quantized k-NN ``mode="auto"`` working set (two f32/i32
    candidate planes per tile + the quantized stack; DESIGN.md section
    17.3)."""
    return sweep_mod.select_mode(
        schedule,
        schedule.n_pairs * block * block * 16
        + schedule.k * _gather_payload_bytes(block, d, mode_q), batch_fn)


def quorum_allpairs_threshold_q(
    qb: QuantBlocks,
    *,
    threshold,
    axis_name: str,
    capacity: int,
    schedule: PairSchedule,
    metric: str = "dot",
    mode: str = "auto",
    mask: jax.Array | None = None,
    n_valid: int | None = None,
    batch_fn: Callable[..., Tuple[jax.Array, ...]] | None = None,
) -> SparseHits:
    """Distributed widened-band threshold join over quantized blocks
    (DESIGN.md section 17.3).

    Must run inside shard_map with ``qb`` the local :class:`QuantBlocks`
    shard.  Emits every global pair whose *quantized* score clears the
    certified band ``threshold - eps(i, j)`` — a superset of the true
    join, resolved exactly by the host rescoring pass in
    :func:`quant_similarity_join`.  ``batch_fn(qb, lo, hi, meta) ->
    (vals, i, j, count)`` is the fused-kernel hook (batched mode only).
    """
    if metric not in JOIN_METRICS:
        raise ValueError(f"metric must be one of {JOIN_METRICS}, "
                         f"got {metric!r}")
    sweep_mod.validate_mode(mode, batch_fn)
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    block, d = qb.q.shape
    if mask is None:
        table = jnp.asarray(pair_mask_table(schedule))
        mask = jnp.take(table, lax.axis_index(axis_name), axis=0)
    mask = mask.reshape(-1)
    if mode == "auto":
        qmode = "int8" if qb.q.dtype == jnp.int8 else "bf16"
        mode = _join_mode_q(schedule, block, d, qmode, batch_fn)
    lo, hi, ga, gb, nv_lo, nv_hi, is_self, _gblocks, _nv = _pair_meta(
        schedule, axis_name, block, n_valid)
    emitter = QuantThresholdEmitter(
        schedule, mask, jnp.float32(threshold), capacity, metric, block,
        axis_name, (lo, hi, ga, gb, nv_lo, nv_hi, is_self),
        batch_fn=batch_fn)
    return sweep_mod.pair_sweep(emitter, schedule=schedule,
                                axis_name=axis_name, mode=mode, x=qb)


def quorum_allpairs_knn_q(
    qb: QuantBlocks,
    *,
    topk: int,
    axis_name: str,
    schedule: PairSchedule,
    metric: str = "dot",
    mode: str = "auto",
    mask: jax.Array | None = None,
    n_valid: int | None = None,
    batch_fn: Callable[..., Tuple[jax.Array, jax.Array]] | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Distributed quantized top-M candidate lists (DESIGN.md section
    17.3) — knn.quorum_allpairs_knn over a :class:`QuantBlocks` shard.

    Returns each valid local row's quantized top-``topk`` (scores,
    global ids); the host certifies the M-th margin against the row
    bounds and rescores (:func:`quant_knn_graph`).
    """
    if metric not in KNN_METRICS:
        raise ValueError(f"metric must be one of {KNN_METRICS}, "
                         f"got {metric!r}")
    if topk < 1:
        raise ValueError(f"topk must be >= 1, got {topk}")
    sweep_mod.validate_mode(mode, batch_fn)
    block, d = qb.q.shape
    if mask is None:
        table = jnp.asarray(pair_mask_table(schedule))
        mask = jnp.take(table, lax.axis_index(axis_name), axis=0)
    mask = mask.reshape(-1)
    if mode == "auto":
        qmode = "int8" if qb.q.dtype == jnp.int8 else "bf16"
        mode = _knn_mode_q(schedule, block, d, qmode, batch_fn)
    lo, hi, ga, gb, nv_lo, nv_hi, is_self, _gblocks, _nv = _pair_meta(
        schedule, axis_name, block, n_valid)
    emitter = QuantKnnEmitter(
        schedule, mask, topk, metric, block, axis_name,
        (lo, hi, ga, gb, nv_lo, nv_hi, is_self), batch_fn=batch_fn)
    vals, idx = sweep_mod.pair_sweep(emitter, schedule=schedule,
                                     axis_name=axis_name, mode=mode, x=qb)
    partials = [(vals[s], idx[s]) for s in range(schedule.k)]
    return quorum_scatter(
        partials, schedule, axis_name,
        reduce_fn=lambda a, b: _merge_lists(a[0], a[1], b[0], b[1], topk))


# ---------------------------------------------------------------------------
# Host drivers: quantize, sweep, certify, rescore (DESIGN.md section 17.4)
# ---------------------------------------------------------------------------

def _shard_quant(corpus: np.ndarray, P: int, mode: str):
    # pad to P blocks, quantize, return (qc, device_arrays, n2 host f32
    # squared norms of the padded matrix for rescoring)
    N, d = corpus.shape
    block = -(-N // P)
    x = np.zeros((P * block, d), np.float32)
    x[:N] = corpus
    qc = quantize_corpus(x, P, block, mode)
    n2 = (x * x).sum(axis=1).astype(np.float32)
    return qc, x, n2


def _kernel_sd(qb: QuantBlocks):
    # the [k, 2] (scale, delta) SMEM operand the fused kernels take
    return jnp.stack([qb.scale.reshape(-1), qb.delta.reshape(-1)], axis=1)


@functools.lru_cache(maxsize=64)
def _qjoin_fn(mesh, axis_name: str, N: int, block: int, threshold: float,
              metric: str, mode: str, capacity: int, use_kernel: bool,
              placement, qmode: str):
    """Build (and cache) the jitted quantized band-join program — one
    trace per (mesh, shape, threshold, capacity, quant mode, ...) key
    (DESIGN.md section 17.4)."""
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

        def batch_fn(qb, lo, hi, meta):
            return kops.pairwise_threshold_q(
                qb.q, _kernel_sd(qb), qb.l1, qb.sq, lo, hi, meta,
                threshold=threshold, capacity=capacity, block_rows=block,
                metric=metric)

    def body(qarr, sarr, darr, l1arr, sqarr, mb):
        qb = QuantBlocks(q=qarr, scale=sarr, delta=darr, l1=l1arr,
                         sq=sqarr)
        hits = quorum_allpairs_threshold_q(
            qb, threshold=threshold, axis_name=axis_name,
            capacity=capacity, schedule=sched, metric=metric, mode=mode,
            mask=mb, n_valid=N, batch_fn=batch_fn)
        return hits.vals, hits.i, hits.j, hits.count.reshape(1)

    spec = PS(axis_name)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec,) * 6,
        out_specs=(spec, spec, spec, spec),
        check_vma=kops.shard_map_check_vma(use_kernel)))
    return lambda leaves: fn(*leaves, mask_table)


@functools.lru_cache(maxsize=64)
def _qknn_fn(mesh, axis_name: str, N: int, block: int, topk: int,
             metric: str, mode: str, use_kernel: bool, placement,
             qmode: str):
    """Build (and cache) the jitted quantized top-M program (DESIGN.md
    section 17.4)."""
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

        def batch_fn(qb, lo, hi, meta):
            return kops.pairwise_topk_q(
                qb.q, _kernel_sd(qb), qb.sq, lo, hi, meta, topk=topk,
                block_rows=block, metric=metric)

    def body(qarr, sarr, darr, l1arr, sqarr, mb):
        qb = QuantBlocks(q=qarr, scale=sarr, delta=darr, l1=l1arr,
                         sq=sqarr)
        return quorum_allpairs_knn_q(
            qb, topk=topk, axis_name=axis_name, schedule=sched,
            metric=metric, mode=mode, mask=mb, n_valid=N,
            batch_fn=batch_fn)

    spec = PS(axis_name)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec,) * 6, out_specs=(spec, spec),
        check_vma=kops.shard_map_check_vma(use_kernel)))
    return lambda leaves: fn(*leaves, mask_table)


def quant_similarity_join(corpus, mesh, *, threshold: float, quant: str,
                          axis_name: str = "q", metric: str = "dot",
                          mode: str = "auto", placement=None,
                          capacity: int | None = None,
                          use_kernel: bool = False, escalate: bool = True,
                          max_doublings: int = 16,
                          stats: dict | None = None) -> JoinResult:
    """Exact similarity join through the quantized band + f32 rescoring
    pipeline (DESIGN.md section 17.4).

    Devices emit the certified band ``score_q >= threshold - eps`` over
    the quantized working set (under the standard capacity/overflow
    escalation contract — counts are *band* counts); the host rescores
    every emitted pair against the f32 corpus and keeps ``score_f32 >=
    threshold``.  The result is bit-identical to
    :func:`core.sparse.similarity_join` (same scores, same (i, j)
    lexsort order).  ``stats`` (optional dict) is filled with the band
    accounting: ``emitted``, ``kept``, ``certain`` (pairs the bound
    alone already proves in), ``borderline``, ``escalations``.
    """
    if quant not in QUANT_DTYPES:
        raise ValueError(
            f"quant must be one of {QUANT_DTYPES}, got {quant!r}")
    corpus = np.asarray(corpus, np.float32)
    N, d = corpus.shape
    if N >= MAX_ROWS_F32_EXACT:
        raise ValueError(
            f"corpus has {N} rows >= 2^24; global row ids would lose "
            "float32 exactness in the fused kernel's compaction")
    P = mesh.shape[axis_name]
    from .placement import placement_from_env, resolve_placement
    plc = (placement_from_env(P) if placement is None
           else resolve_placement(placement, P))
    block = -(-N // P)
    qc, x, n2 = _shard_quant(corpus, P, quant)
    leaves = qc.device_arrays()
    sched = plc.schedule()
    n_cand = sched.n_pairs * block * block
    cap = int(capacity) if capacity is not None else default_capacity(n_cand)

    escalations = 0
    while True:
        run = _qjoin_fn(mesh, axis_name, N, block, float(threshold),
                        metric, mode, cap, use_kernel, plc, quant)
        vals, gi, gj, counts = (np.asarray(a) for a in run(leaves))
        counts = counts.reshape(-1)
        overflow = bool((counts > cap).any())
        if not overflow or not escalate or escalations >= max_doublings:
            break
        cap = 2 * cap
        escalations += 1
    if overflow and escalate:
        raise RuntimeError(
            f"quantized band join still overflows capacity {cap} after "
            f"{escalations} doublings; raise `capacity`/`max_doublings` "
            "or the threshold")

    vals = vals.reshape(P, -1)
    gi = gi.reshape(P, -1)
    gj = gj.reshape(P, -1)
    keep_i, keep_j, keep_v = [], [], []
    for dev in range(P):
        n = min(int(counts[dev]), cap)
        keep_i.append(gi[dev, :n])
        keep_j.append(gj[dev, :n])
        keep_v.append(vals[dev, :n])
    ai = np.concatenate(keep_i)
    aj = np.concatenate(keep_j)
    band_v = np.concatenate(keep_v)

    # f32 rescoring: the exact score of every band pair, with the same
    # reduction order as the brute-force oracle's row gathers
    dots = np.einsum("nd,nd->n", x[ai], x[aj]).astype(np.float32)
    if metric == "l2":
        rescored = (2.0 * dots - n2[aj]) - n2[ai]
    else:
        rescored = dots
    keep = rescored >= np.float32(threshold)
    if stats is not None:
        eps = eps_pairs(qc, ai, aj, metric)
        certain = band_v.astype(np.float64) >= float(threshold) + eps
        stats.update(
            emitted=int(ai.shape[0]), kept=int(keep.sum()),
            certain=int((certain & keep).sum()),
            borderline=int(ai.shape[0]) - int((certain & keep).sum()),
            escalations=escalations)
    ai, aj, av = ai[keep], aj[keep], rescored[keep]
    order = np.lexsort((aj, ai))
    return JoinResult(i=ai[order], j=aj[order], scores=av[order],
                      counts=counts, capacity=cap, escalations=escalations,
                      overflow=overflow)


def quant_knn_graph(corpus, mesh, *, topk: int, quant: str,
                    axis_name: str = "q", metric: str = "dot",
                    mode: str = "auto", placement=None,
                    use_kernel: bool = False) -> KnnResult:
    """Exact k-NN graph through quantized top-M candidate generation +
    certified rescoring (DESIGN.md section 17.4).

    Runs the quantized sweep for each row's top-M (M starts at the
    power-of-two bucket of ``topk``), then certifies per row: the list
    is complete (sentinel tail or M covers the corpus) **or** the f32
    k-th rescored candidate beats the quantized M-th score plus the
    row's certified bound — no row outside the list can enter the true
    top-k.  Uncertified rows double M and rerun (terminating at M >=
    N - 1, where the list is exhaustive).  Returns a
    :class:`core.knn.KnnResult` bit-identical to
    :func:`core.knn.knn_graph`.
    """
    if quant not in QUANT_DTYPES:
        raise ValueError(
            f"quant must be one of {QUANT_DTYPES}, got {quant!r}")
    if topk < 1:
        raise ValueError(f"topk must be >= 1, got {topk}")
    from ..serving.engine import quantize_pow2
    corpus = np.asarray(corpus, np.float32)
    N, d = corpus.shape
    P = mesh.shape[axis_name]
    from .placement import placement_from_env, resolve_placement
    plc = (placement_from_env(P) if placement is None
           else resolve_placement(placement, P))
    block = -(-N // P)
    qc, x, n2 = _shard_quant(corpus, P, quant)
    leaves = qc.device_arrays()
    eps_row = eps_rows_upper(qc, metric, N)
    total = P * block

    out_v = np.full((N, topk), NEG_INF, np.float32)
    out_i = np.full((N, topk), IDX_SENTINEL, np.int64)
    M = quantize_pow2(topk)
    pending = np.ones((N,), bool)
    while True:
        run = _qknn_fn(mesh, axis_name, N, block, int(M), metric, mode,
                       use_kernel, plc, quant)
        vals_q, idx_q = (np.asarray(a) for a in run(leaves))
        vals_q, idx_q = vals_q[:N], idx_q[:N]
        newly = []
        for r in np.nonzero(pending)[0]:
            cand = idx_q[r][idx_q[r] != IDX_SENTINEL].astype(np.int64)
            complete = (cand.shape[0] < M) or (M >= N - 1)
            dots = (x[cand] @ x[r]).astype(np.float32)
            if metric == "l2":
                s = (2.0 * dots - n2[r]) - n2[cand]
            else:
                s = dots
            order = np.lexsort((cand, -s.astype(np.float64)))
            kth = (float(s[order[min(topk, len(order)) - 1]])
                   if len(order) else NEG_INF)
            c_M = float(vals_q[r, M - 1]) if M <= vals_q.shape[1] else \
                NEG_INF
            certified = complete or (
                len(order) >= topk
                and kth > c_M + float(eps_row[r]))
            if certified:
                take = order[:topk]
                out_v[r, :len(take)] = s[take]
                out_i[r, :len(take)] = cand[take]
                newly.append(r)
        pending[np.asarray(newly, np.int64)] = False
        if not pending.any():
            break
        M = min(quantize_pow2(2 * M), quantize_pow2(total))
    return KnnResult(indices=out_i, scores=out_v, topk=int(topk))


# ---------------------------------------------------------------------------
# Serving path: quantized resident stack + certified query top-k
# (DESIGN.md section 17.4)
# ---------------------------------------------------------------------------

class QuantQueryEmitter(SweepEmitter):
    """Per-query quantized top-M over the resident quantized stack
    (DESIGN.md section 17.4) — serving.engine.QueryTopKEmitter with the
    dequantized slot score; the host certifies the M-th margin against
    :func:`eps_queries` and rescores against its f32 mirror.
    """

    def __init__(self, schedule: PairSchedule, queries, mask, gidx,
                 topk: int, metric: str):
        self.schedule = schedule
        self.queries = queries
        self.mask = mask
        self.gidx = gidx
        self.topk = topk
        self.metric = metric

    def items(self):
        """Slot sweep: one work item per resident slot."""
        from .sweep import slot_items
        return slot_items(self.schedule.k)

    def _slot_scores(self, fq, scale, sq):
        # [Q, block] dequantized scores of one slot (exact stored norms)
        qn = self.queries
        s = (jnp.dot(qn, fq.T, precision=PRECISION)
             * jnp.asarray(scale).reshape(()))
        if self.metric == "l2":
            s = ((2.0 * s - sq[None, :])
                 - jnp.sum(qn * qn, axis=-1)[:, None])
        elif self.metric != "dot":
            raise ValueError(
                f"metric must be one of {QUERY_METRICS}, "
                f"got {self.metric!r}")
        return s

    def batch(self, quorum: QuantBlocks):
        """One einsum over the whole quantized stack + a single top-M
        over all k*block candidates."""
        from .sweep import topk_by_score
        fq = quorum.q.astype(jnp.float32)
        k, block = fq.shape[0], fq.shape[1]
        s = jnp.einsum("qd,sbd->qsb", self.queries, fq,
                       precision=PRECISION) \
            * quorum.scale.reshape(-1)[None, :, None]
        if self.metric == "l2":
            s = ((2.0 * s - quorum.sq[None])
                 - jnp.sum(self.queries * self.queries,
                           axis=-1)[:, None, None])
        elif self.metric != "dot":
            raise ValueError(
                f"metric must be one of {QUERY_METRICS}, "
                f"got {self.metric!r}")
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

    def scan_emit(self, carry, quorum: QuantBlocks, item):
        """Merge one slot's masked dequantized scores into the list."""
        from .sweep import merge_topk
        cv, ci = carry
        slot, vrow, grow = item
        fq = jnp.take(quorum.q, slot, axis=0).astype(jnp.float32)
        s = self._slot_scores(fq, jnp.take(quorum.scale.reshape(-1), slot),
                              jnp.take(quorum.sq, slot, axis=0))
        Q, block = self.queries.shape[0], fq.shape[0]
        s = jnp.where(vrow[None], s, NEG_INF)
        g = jnp.broadcast_to(jnp.where(vrow, grow, IDX_SENTINEL)[None],
                             (Q, block))
        return merge_topk(cv, ci, s, g, self.topk)

    def overlap_begin(self):
        """The per-slot candidate lists the tournament merge folds."""
        return []

    def overlap_emit(self, lists, idx, bi: QuantBlocks, bj: QuantBlocks):
        """Select each slot's local top-M as its scores materialize."""
        from .sweep import topk_by_score
        fq = bi.q.astype(jnp.float32)
        Q, block = self.queries.shape[0], fq.shape[0]
        s = self._slot_scores(fq, bi.scale, bi.sq)
        s = jnp.where(self.mask[idx][None], s, NEG_INF)
        g = jnp.broadcast_to(
            jnp.where(self.mask[idx], self.gidx[idx], IDX_SENTINEL)[None],
            (Q, block))
        lists.append(topk_by_score(s, g, self.topk))

    def overlap_finalize(self, lists):
        """Pairwise tournament merge (log2 k depth)."""
        from .sweep import merge_topk
        while len(lists) > 1:
            nxt = []
            for j in range(0, len(lists) - 1, 2):
                nxt.append(merge_topk(*lists[j], *lists[j + 1], self.topk))
            if len(lists) % 2:
                nxt.append(lists[-1])
            lists = nxt
        return lists[0]


def quorum_query_topk_q(queries, qstack: QuantBlocks, stack_valid,
                        mask_row, *, topk: int, axis_name: str,
                        schedule: PairSchedule, mode: str = "auto",
                        metric: str = "dot"):
    """Quantized query top-M over the resident stack (DESIGN.md section
    17.4) — serving.engine.quorum_query_topk with a :class:`QuantBlocks`
    stack.  Must run inside shard_map; returns per-query quantized
    (scores [Q, M], global ids [Q, M]) identical on every device."""
    from ..serving.engine import _query_geometry, tree_merge_topk
    sweep_mod.validate_mode(mode, None)
    k, block, d = qstack.q.shape
    mask_row = mask_row.reshape(-1)
    if mode == "auto":
        Q = queries.shape[0]
        qmode = "int8" if qstack.q.dtype == jnp.int8 else "bf16"
        mode = sweep_mod.select_mode(
            schedule,
            2 * Q * k * block * 4
            + k * _gather_payload_bytes(block, d, qmode), None)
    gidx, mask = _query_geometry(schedule, axis_name, block, mask_row,
                                 stack_valid)
    emitter = QuantQueryEmitter(schedule, queries, mask, gidx, topk,
                                metric)
    vals, idx = sweep_mod.pair_sweep(emitter, schedule=schedule,
                                     axis_name=axis_name, mode=mode,
                                     stack=qstack)
    return tree_merge_topk(vals, idx, axis_name=axis_name, P=schedule.P,
                           topk=topk)


@functools.lru_cache(maxsize=64)
def _query_q_fn(mesh, axis_name: str, topk: int, mode: str, metric: str,
                placement, qmode: str):
    """Build (and cache) the jitted quantized serving query program
    (DESIGN.md section 17.4) — keyed per (mesh, top-M bucket, mode,
    metric, placement, quant mode) like serving.engine.query_fn."""
    from jax.sharding import PartitionSpec as PS
    from ..serving.cover import build_cover
    P = mesh.shape[axis_name]
    sched = placement.schedule()
    plan = build_cover(P, placement)
    mask_table = jnp.asarray(plan.mask_table())          # [P, k]

    def body(queries, qarr, sarr, darr, l1arr, sqarr, stack_valid,
             mask_row):
        qb = QuantBlocks(q=qarr, scale=sarr, delta=darr, l1=l1arr,
                         sq=sqarr)
        vals, idx = quorum_query_topk_q(
            queries, qb, stack_valid, mask_row, topk=topk,
            axis_name=axis_name, schedule=sched, mode=mode, metric=metric)
        return vals[None], idx[None]        # [1, Q, M] per device

    spec = PS(axis_name)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(PS(),) + (spec,) * 7,
        out_specs=(spec, spec)))

    def run(queries, stacks, stack_valid):
        vals, idx = fn(queries, *stacks, stack_valid, mask_table)
        return vals[0], idx[0]              # all device copies identical

    return run


class QuantServing:
    """The quantized resident state of a serving corpus (DESIGN.md
    section 17.4) — owned by ``serving.engine.ServingCorpus`` when it
    is built with ``quant != "off"``.

    Keeps a [P * block, d] f32 host mirror of the corpus (the exact
    rescoring source), the :class:`QuantizedCorpus` built from it, and
    the device-resident quantized stacks in the streaming layout
    (device-major: device i's slot s holds block ``(i + shifts[s]) %
    P``).  Streamed block updates re-quantize and rebuild the stacks
    from the mirror — the harness simplification this PR documents; a
    per-block ppermute delta path would reuse stream.replace_block.
    """

    def __init__(self, mode: str, mesh, axis_name: str,
                 schedule: PairSchedule, block: int, rows: np.ndarray):
        if mode not in QUANT_DTYPES:
            raise ValueError(
                f"quant must be one of {QUANT_DTYPES}, got {mode!r}")
        self.mode = mode
        self.mesh = mesh
        self.axis_name = axis_name
        self.schedule = schedule
        self.block = block
        self.P = schedule.P
        self.rows = np.asarray(rows, np.float32)          # [P * block, d]
        self.n2 = (self.rows * self.rows).sum(axis=1).astype(np.float32)
        self._requant()

    def _requant(self) -> None:
        # rebuild the quantized corpus + the device-major slot stacks
        P, k = self.P, self.schedule.k
        self.qc = quantize_corpus(self.rows, P, self.block, self.mode)
        order = np.asarray(
            [(i + int(s)) % P for i in range(P)
             for s in self.schedule.shifts], np.int64)    # [P * k]
        qb = self.qc.q.reshape(P, self.block, -1)
        rows_of = order[:, None] * self.block + np.arange(self.block)
        self.stacks = (
            jnp.asarray(qb[order].reshape(P * k, self.block, -1)),
            jnp.asarray(self.qc.scale[order]),
            jnp.asarray(self.qc.delta[order]),
            jnp.asarray(self.qc.l1[rows_of].reshape(P * k, self.block)),
            jnp.asarray(self.qc.sq[rows_of].reshape(P * k, self.block)))

    def update_block(self, b: int, data: np.ndarray, nvalid: int) -> None:
        """Apply a streamed block replace to the mirror and re-quantize
        (full rebuild; DESIGN.md section 17.4)."""
        blk = np.zeros((self.block, self.rows.shape[1]), np.float32)
        blk[:data.shape[0]] = data
        blk[nvalid:] = 0.0
        self.rows[b * self.block:(b + 1) * self.block] = blk
        self.n2 = (self.rows * self.rows).sum(axis=1).astype(np.float32)
        self._requant()


def serving_query(corpus, queries, *, topk: int, mode: str = "auto",
                  metric: str = "dot"):
    """Exact serving top-k through the quantized stack + certified
    rescoring (DESIGN.md section 17.4).

    ``corpus`` is a ``serving.engine.ServingCorpus`` whose ``quant``
    attribute holds a :class:`QuantServing`.  Runs the quantized device
    top-M (M the power-of-two bucket of ``topk``), rescores each
    query's candidates against the f32 host mirror, and certifies: the
    candidate list is exhaustive, or the f32 k-th rescored score beats
    the quantized M-th score plus :func:`eps_queries` — otherwise M
    doubles and the device pass reruns.  Returns (scores [Q, topk],
    global row ids [Q, topk]) bit-identical to the f32
    ``ServingCorpus.query`` path.
    """
    from ..serving.engine import quantize_pow2
    qs = corpus.quant
    if qs is None:
        raise ValueError(
            "serving_query needs a quantized corpus (ServingCorpus.build "
            "with quant='int8'/'bf16'); use ServingCorpus.query for f32")
    if topk < 1:
        raise ValueError(f"topk must be >= 1, got {topk}")
    q = np.asarray(queries, np.float32)
    Q = q.shape[0]
    total = qs.P * qs.block
    valid = np.zeros((total,), bool)
    for b in range(qs.P):
        valid[b * qs.block: b * qs.block + int(corpus.filled[b])] = True
    n_valid_rows = int(valid.sum())
    eps_q = eps_queries(qs.qc, q, metric, total)

    out_v = np.full((Q, topk), NEG_INF, np.float32)
    out_i = np.full((Q, topk), IDX_SENTINEL, np.int64)
    M = quantize_pow2(topk)
    pending = np.ones((Q,), bool)
    qj = jnp.asarray(q)
    while True:
        run = _query_q_fn(corpus.mesh, corpus.axis_name, int(M), mode,
                          metric, corpus.placement, qs.mode)
        vals_q, idx_q = (np.asarray(a)
                         for a in run(qj, qs.stacks,
                                      corpus.state.stack_valid))
        newly = []
        for qi in np.nonzero(pending)[0]:
            cand = idx_q[qi][idx_q[qi] != IDX_SENTINEL].astype(np.int64)
            complete = (cand.shape[0] < M) or (M >= n_valid_rows)
            dots = (qs.rows[cand] @ q[qi]).astype(np.float32)
            if metric == "l2":
                s = ((2.0 * dots - qs.n2[cand])
                     - np.float32((q[qi] * q[qi]).sum()))
            else:
                s = dots
            order = np.lexsort((cand, -s.astype(np.float64)))
            kth = (float(s[order[min(topk, len(order)) - 1]])
                   if len(order) else NEG_INF)
            c_M = float(vals_q[qi, M - 1])
            certified = complete or (
                len(order) >= topk and kth > c_M + float(eps_q[qi]))
            if certified:
                take = order[:topk]
                out_v[qi, :len(take)] = s[take]
                out_i[qi, :len(take)] = cand[take]
                newly.append(qi)
        pending[np.asarray(newly, np.int64)] = False
        if not pending.any():
            break
        M = min(quantize_pow2(2 * M), quantize_pow2(total))
    return out_v, out_i


# ---------------------------------------------------------------------------
# Selfcheck (DESIGN.md section 17.6)
# ---------------------------------------------------------------------------

def _serving_topk_oracle(rows: np.ndarray, valid: np.ndarray,
                         queries: np.ndarray, topk: int, metric: str):
    # host f32 serving oracle: full scores, invalid rows masked, exact
    # (-score, index) selection with sentinel padding
    s = (queries @ rows.T).astype(np.float32)
    if metric == "l2":
        n2 = (rows * rows).sum(axis=1).astype(np.float32)
        qn2 = (queries * queries).sum(axis=1).astype(np.float32)
        s = 2.0 * s - n2[None, :] - qn2[:, None]
    s = np.where(valid[None, :], s, NEG_INF)
    Q, total = s.shape
    out_v = np.full((Q, topk), NEG_INF, np.float32)
    out_i = np.full((Q, topk), IDX_SENTINEL, np.int64)
    for qi in range(Q):
        cand = np.nonzero(valid)[0]
        order = np.lexsort((cand, -s[qi, cand].astype(np.float64)))
        take = order[:topk]
        out_v[qi, :len(take)] = s[qi, cand[take]]
        out_i[qi, :len(take)] = cand[take]
    return out_v, out_i


def selfcheck_main(nblocks: int | None = None, modes=None,
                   placement=None) -> None:
    """Exactness selfcheck of the whole quantized pipeline (DESIGN.md
    section 17.6): for each quant mode x metric, the rescored join,
    k-NN graph, and serving query must be **bit-identical** to the f32
    oracles across every execution mode (plus the fused-kernel batched
    path), including after a streamed block replace on the serving
    side.  ``REPRO_QUANT`` (when set to a non-off mode) restricts the
    swept quant modes — the CI placement-matrix cell sets it."""
    from ..core.placement import placement_from_env, resolve_placement
    from ..core.sparse import brute_force_join, threshold_for_selectivity
    from ..core.knn import brute_force_knn
    from ..serving.engine import ServingCorpus

    Pn = nblocks or max(jax.device_count(), 4)
    if jax.device_count() < Pn:
        raise SystemExit(
            f"need {Pn} devices (XLA_FLAGS="
            f"--xla_force_host_platform_device_count={Pn})")
    if modes is None:
        modes = tuple(ENGINE_MODES) + ("kernel",)
    plc = (placement_from_env(Pn) if placement is None
           else resolve_placement(placement, Pn))
    from ..launch.mesh import make_mesh
    mesh = make_mesh((Pn,), ("q",), devices=jax.devices()[:Pn])

    block, d, topk = 8, 16, 4
    N = Pn * block - 3
    rng = np.random.default_rng(0)
    corpus = rng.standard_normal((N, d)).astype(np.float32)
    corpus[:2 * block] *= 0.05          # vary block scales
    queries = rng.standard_normal((5, d)).astype(np.float32)

    env_q = quant_from_env()
    qmodes = (env_q,) if env_q != "off" else QUANT_DTYPES
    for qm in qmodes:
        for metric in ("dot", "l2"):
            thr = threshold_for_selectivity(corpus, 0.08, metric)
            ref_i, ref_j, ref_s = brute_force_join(corpus, thr, metric)
            ref_knn = brute_force_knn(corpus, topk, metric)
            for mode in modes:
                use_kernel = mode == "kernel"
                m = "batched" if use_kernel else mode
                stats: dict = {}
                res = quant_similarity_join(
                    corpus, mesh, threshold=thr, quant=qm, metric=metric,
                    mode=m, placement=plc, use_kernel=use_kernel,
                    stats=stats)
                assert np.array_equal(res.i, ref_i), \
                    (qm, metric, mode, "join i")
                assert np.array_equal(res.j, ref_j), \
                    (qm, metric, mode, "join j")
                np.testing.assert_allclose(res.scores, ref_s,
                                           rtol=1e-5, atol=1e-5)
                assert stats["emitted"] >= stats["kept"] == res.n_pairs
                knn = quant_knn_graph(
                    corpus, mesh, topk=topk, quant=qm, metric=metric,
                    mode=m, placement=plc, use_kernel=use_kernel)
                assert np.array_equal(knn.indices, ref_knn.indices), \
                    (qm, metric, mode, "knn idx")
                np.testing.assert_allclose(knn.scores, ref_knn.scores,
                                           rtol=1e-5, atol=1e-5)
        # serving: quantized stack + streamed replace, dot metric per
        # mode (the serving engines have no fused-kernel quant path)
        sc = ServingCorpus.build(corpus, mesh, placement=plc, quant=qm)
        total = sc.P * sc.block
        valid = np.zeros((total,), bool)
        valid[:N] = True
        rows = np.zeros((total, d), np.float32)
        rows[:N] = corpus
        for metric in ("dot", "l2"):
            ref_v, ref_i = _serving_topk_oracle(rows, valid, queries,
                                                topk, metric)
            for mode in ENGINE_MODES:
                sv, si = serving_query(sc, queries, topk=topk, mode=mode,
                                       metric=metric)
                assert np.array_equal(si, ref_i), (qm, metric, mode,
                                                   "serving idx")
                np.testing.assert_allclose(sv, ref_v, rtol=1e-5,
                                           atol=1e-5)
        newb = rng.standard_normal((sc.block, d)).astype(np.float32)
        sc.replace_block(1, newb)
        rows[sc.block:2 * sc.block] = newb
        valid[sc.block:2 * sc.block] = True
        ref_v, ref_i = _serving_topk_oracle(rows, valid, queries, topk,
                                            "dot")
        sv, si = serving_query(sc, queries, topk=topk, metric="dot")
        assert np.array_equal(si, ref_i), (qm, "churn serving idx")
        np.testing.assert_allclose(sv, ref_v, rtol=1e-5, atol=1e-5)
    print(f"quant selfcheck OK: P={Pn} placement={plc.name} "
          f"quant={','.join(qmodes)} modes={','.join(modes)}")


if __name__ == "__main__":
    _nb = int(sys.argv[1]) if len(sys.argv) > 1 else None
    _modes = tuple(sys.argv[2].split(",")) if len(sys.argv) > 2 else None
    _plc = sys.argv[3] if len(sys.argv) > 3 else None
    selfcheck_main(_nb, _modes, _plc)
