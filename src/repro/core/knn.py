"""All-pairs k-NN graph construction over quorum placements.

The workload none of the previous engines could express: for *every*
corpus row, the top-k nearest other rows — a per-row top-k selection
over the full O(N^2) pair sweep (the k-NN graph behind graph-based ANN
indexes, dedup clustering, and spectral methods).  It is ~200 lines on
the unified pair-sweep runtime (core/sweep.py) precisely because the
runtime already owns the schedule, the gather shifts, the execution
modes, and the kernel-hook dispatch; this module only supplies the
emitter and the reduction monoid (DESIGN.md section 12.3):

  * **emitter** — :class:`KnnEmitter`: each scheduled tile's [block,
    block] scores feed *both* endpoints' neighbor lists (rows of the
    ``lo`` block receive the ``hi`` block's rows as candidates and vice
    versa; self tiles exclude the diagonal and contribute one side),
    masked by the ownership rules (the engine dedup mask, row validity)
    and folded into per-slot running [k, block, topk] lists under the
    (-score, index) total order.
  * **monoid** — the scatter reduction is a top-k *merge*, not a sum:
    ``quorum_scatter`` routes each slot's partial lists back to the
    block owner with the inverse shifts and folds arrivals with the
    selection merge — the first non-additive monoid through the shared
    scatter, which is exactly what the Emitter/Combiner split buys.

Exactly-once coverage: the per-difference ownership partition schedules
every unordered block pair once (the even-P d = P/2 orbit deduplicated
by the mask), so every candidate row v != u reaches u's list exactly
once globally; selection by a strict total order makes the merges
associative, so all three execution modes, the fused kernel
(kernels/pairwise_topk.py), and the scatter order produce identical
indices.  Scores use the orientation-consistent L2 subtraction order of
ref.pairwise_topk so both sides of a tile match the host oracle's
matrix bitwise.

Verification mirrors the sparse engine: ``python -m repro.core.knn``
asserts exact index equality with the dense brute-force oracle for
every mode (incl. the fused kernel), both metrics, ragged corpora, and
underfull neighbor lists; tests/test_knn.py sweeps it over every
registered placement at P in {4, 5, 7, 8, 12, 13}.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..kernels.ref import IDX_SENTINEL, NEG_INF, PRECISION
from ..kernels.ref import QUERY_METRICS as KNN_METRICS
from ..launch.mesh import make_mesh, stage_rows
from ..obs import trace as obs_trace
from . import sweep as sweep_mod
from .scheduler import PairSchedule
from .sparse import _pair_meta, _pair_score_matrix
from .sweep import (ENGINE_MODES, SweepEmitter, mark_varying,
                    pair_mask_table, quorum_scatter)

__all__ = [
    "KnnEmitter",
    "KnnResult",
    "quorum_allpairs_knn",
    "knn_graph",
    "brute_force_knn",
    "KNN_METRICS",
]


def _merge_lists(cv, ci, sv, si, topk: int):
    """Fold candidate (scores, ids) into running [..., topk] lists by the
    (-score, index) total order — the k-NN selection monoid (associative
    and commutative under a strict total order, so every mode and the
    scatter fold select identically).  Delegates to the runtime's shared
    two-key selection (core/sweep.py topk_by_score)."""
    return sweep_mod.topk_by_score(jnp.concatenate([cv, sv], axis=-1),
                                   jnp.concatenate([ci, si], axis=-1), topk)


def _item_candidates(bi, bj, metric: str, active, is_self, ga, gb,
                     nv_lo, nv_hi, block_rows: int):
    """Both orientations' masked candidate planes for one tile — the
    single home of the k-NN tile math (bit-parity with
    ref.pairwise_topk): (lo-side scores [block, block], lo-side ids,
    hi-side scores, hi-side ids); the hi side is all-sentinel for self
    tiles (one contribution per pair)."""
    dots = jnp.dot(bi, bj.T, precision=PRECISION)         # [block, block]
    if metric == "l2":
        bin2 = jnp.sum(bi * bi, axis=-1)
        bjn2 = jnp.sum(bj * bj, axis=-1)
        t_lo = (2.0 * dots - bjn2[None, :]) - bin2[:, None]
        t_hi = (2.0 * dots - bin2[:, None]) - bjn2[None, :]
    else:
        t_lo = t_hi = dots
    block = bi.shape[0]
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


def _select_mode(schedule: PairSchedule, block: int,
                 batch_fn: Optional[Callable]) -> str:
    """The k-NN engine's ``mode="auto"`` working set fed to the shared
    heuristic (core/sweep.py select_mode): two [n_pairs, block, block]
    candidate planes (f32 scores + i32 ids) per tile orientation."""
    return sweep_mod.select_mode(
        schedule, schedule.n_pairs * block * block * 16, batch_fn)


class KnnEmitter(SweepEmitter):
    """Per-row top-k selection over the scheduled pairs (DESIGN.md
    section 12.3 — the k-NN graph workload).

    Folds every tile's two candidate planes into per-slot running
    [k, block, topk] (value, index) lists; the adapter then scatter-
    *merges* the per-slot partials at the block owners (the non-additive
    monoid of DESIGN.md section 12.2).
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

    @staticmethod
    def delta_retract(standing, stale, ctx=None):
        """Report the rows whose standing neighbor list cites a
        retracted source (DESIGN.md section 16.4).  Top-k selection is
        not invertible — a removed neighbor can expose a candidate the
        list already discarded — so retraction returns the *refresh
        set*: ``standing`` is the ``(scores, indices)`` pair, ``stale``
        the dirty global-id ``(starts, stops)`` ranges, and the result
        a boolean row mask the delta driver rebuilds from its per-tile
        candidate ledger."""
        _, best_i = standing
        starts, stops = (np.asarray(stale[0], np.int64),
                         np.asarray(stale[1], np.int64))
        hit = ((best_i[:, :, None] >= starts[None, None, :])
               & (best_i[:, :, None] < stops[None, None, :]))
        return hit.any(axis=(1, 2))

    @staticmethod
    def delta_fold(standing, fresh, ctx=None):
        """Merge fresh per-row candidates into standing neighbor lists
        under the strict (-score, index) total order (DESIGN.md
        section 16.4) — an associative, commutative monoid, so the
        merged top-k is bit-equal to a from-scratch fold whenever the
        standing list already equals the top-k of its unretracted
        sources.  Both arguments are ``(scores [n, k], indices [n, k])``
        with the (-inf, int64 max) sentinel padding every candidate
        plane in this repo uses."""
        s = np.concatenate([standing[0], fresh[0]], axis=1)
        i = np.concatenate([standing[1], fresh[1]], axis=1)
        order = np.lexsort((i, -s.astype(np.float64)), axis=1)
        topk = standing[0].shape[1]
        return (np.take_along_axis(s, order, axis=1)[:, :topk],
                np.take_along_axis(i, order, axis=1)[:, :topk])

    def batch(self, quorum):
        """Every tile in one batched accumulation.  The batched jnp step
        IS the ref oracle (kernels/ref.py pairwise_topk), with the fused
        Pallas kernel swapping in through the same hook."""
        batch_fn = self.batch_fn
        if batch_fn is None:
            from ..kernels import ref as kref
            batch_fn = functools.partial(
                kref.pairwise_topk, topk=self.topk, block_rows=self.block,
                metric=self.metric)
        meta = jnp.stack([(self.mask > 0).astype(jnp.int32),
                          self.is_self.astype(jnp.int32),
                          self.ga, self.gb, self.nv_lo, self.nv_hi],
                         axis=1)                           # [n_pairs, 6]
        return batch_fn(quorum, self.lo, self.hi, meta)

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

    def scan_emit(self, carry, quorum, item):
        """Merge one tile's two candidate planes into the running
        lists (serial per-pair; the low-memory oracle)."""
        vals, idx = carry
        lo_p, hi_p, m_p, self_p, ga_p, gb_p, nvl_p, nvh_p = item
        bi = jnp.take(quorum, lo_p, axis=0)
        bj = jnp.take(quorum, hi_p, axis=0)
        cv_l, ci_l, cv_h, ci_h = _item_candidates(
            bi, bj, self.metric, m_p > 0, self_p, ga_p, gb_p, nvl_p, nvh_p,
            self.block)
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

    def overlap_emit(self, state, item_idx, bi, bj):
        """Merge one tile as soon as its later block lands (static slot
        indices, so early slots' scatter shifts can pipeline)."""
        lo_s = int(self.schedule.pair_slots[item_idx, 0])
        hi_s = int(self.schedule.pair_slots[item_idx, 1])
        vals, idx = state["carry"]
        cv_l, ci_l, cv_h, ci_h = _item_candidates(
            bi, bj, self.metric, self.mask[item_idx] > 0,
            self.is_self[item_idx], self.ga[item_idx], self.gb[item_idx],
            self.nv_lo[item_idx], self.nv_hi[item_idx], self.block)
        mv, mi = _merge_lists(vals[lo_s], idx[lo_s], cv_l, ci_l, self.topk)
        vals = vals.at[lo_s].set(mv)
        idx = idx.at[lo_s].set(mi)
        if lo_s != hi_s:  # self tile: one contribution, hi plane is sentinel
            mv2, mi2 = _merge_lists(vals[hi_s], idx[hi_s], cv_h, ci_h,
                                    self.topk)
            vals = vals.at[hi_s].set(mv2)
            idx = idx.at[hi_s].set(mi2)
        state["carry"] = (vals, idx)

    def overlap_finalize(self, state):
        """The per-slot running lists, ready for the scatter merge."""
        return state["carry"]


def quorum_allpairs_knn(
    x: jax.Array,
    *,
    topk: int,
    axis_name: str,
    schedule: PairSchedule | None = None,
    axis_size: int | None = None,
    placement=None,
    metric: str = "dot",
    mode: str = "auto",
    mask: jax.Array | None = None,
    n_valid: int | None = None,
    batch_fn: Callable[..., Tuple[jax.Array, jax.Array]] | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Distributed all-pairs k-NN graph construction (DESIGN.md section
    12.3).

    Must run inside shard_map with ``x`` the local [block, d] shard.
    Returns ``(scores [block, topk], indices [block, topk])`` — each
    *valid* local row's top-k nearest other valid rows (self excluded)
    by the (-score, index) total order, with (NEG_INF, IDX_SENTINEL)
    sentinels when fewer than ``topk`` candidates exist; rows beyond
    ``n_valid`` carry unspecified lists (the host wrapper slices them).

    ``placement`` / ``schedule`` / ``axis_size`` select the residency
    layer exactly as in the other engines (``REPRO_PLACEMENT`` consulted
    when both are None); a full-replication placement runs the same
    generic pipeline over its A = {0..P-1} shifts.  ``mode`` is the
    runtime's batched/overlap/scan surface (``REPRO_ALLPAIRS_MODE``
    honored); ``batch_fn(quorum, lo, hi, meta) -> (vals, idx)`` is the
    fused-kernel hook (kernels.ops.pairwise_topk), batched mode only.  A
    hook that returns more (the kernel's merge counts, ``count_rounds``)
    has the rest appended to the result unchanged.
    """
    if metric not in KNN_METRICS:
        raise ValueError(f"metric must be one of {KNN_METRICS}, "
                         f"got {metric!r}")
    if topk < 1:
        raise ValueError(f"topk must be >= 1, got {topk}")
    sweep_mod.validate_mode(mode, batch_fn)
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

    lo, hi, ga, gb, nv_lo, nv_hi, is_self, _gblocks, _nv = _pair_meta(
        schedule, axis_name, block, n_valid)
    emitter = KnnEmitter(schedule, mask, topk, metric, block, axis_name,
                         (lo, hi, ga, gb, nv_lo, nv_hi, is_self),
                         batch_fn=batch_fn)
    vals, idx, *extra = sweep_mod.pair_sweep(
        emitter, schedule=schedule, axis_name=axis_name, mode=mode, x=x)
    partials = [(vals[s], idx[s]) for s in range(schedule.k)]
    mv, mi = quorum_scatter(
        partials, schedule, axis_name,
        reduce_fn=lambda a, b: _merge_lists(a[0], a[1], b[0], b[1], topk))
    return (mv, mi, *extra)


# ---------------------------------------------------------------------------
# Host-level driver + oracle (DESIGN.md section 12.3)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KnnResult:
    """Host-side k-NN graph (:func:`knn_graph`).

    ``indices[r]`` lists row r's ``topk`` nearest other rows (ascending
    by the (-score, index) order, i.e. best first); ``scores`` the
    matching similarity scores.  When the corpus has fewer than
    ``topk`` other rows, the tail is (IDX_SENTINEL, NEG_INF) padding.
    """

    indices: np.ndarray
    scores: np.ndarray
    topk: int

    @property
    def n_rows(self) -> int:
        """Number of corpus rows in the graph."""
        return int(self.indices.shape[0])


@functools.lru_cache(maxsize=64)
def _knn_fn(mesh, axis_name: str, N: int, block: int, topk: int,
            metric: str, mode: str, use_kernel: bool, placement,
            count_rounds: bool = False):
    """Build (and cache) the jitted distributed k-NN program — one trace
    per (mesh, shape, topk, ...) key, reused across repeated graphs.
    ``count_rounds`` (kernel only) adds a third output, each device's
    kernel merge counts [k, row tiles, 2] stacked over the devices."""
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
        batch_fn = functools.partial(kops.pairwise_topk, topk=topk,
                                     block_rows=block, metric=metric,
                                     count_rounds=count_rounds)

    def body(xb, mb):
        return quorum_allpairs_knn(
            xb, topk=topk, axis_name=axis_name, schedule=sched, mask=mb,
            metric=metric, mode=mode, n_valid=N, batch_fn=batch_fn)

    spec = PS(axis_name)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec),
        out_specs=(spec,) * (3 if count_rounds else 2),
        check_vma=kops.shard_map_check_vma(use_kernel)))
    return lambda xs: fn(xs, mask_table)


def knn_graph(corpus, mesh, *, topk: int, axis_name: str = "q",
              metric: str = "dot", mode: str = "auto", placement=None,
              use_kernel: bool = False,
              quant: str | None = None) -> KnnResult:
    """The k-NN graph of ``corpus`` rows, exactly (DESIGN.md section
    12.3).

    The host entry point: stages the [N, d] corpus as P quorum blocks
    (:func:`launch.mesh.stage_rows`, zero rows past N), runs
    :func:`quorum_allpairs_knn` under the selected placement (None
    defers to ``REPRO_PLACEMENT``), and slices the padding rows back
    off.  ``use_kernel`` routes the batched inner step through the fused
    Pallas kernel (kernels/pairwise_topk.py).  ``quant`` selects the
    quantized candidate-generation + certified-rescoring path (DESIGN.md
    section 17): ``"int8"`` / ``"bf16"`` route through
    :func:`core.quant.quant_knn_graph` (bit-identical results),
    ``"off"`` forces pure f32, None defers to ``REPRO_QUANT``.  Returns
    a :class:`KnnResult` with each row's exact top-k neighbors.
    """
    if quant is None:
        from .quant import quant_from_env
        quant = quant_from_env()
    if quant != "off":
        from . import quant as quant_mod
        return quant_mod.quant_knn_graph(
            corpus, mesh, topk=topk, quant=quant, axis_name=axis_name,
            metric=metric, mode=mode, placement=placement,
            use_kernel=use_kernel)
    N = len(corpus)
    P = mesh.shape[axis_name]
    from .placement import placement_from_env, resolve_placement
    plc = (placement_from_env(P) if placement is None
           else resolve_placement(placement, P))
    block = -(-N // P)
    # host spans of one build (DESIGN.md section 14.1): the device idles
    # in all but knn.wait
    tr = obs_trace.get_tracer()
    with tr.span("knn.stage", rows=N, P=P, padded=int(P * block != N)):
        corpus = np.ascontiguousarray(corpus, np.float32)
    with tr.span("knn.upload", rows=N, P=P):
        # blocks until the rows are on the chips, so that the span holds
        # the transfer and any padding; the program could not start
        # before it anyway
        xs = jax.block_until_ready(stage_rows(corpus, mesh, axis_name,
                                              block))
    # with the Tracer on, the kernel also counts its merge rounds; the
    # program the Tracer does not see neither computes nor fetches them
    count = bool(tr) and use_kernel
    with tr.span("knn.dispatch", rows=N, P=P):
        run = _knn_fn(mesh, axis_name, N, block, int(topk), metric, mode,
                      use_kernel, plc, count)
        out = run(xs)
    with tr.span("knn.wait", rows=N, P=P):
        jax.block_until_ready(out)
    with tr.span("knn.fetch", rows=N, P=P):
        vals, idx, *counts = (np.asarray(a) for a in out)
    if counts:
        per_dev = counts[0].reshape(P, -1, 2).sum(axis=1)
        for d, (rounds, steps) in enumerate(per_dev):
            tr.count("pairwise_topk.merge_rounds", int(rounds), device=d)
            tr.count("pairwise_topk.merge_steps", int(steps), device=d)
    return KnnResult(indices=idx[:N], scores=vals[:N], topk=int(topk))


def brute_force_knn(corpus: np.ndarray, topk: int,
                    metric: str = "dot") -> KnnResult:
    """Dense O(N^2) oracle: each row's top-k other rows by the engine's
    (-score, index) total order, same float32 score formulas (DESIGN.md
    section 12.3), sentinel-padded when topk > N - 1."""
    s = _pair_score_matrix(corpus, metric)
    N = s.shape[0]
    eff = min(topk, N - 1)
    idx = np.full((N, topk), np.int32(IDX_SENTINEL), np.int32)
    vals = np.full((N, topk), np.float32(NEG_INF), np.float32)
    for r in range(N):
        cand = np.concatenate([np.arange(r), np.arange(r + 1, N)])
        order = np.lexsort((cand, -s[r, cand]))[:eff]
        idx[r, :eff] = cand[order]
        vals[r, :eff] = s[r, cand[order]]
    return KnnResult(indices=idx, scores=vals, topk=int(topk))


# ---------------------------------------------------------------------------
# Selfcheck (subprocess entry point — tests/test_knn.py sweeps this)
# ---------------------------------------------------------------------------

def selfcheck_main(nblocks: int | None = None,
                   modes: Sequence[str] = ENGINE_MODES + ("kernel",),
                   placement: str | None = None) -> None:
    """Distributed k-NN graph selfcheck, mirroring core.sparse's
    (DESIGN.md section 12.3).

    Run as ``XLA_FLAGS=--xla_force_host_platform_device_count=<P> python
    -m repro.core.knn [P] [modes] [placement]``.  Asserts exact
    neighbor-index equality with the dense brute-force oracle for every
    requested mode (incl. the fused ``kernel`` batched path), both
    metrics, a ragged corpus tail, and an underfull (topk > N - 1)
    neighbor list with sentinel padding.
    """
    from .placement import placement_from_env, resolve_placement

    devs = jax.devices()
    Pn = nblocks or len(devs)
    assert len(devs) >= Pn, f"need {Pn} devices, have {len(devs)}"
    plc = (placement_from_env(Pn) if placement is None
           else resolve_placement(placement, Pn))
    mesh = make_mesh((Pn,), ("q",), devices=devs[:Pn])
    block, d, topk = 8, 16, 4
    rng = np.random.default_rng(0)
    N = Pn * block - 3          # ragged tail: exercises row validity
    corpus = rng.normal(size=(N, d)).astype(np.float32)

    for metric in KNN_METRICS:
        want = brute_force_knn(corpus, topk, metric)
        label = f"P={Pn} metric={metric}"
        for m in modes:
            mode, uk = ("batched", True) if m == "kernel" else (m, False)
            got = knn_graph(corpus, mesh, topk=topk, metric=metric,
                            mode=mode, placement=plc, use_kernel=uk)
            np.testing.assert_array_equal(
                got.indices, want.indices, err_msg=f"{label} mode={m}")
            np.testing.assert_allclose(
                got.scores, want.scores, rtol=1e-5, atol=1e-5,
                err_msg=f"{label} mode={m}")

    # underfull lists: topk exceeds the candidate count; the tail must
    # be exact (IDX_SENTINEL, NEG_INF) padding in every mode
    tiny = rng.normal(size=(Pn + 2, d)).astype(np.float32)
    want = brute_force_knn(tiny, Pn + 4, "dot")
    for m in modes:
        mode, uk = ("batched", True) if m == "kernel" else (m, False)
        got = knn_graph(tiny, mesh, topk=Pn + 4, mode=mode, placement=plc,
                        use_kernel=uk)
        np.testing.assert_array_equal(got.indices, want.indices,
                                      err_msg=f"underfull mode={m}")

    print(f"knn selfcheck OK: P={Pn} placement={plc.describe()} "
          f"modes={','.join(modes)} N={N} topk={topk} "
          f"metrics={','.join(KNN_METRICS)}")


if __name__ == "__main__":
    import sys
    selfcheck_main(
        int(sys.argv[1]) if len(sys.argv) > 1 else None,
        tuple(sys.argv[2].split(",")) if len(sys.argv) > 2
        else ENGINE_MODES + ("kernel",),
        sys.argv[3] if len(sys.argv) > 3 else None)
