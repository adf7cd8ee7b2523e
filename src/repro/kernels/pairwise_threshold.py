"""Pallas TPU kernel: fused thresholded scoring + sparse hit emission, tiled.

The sparse join's batched inner step (core/sparse.py, DESIGN.md section
11) scores every scheduled slot pair, thresholds the scores and compacts
the passing entries into fixed-capacity buffers.  This kernel fuses the
scoring and thresholding and tiles them like kernels/pairwise_topk.py, so
the block size is bounded by HBM and not by VMEM:

  * tile steps — one [tr, tc] f32 score tile per grid step (the dot on
    the MXU at HIGHEST precision; l2 from precomputed squared norms in
    the oracle's order ``(2 d - |y|^2) - |x|^2``), ``tile_plan`` and
    ``pad_rows`` as in the k-NN kernel.  The grid is ``[pair, fold,
    step]`` (:class:`Steps`): column tiles in folds of two, the row tiles
    of each inside, so a column tile stays resident while the row tiles
    stream past it.  A self tile (``lo == hi``) runs only the steps that
    hold some ``r < s`` entry, which fills each fold exactly, so every
    unordered pair is scored once; a cross tile runs all of them,
  * the per-device part (the ownership dedup and prefilter flag
    ``active``, the block ids, valid-row counts) is a second scalar
    operand the body reads: an inactive tile's steps, and steps wholly in
    padded rows, compute nothing,
  * count-first emission — a step counts its hits per row and writes its
    count.  Only a step with hits emits: each round takes every row's
    first remaining hit column, and a row's t-th hit lands in lane
    ``off[r] + t`` of the step's slot row of ``slot`` lanes, ``off`` the
    exclusive prefix of the rows' (clamped) counts, computed as a
    strictly-lower-triangular [tr, tr] matmul.  The rounds run as many
    times as the busiest row has hits, so emission costs O(hits) plus a
    small constant; there is no in-tile cumsum and no one-hot over the
    tile.  A step with more than ``slot`` hits keeps the first ``slot``
    in (row, column) order and its true count,
  * compaction — the wrapper gathers the slot rows into the
    ``[capacity]`` buffers with an XLA prefix sum over the step counts.
    The buffer order is step order (pair, fold, step), then row, then
    column, not the oracle's row-major order over a pair;
    ``similarity_join`` sorts by (i, j), which makes the two agree.

The overflow contract (DESIGN.md 11.2) holds at both limits: the count
is the true number of hits, the kept hits are a valid prefix, and the
``dropped`` statistic (hits a step's slot row could not hold) tells
``similarity_join`` to re-run with a wider slot, as ``count > capacity``
tells it to re-run with a larger capacity.  Global row ids are int32.
Interpret mode on CPU mirrors kernels/ops.py conventions and is swept in
tests/test_kernels.py against ref.pairwise_threshold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pairwise_topk import pad_rows, tile_plan
from .ref import IDX_SENTINEL as _IDX_SENTINEL
from .ref import NEG_INF, PRECISION, out_struct

IDX_SENTINEL = int(_IDX_SENTINEL)

# lanes of one step's slot row: the hits a tile step can hand over before
# its slot overflows (similarity_join widens it on overflow)
SLOT = 128
# scoped VMEM for a step: at d = 512 the double-buffered [2,048, d] column
# tile alone is 8 MiB, past the compiler's 16 MiB default with the score
# and hit tiles (a TPU v5e core has 128 MiB)
VMEM_LIMIT = 64 << 20
# pair table columns: (lo slot, hi slot, self tile)
_PAIR = 3
_META = 6
# steps whose counts share one (8, 128) output tile
_COUNTS_PER_TILE = 8 * 128


class Steps:
    """The tile steps of a ``[pair, fold, step]`` grid over blocks of
    ``rows`` (padded) rows in [tr, tc] tiles, tc a multiple of tr.

    Column tiles are taken in folds of two, ``f`` and ``ntc - 1 - f``,
    each with its row tiles inside.  A cross tile runs every row tile of
    both.  A self tile runs only the row tiles that hold an entry above
    the diagonal (row < column): ``m (c + 1)`` of column tile c, m = tc /
    tr, so each fold holds ``m (ntc + 1)`` live steps whatever f is and
    the triangle fills the grid.  Steps past a fold's live count are dead:
    they repeat the fold's last live tile (no DMA) and compute nothing.
    """

    def __init__(self, rows: int, tr: int, tc: int, any_cross: bool):
        assert tc % tr == 0 and rows % tc == 0, (rows, tr, tc)
        self.tr, self.tc = tr, tc
        self.m = tc // tr
        self.ntc = rows // tc
        self.nrt = rows // tr
        self.folds = -(-self.ntc // 2)
        self.fold_len = (2 * self.nrt if any_cross
                         else self.m * (self.ntc + 1))

    def decode(self, f, s, is_self):
        """(row tile, column tile, live) of step s of fold f; scalar
        arithmetic, so that index maps and the body share it."""
        ca, cb = f, self.ntc - 1 - f
        split = jnp.where(is_self == 1, self.m * (f + 1), self.nrt)
        total = jnp.where(ca == cb, split,
                          jnp.where(is_self == 1, self.m * (self.ntc + 1),
                                    2 * self.nrt))
        s_live = jnp.minimum(s, total - 1)
        second = s_live >= split
        return (jnp.where(second, s_live - split, s_live),
                jnp.where(second, cb, ca), s < total)

    def flat(self, p, f, s):
        """The step's position in the grid's row-major order."""
        return (p * self.folds + f) * self.fold_len + s


def _threshold_kernel(pair_ref, meta_ref, xr_ref, xc_ref, *rest,
                      steps: Steps, slot: int, threshold: float,
                      block_rows: int, metric: str):
    if metric == "l2":
        nr_ref, nc_ref, *rest = rest
    ov_ref, oi_ref, oj_ref, oc_ref, sc_ref, hit_ref = rest
    tr, tc = steps.tr, steps.tc
    p, f, s = (pl.program_id(a) for a in range(3))
    t = steps.flat(p, f, s)
    m = p * _META
    rt, ct, live = steps.decode(f, s, pair_ref[p * _PAIR + 2])
    r0, c0 = rt * tr, ct * tc                   # the step's first row, column
    is_self = meta_ref[m + 1]
    nv_lo, nv_hi = meta_ref[m + 4], meta_ref[m + 5]

    @pl.when(t % _COUNTS_PER_TILE == 0)
    def _init_counts():
        oc_ref[...] = jnp.zeros(oc_ref.shape, jnp.int32)

    @pl.when(live & (meta_ref[m] == 1) & (r0 < nv_lo) & (c0 < nv_hi))
    def _tile():
        dots = lax.dot_general(xr_ref[...], xc_ref[...],
                               (((1,), (1,)), ((), ())),
                               precision=PRECISION,
                               preferred_element_type=jnp.float32)
        if metric == "l2":  # the oracle's order
            sc_ref[...] = (2.0 * dots - nc_ref[...]) - nr_ref[...]
        else:
            sc_ref[...] = dots
        col = lax.broadcasted_iota(jnp.int32, (tr, tc), 1)
        # a hit holds its column, every other entry tc
        hit_ref[...] = jnp.where(sc_ref[...] >= threshold, col, tc)

        @pl.when((r0 + tr > nv_lo) | (c0 + tc > nv_hi)
                 | ((is_self == 1) & (r0 + tr > c0)))
        def _mask():
            rg = r0 + lax.broadcasted_iota(jnp.int32, (tr, tc), 0)
            cg = c0 + col
            keep = (rg < nv_lo) & (cg < nv_hi) & ((is_self == 0) | (rg < cg))
            hit_ref[...] = jnp.where(keep, hit_ref[...], tc)

        cnt = jnp.sum(jnp.where(hit_ref[...] < tc, 1, 0), axis=1,
                      keepdims=True)                            # [tr, 1]
        n = jnp.sum(cnt)
        pos = (lax.broadcasted_iota(jnp.int32, oc_ref.shape, 0) * 128
               + lax.broadcasted_iota(jnp.int32, oc_ref.shape, 1))
        oc_ref[...] = jnp.where(pos == t % _COUNTS_PER_TILE, n, oc_ref[...])

        @pl.when(n > 0)
        def _emit():
            kept = jnp.minimum(cnt, slot)
            # exclusive prefix of the rows' counts: a strictly lower
            # triangular matmul, exact for integers at HIGHEST
            below = (lax.broadcasted_iota(jnp.int32, (tr, tr), 1)
                     < lax.broadcasted_iota(jnp.int32, (tr, tr), 0))
            off = jnp.dot(jnp.where(below, 1.0, 0.0),
                          jnp.broadcast_to(kept.astype(jnp.float32),
                                           (tr, 128)),
                          precision=PRECISION,
                          preferred_element_type=jnp.float32)
            off = off[:, :1].astype(jnp.int32)                  # [tr, 1]
            lane = lax.broadcasted_iota(jnp.int32, (tr, slot), 1)
            gi = (meta_ref[m + 2] * block_rows + r0
                  + lax.broadcasted_iota(jnp.int32, (tr, 1), 0))
            gj0 = meta_ref[m + 3] * block_rows + c0

            def round_(k, acc):
                av, ai, aj = acc
                hit = hit_ref[...]
                first = jnp.min(hit, axis=1, keepdims=True)     # [tr, 1]
                at = col == first
                v = jnp.max(jnp.where(at, sc_ref[...], -jnp.inf), axis=1,
                            keepdims=True)
                hit_ref[...] = jnp.where(at, tc, hit)
                gj = gj0 + first
                into = (lane == off + k) & (k < kept)           # [tr, slot]
                av = jnp.maximum(av, jnp.max(
                    jnp.where(into, v, -jnp.inf), axis=0, keepdims=True))
                ai = jnp.maximum(ai, jnp.max(
                    jnp.where(into, jnp.minimum(gi, gj), -1), axis=0,
                    keepdims=True))
                aj = jnp.maximum(aj, jnp.max(
                    jnp.where(into, jnp.maximum(gi, gj), -1), axis=0,
                    keepdims=True))
                return av, ai, aj

            init = (jnp.full((1, slot), -jnp.inf, jnp.float32),
                    jnp.full((1, slot), -1, jnp.int32),
                    jnp.full((1, slot), -1, jnp.int32))
            av, ai, aj = lax.fori_loop(0, jnp.max(kept), round_, init)
            mine = (lax.broadcasted_iota(jnp.int32, ov_ref.shape, 0)
                    == t % 8)
            ov_ref[...] = jnp.where(mine, av, ov_ref[...])
            oi_ref[...] = jnp.where(mine, ai, oi_ref[...])
            oj_ref[...] = jnp.where(mine, aj, oj_ref[...])


def emit_steps(quorum, lo, hi, meta, *, threshold: float, block_rows: int,
               metric: str = "dot", slot: int = SLOT,
               interpret: bool = False):
    """Run the tiled kernel: per tile step its slot row and its true hit
    count.

    quorum: [k, block, d] f32; lo/hi: [n_pairs] concrete slot ids (a pair
    is a self tile when they are equal, as the schedule makes them); meta:
    [n_pairs, 6] int32 ``(active, is_self, ga, gb, nv_lo, nv_hi)``.
    Returns ``(vals f32 [S, slot], i i32
    [S, slot], j i32 [S, slot], counts i32 [S])`` over the S grid steps
    (:class:`Steps`); lane l of a step's row is valid for l < min(count,
    slot), and a dead step counts 0."""
    if metric not in ("dot", "l2"):
        raise ValueError(f"metric must be one of ('dot', 'l2'), "
                         f"got {metric!r}")
    k, block, d = quorum.shape
    n_pairs = lo.shape[0]
    assert hi.shape == (n_pairs,) and meta.shape == (n_pairs, 6), \
        (hi.shape, meta.shape)
    assert block >= block_rows, (block, block_rows)
    tr, tc, bp = tile_plan(block)
    lo = np.asarray(lo, np.int32)
    hi = np.asarray(hi, np.int32)
    steps = Steps(bp, tr, tc, any_cross=bool((lo != hi).any()))
    n_steps = n_pairs * steps.folds * steps.fold_len
    pairs = np.stack([lo, hi, (lo == hi).astype(np.int32)], 1)
    xp = pad_rows(quorum.astype(jnp.float32), bp)

    def rows_map(p, f, s, pr, mr):
        rt, _, _ = steps.decode(f, s, pr[p * _PAIR + 2])
        return pr[p * _PAIR], rt, 0

    def cols_map(p, f, s, pr, mr):
        _, ct, _ = steps.decode(f, s, pr[p * _PAIR + 2])
        return pr[p * _PAIR + 1], ct, 0

    in_specs = [pl.BlockSpec((None, tr, d), rows_map),
                pl.BlockSpec((None, tc, d), cols_map)]
    operands = [xp, xp]
    if metric == "l2":
        sq = jnp.sum(xp * xp, axis=-1)

        def ncols_map(p, f, s, pr, mr):
            slot_, ct, _ = cols_map(p, f, s, pr, mr)
            return slot_, 0, ct

        in_specs += [pl.BlockSpec((None, tr, 1), rows_map),
                     pl.BlockSpec((None, 1, tc), ncols_map)]
        operands += [sq[:, :, None], sq[:, None, :]]
    ins = (jnp.asarray(pairs.reshape(-1)),
           jnp.asarray(meta, jnp.int32).reshape(-1), *operands)
    rows8 = -(-n_steps // 8) * 8
    tiles = -(-n_steps // _COUNTS_PER_TILE)
    slot_spec = pl.BlockSpec(
        (8, slot), lambda p, f, s, pr, mr: (steps.flat(p, f, s) // 8, 0))
    out_specs = [slot_spec, slot_spec, slot_spec,
                 pl.BlockSpec((8, 128), lambda p, f, s, pr, mr:
                              (steps.flat(p, f, s) // _COUNTS_PER_TILE, 0))]
    out_shape = [out_struct((rows8, slot), jnp.float32, *ins),
                 out_struct((rows8, slot), jnp.int32, *ins),
                 out_struct((rows8, slot), jnp.int32, *ins),
                 out_struct((tiles * 8, 128), jnp.int32, *ins)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_pairs, steps.folds, steps.fold_len),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((tr, tc), jnp.float32),
                        pltpu.VMEM((tr, tc), jnp.int32)],
    )
    ov, oi, oj, oc = pl.pallas_call(
        functools.partial(_threshold_kernel, steps=steps, slot=slot,
                          threshold=float(threshold), block_rows=block_rows,
                          metric=metric),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="pairwise_threshold",
    )(*ins)
    return (ov[:n_steps], oi[:n_steps], oj[:n_steps],
            oc.reshape(-1)[:n_steps])


def compact_steps(vals, gi, gj, counts, *, capacity: int):
    """Gather the steps' slot rows into ``[capacity]`` buffers in step
    order (an XLA prefix sum over the counts, no scatter).  Returns
    ``(vals f32 [capacity], i i32 [capacity], j i32 [capacity], count i32
    [], stats i32 [2])``: ``count`` the true number of hits, ``stats``
    ``(steps that emitted, hits dropped by a full slot row)``; unused
    buffer entries hold (NEG_INF, IDX_SENTINEL)."""
    n_steps, slot = vals.shape
    kept = jnp.minimum(counts, slot)
    ends = jnp.cumsum(kept)
    total_kept = ends[-1]
    count = jnp.sum(counts)
    q = jnp.arange(capacity, dtype=jnp.int32)
    # the step holding buffer entry q: the first whose kept hits end past q
    step = jnp.minimum(jnp.searchsorted(ends, q, side="right"), n_steps - 1)
    lane = jnp.clip(q - (ends - kept)[step], 0, slot - 1)
    used = q < total_kept
    stats = jnp.stack([jnp.sum((counts > 0).astype(jnp.int32)),
                       count - total_kept])
    return (jnp.where(used, vals[step, lane], NEG_INF),
            jnp.where(used, gi[step, lane], IDX_SENTINEL),
            jnp.where(used, gj[step, lane], IDX_SENTINEL),
            count, stats)


def pairwise_threshold_pallas(quorum: jax.Array, lo, hi, meta: jax.Array,
                              *, threshold: float, capacity: int,
                              block_rows: int, metric: str = "dot",
                              slot: int = SLOT, interpret: bool = False):
    """quorum: [k, block, d] corpus blocks; lo/hi: [n_pairs] concrete
    int32 slot ids; meta: [n_pairs, 6] int32 ``(active, is_self, ga, gb,
    nv_lo, nv_hi)`` (see ref.pairwise_threshold, the oracle of the hit
    set).  ``block_rows`` is the unpadded global block stride for row-id
    math.  Returns ``(vals f32 [capacity], i i32 [capacity], j i32
    [capacity], count i32 [], stats i32 [2])`` as :func:`compact_steps`.
    """
    vals, gi, gj, counts = emit_steps(
        quorum, lo, hi, meta, threshold=threshold, block_rows=block_rows,
        metric=metric, slot=slot, interpret=interpret)
    return compact_steps(vals, gi, gj, counts, capacity=capacity)
