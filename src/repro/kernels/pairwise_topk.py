"""Pallas TPU kernel: fused pair scoring + per-row running top-k, tiled.

The k-NN graph engine's batched inner step (core/knn.py, DESIGN.md
section 12.3) materializes an [n_pairs, block, block] score tensor and
sorts every block row's candidate list.  This kernel fuses the step and
tiles it, so the block size is bounded by HBM and not by VMEM:

  * directed items — each scheduled slot pair (lo, hi) offers the hi
    block's rows as candidates to the lo block's rows and, unless it is
    a self tile, the lo block's rows to the hi block's rows.  The
    wrapper groups these directed items by receiving slot into a
    scalar-prefetched table (:func:`directed_items`),
  * grid ``(receiver slot s, row tile r, item j, column tile c)`` — each
    [tr, topk] output tile is visited in one consecutive run, so the
    running (value, index) lists stay resident in VMEM across (j, c) and
    are written back once; steps past a slot's item count run no body
    and repeat the previous block index (no DMA),
  * tile scoring — one [tr, tc] dot (f32 on the MXU at HIGHEST
    precision) or L2 tile per step.  L2 uses precomputed squared norms
    and the orientation-consistent order ``(2 d - |cand|^2) - |row|^2``
    of ref.pairwise_topk, so both orientations of a pair see the
    oracle's scores; a non-self pair is therefore scored once per
    orientation (twice the MXU work of one shared tile),
  * running top-k, gated by each row's k-th entry — the lists are kept
    sorted under the (-score, index) total order in VMEM scratch a lane
    tile wide.  A step first counts, per row, the candidates that reach
    the row's k-th score (only those can enter) and runs n = min(topk,
    the largest count) rounds, none when n is 0.  A round takes every
    row's best remaining candidate of the tile and inserts it at its
    rank (the entries below shift one lane, the k-th drops out; a row
    whose candidate does not beat its k-th entry keeps its list).
    Selection by a strict total order keeps the lists bit-identical to
    the oracle's two-key sort whatever the candidate order.  With
    ``count_rounds`` the kernel also returns per (slot, row tile) the
    rounds it ran and the tile steps that ran (``knn_graph`` reads them
    into Tracer counters).

The table's slot structure comes from the schedule's concrete slot ids;
the per-device part (the ownership dedup mask in ``meta[:, 0]``, block
ids, valid-row counts) is a second scalar operand the body reads, so a
masked tile still streams its blocks but computes nothing.  Self tiles
exclude the diagonal; candidate columns at or beyond a block's valid-row
count score -inf, below every list entry, so they never enter.  The
query kernel (kernels/query_score.py) keeps the ungated
:func:`merge_topk_rows`.  The quantized variant
(kernels/pairwise_batch_q.py) runs the same grid with int8/bf16 operands
and a dequant epilogue.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import IDX_SENTINEL as _IDX_SENTINEL
from .ref import NEG_INF, PRECISION, QUERY_METRICS, out_struct

IDX_SENTINEL = int(_IDX_SENTINEL)

# receiver rows x sender columns per grid step.  A block above
# TILE_COLS rows is zero-padded to a multiple of its column tile (padded
# rows are invalid): the widest of WIDE_COLS, halved down to TILE_COLS,
# that pads at most an eighth of the block.  Wide column tiles spread a
# step's fixed costs (the grid step, each round's list insert) over more
# candidates: on a TPU v5e the kernel takes a 131,072-row block 1.22x
# faster at 2,048 columns than at 512, while 512-row tiles are slower
# than 256-row ones
TILE_ROWS = 256
TILE_COLS = 512
WIDE_COLS = 2048
# directed-item table columns: (sender slot, live, item id) for the
# index maps and (active, is_self, sender block id, sender valid rows)
# per item for the body
_TAB = 3
_META = 4
# the diagonal offset of a non-self tile: no (row, column) of a tile
# differs by it, so the diagonal test needs no self-tile case
_NO_DIAG = 1 << 30


def tile_plan(block: int, rows: int = TILE_ROWS, cols: int = TILE_COLS,
              wide: int = WIDE_COLS):
    """(tr, tc, padded block) for a block of ``block`` rows: one tile
    of the 8-sublane-padded block when it fits ``cols``, else ``rows``
    by tc tiles over a block padded to a multiple of tc, tc the widest
    of ``wide`` halved down to ``cols`` that is at most block / 8."""
    b8 = -(-block // 8) * 8
    if b8 <= cols:
        return b8, b8, b8
    tc = wide
    while tc > cols and 8 * tc > block:
        tc //= 2
    return rows, tc, -(-block // tc) * tc


def pad_rows(x, rows: int, axis: int = 1):
    """Zero-pad ``axis`` of ``x`` up to ``rows`` (no copy when equal)."""
    extra = rows - x.shape[axis]
    if extra == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, extra)
    return jnp.pad(x, widths)


def merge_topk_rows(av, ai, cv, ci, topk: int):
    """Merge candidates ``(cv, ci)`` [R, C] into running lists ``(av,
    ai)`` [R, topk] by ``topk`` rounds of extract-the-maximum under the
    (-score, index) order.  Never concatenates along lanes and writes
    column t with a lane-iota select, so it lowers on TPU for any C and
    topk."""
    col = lax.broadcasted_iota(jnp.int32, av.shape, 1)
    out_v = jnp.full(av.shape, NEG_INF, jnp.float32)
    out_i = jnp.full(av.shape, IDX_SENTINEL, jnp.int32)
    for t in range(topk):
        m = jnp.maximum(jnp.max(av, axis=1, keepdims=True),
                        jnp.max(cv, axis=1, keepdims=True))       # [R, 1]
        tie_a = av == m
        tie_c = cv == m
        sel = jnp.minimum(
            jnp.min(jnp.where(tie_a, ai, IDX_SENTINEL), axis=1, keepdims=True),
            jnp.min(jnp.where(tie_c, ci, IDX_SENTINEL), axis=1, keepdims=True))
        out_v = jnp.where(col == t, m, out_v)
        out_i = jnp.where(col == t, sel, out_i)
        hit_a = tie_a & (ai == sel)
        hit_c = tie_c & (ci == sel)
        av = jnp.where(hit_a, NEG_INF, av)
        ai = jnp.where(hit_a, IDX_SENTINEL, ai)
        cv = jnp.where(hit_c, NEG_INF, cv)
        ci = jnp.where(hit_c, IDX_SENTINEL, ci)
    return out_v, out_i


def directed_items(lo, hi, k: int):
    """Group every pair's orientations by receiving slot, on the host.

    ``lo``/``hi`` are the concrete slot ids of the schedule.  Returns
    ``(table, J)``: ``table`` is a flat int32 [k * J * 3] array whose row
    ``s * J + j`` names receiver slot s's j-th directed item ``(sender
    slot, live, item id)``, J is the largest item count of any slot, and
    rows past a slot's count are not live and repeat its last sender (no
    DMA).  Item i < n_pairs is pair i's lo-receives orientation, item
    n_pairs + i its hi-receives one, which a self pair does not have."""
    lo = np.asarray(lo, np.int32)
    hi = np.asarray(hi, np.int32)
    recv = np.concatenate([lo, hi])
    send = np.concatenate([hi, lo])
    live = np.concatenate([np.ones(lo.shape, bool), lo != hi])
    per_slot = [np.nonzero(live & (recv == s))[0] for s in range(k)]
    J = max(1, max(len(items) for items in per_slot))
    table = np.zeros((k, J, _TAB), np.int32)
    for s, items in enumerate(per_slot):
        for j in range(J):
            if j < len(items):
                table[s, j] = (send[items[j]], 1, items[j])
            elif len(items):
                table[s, j] = (send[items[-1]], 0, items[-1])
            else:
                table[s, j] = (s, 0, 0)
    return table.reshape(-1), J


def item_meta(meta):
    """Per directed item ``(active, is_self, sender block id, sender
    valid rows)``, flat int32 [2 * n_pairs * 4] in the item order of
    :func:`directed_items` — the part of the table that varies per
    device (the dedup mask and the global block ids), read in the body."""
    meta = jnp.asarray(meta, jnp.int32)
    act, slf = meta[:, 0], meta[:, 1]
    items = jnp.stack([
        jnp.concatenate([act, act * (1 - slf)]),
        jnp.concatenate([slf, jnp.zeros_like(slf)]),
        jnp.concatenate([meta[:, 3], meta[:, 2]]),
        jnp.concatenate([meta[:, 5], meta[:, 4]])], axis=-1)
    return items.reshape(-1)


def _gated_merge(cv_ref, lv_ref, li_ref, base, *, topk: int):
    """Merge the candidate tile in ``cv_ref`` [R, C] (masked entries -inf;
    column c is candidate ``base + c``) into the sorted running lists
    ``(lv_ref, li_ref)`` [R, L >= 128] (lanes ``topk`` to L - 1 hold
    +inf), gated by each row's k-th entry.

    Only a candidate that reaches its row's k-th score can enter, so the
    merge runs n = min(topk, the most such candidates in any row) rounds
    and none when n is 0.  A round takes every row's best remaining
    candidate under the (-score, index) order and inserts it at its
    rank: the entries below shift one lane and the k-th drops out; a row
    whose candidate does not beat its k-th entry is left as it is.
    Returns n (an int32 scalar)."""
    rows, cols = cv_ref.shape
    col = lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    reach = jnp.where(cv_ref[...] >= lv_ref[:, topk - 1:topk], 1, 0)
    n = jnp.minimum(jnp.max(jnp.sum(reach, axis=1, keepdims=True)), topk)

    def round_(t, carry):
        cv = cv_ref[...]
        m = jnp.max(cv, axis=1, keepdims=True)
        first = jnp.min(jnp.where(cv == m, col, cols), axis=1, keepdims=True)
        sel = base + first
        lv, li = lv_ref[...], li_ref[...]
        # a sorted prefix; +inf past lane topk - 1 keeps those lanes and
        # makes lane 0 follow a "better" lane after the rotate
        better = (lv > m) | ((lv == m) & (li < sel))
        after = pltpu.roll(jnp.where(better, 1, 0), 1, 1) == 1
        lv_ref[...] = jnp.where(better, lv, jnp.where(after, m,
                                                      pltpu.roll(lv, 1, 1)))
        li_ref[...] = jnp.where(better, li, jnp.where(after, sel,
                                                      pltpu.roll(li, 1, 1)))
        cv_ref[...] = jnp.where(col == first, -jnp.inf, cv)
        return carry

    lax.fori_loop(0, n, round_, 0)
    return n


def _directed_topk_kernel(tab_ref, it_ref, xr_ref, xs_ref, nr_ref, ns_ref,
                          *rest, J: int, tr: int, tc: int, ntc: int,
                          topk: int, block_rows: int, metric: str,
                          quant: bool, count: bool):
    rest = list(rest)
    sd_ref = rest.pop(0) if quant else None
    ov_ref, oi_ref = rest.pop(0), rest.pop(0)
    cnt_ref = rest.pop(0) if count else None
    cv_ref, lv_ref, li_ref = rest[:3]
    acc_ref = rest[3] if count else None
    s, r, j, c = (pl.program_id(a) for a in range(4))
    base = (s * J + j) * _TAB
    m = tab_ref[base + 2] * _META

    @pl.when((j == 0) & (c == 0))
    def _init():
        lane = lax.broadcasted_iota(jnp.int32, lv_ref.shape, 1)
        lv_ref[...] = jnp.where(lane < topk, NEG_INF, jnp.inf)
        li_ref[...] = jnp.full(li_ref.shape, IDX_SENTINEL, jnp.int32)
        if count:
            acc_ref[0] = 0
            acc_ref[1] = 0

    @pl.when((tab_ref[base + 1] == 1) & (it_ref[m] == 1))
    def _tile():
        xr = xr_ref[...].astype(jnp.float32)                  # [tr, d]
        xs = xs_ref[...].astype(jnp.float32)                  # [tc, d]
        dots = lax.dot_general(xr, xs, (((1,), (1,)), ((), ())),
                               precision=PRECISION,
                               preferred_element_type=jnp.float32)
        if quant:  # dequant epilogue, scale order of ref.pairwise_topk_q
            dots = dots * (sd_ref[s, 0] * sd_ref[tab_ref[base], 0])
        if metric == "l2":
            cv_ref[...] = (2.0 * dots - ns_ref[...]) - nr_ref[...]
        else:
            cv_ref[...] = dots
        # columns past the sender's valid rows, and a self tile's
        # diagonal, are no candidates (-inf: below every list entry)
        nvc = it_ref[m + 3] - c * tc
        diag = jnp.where(it_ref[m + 1] == 1, c * tc - r * tr, _NO_DIAG)

        @pl.when((nvc < tc) | ((diag > -tc) & (diag < tr)))
        def _mask():
            row = lax.broadcasted_iota(jnp.int32, (tr, tc), 0)
            col = lax.broadcasted_iota(jnp.int32, (tr, tc), 1)
            keep = (col < nvc) & (row - col != diag)
            cv_ref[...] = jnp.where(keep, cv_ref[...], -jnp.inf)

        rounds = _gated_merge(cv_ref, lv_ref, li_ref,
                              it_ref[m + 2] * block_rows + c * tc, topk=topk)
        if count:
            acc_ref[0] += rounds
            acc_ref[1] += 1

    @pl.when((j == J - 1) & (c == ntc - 1))
    def _write():
        ov_ref[...] = lv_ref[:, :topk]
        oi_ref[...] = li_ref[:, :topk]
        if count:
            lane = lax.broadcasted_iota(jnp.int32, cnt_ref.shape, 1)
            cnt_ref[...] = jnp.where(lane == 0, acc_ref[0],
                                     jnp.where(lane == 1, acc_ref[1], 0))


def directed_topk_call(x, sq, lo, hi, meta, *, topk: int, block_rows: int,
                       metric: str, name: str, sd=None,
                       count_rounds: bool = False, interpret: bool = False):
    """Run the tiled directed top-k kernel.

    x: [k, block, d] blocks (f32, or int8/bf16 with ``sd`` [k, 2] f32
    per-block (scale, delta)); sq: [k, block] f32 squared row norms (read
    for l2 only); lo/hi/meta as in ref.pairwise_topk.  ``name`` is the
    kernel's fixed name: the compiled custom call, and so its device op
    in a profile, reads ``<name>.N`` whatever program holds it.  Returns
    per-slot running lists ``(vals f32 [k, block, topk], idx i32 [k,
    block, topk])``; with ``count_rounds`` also ``counts`` i32 [k, row
    tiles, 2]: per (slot, row tile) the merge rounds run and the tile
    steps that ran, summed over the (item, column tile) steps."""
    if metric not in QUERY_METRICS:
        raise ValueError(f"metric must be one of {QUERY_METRICS}, "
                         f"got {metric!r}")
    k, block, d = x.shape
    n_pairs = lo.shape[0]
    assert hi.shape == (n_pairs,) and meta.shape == (n_pairs, 6), \
        (hi.shape, meta.shape)
    assert sq.shape == (k, block), (sq.shape, x.shape)
    assert block >= block_rows, (block, block_rows)
    tr, tc, bp = tile_plan(block)
    table, J = directed_items(lo, hi, k)
    table = jnp.asarray(table)
    items = item_meta(meta)
    xp = pad_rows(x, bp)
    sqp = pad_rows(jnp.asarray(sq, jnp.float32), bp)
    ntc = bp // tc
    nrt = bp // tr

    def send_index(s, j, c, tab):
        row = (s * J + j) * _TAB
        live = tab[row + 1]
        # a dead step repeats the last live block index: no DMA
        return tab[row], live * c + (1 - live) * (ntc - 1)

    def xs_map(s, r, j, c, tab, it):
        slot, col = send_index(s, j, c, tab)
        return slot, col, 0

    def ns_map(s, r, j, c, tab, it):
        slot, col = send_index(s, j, c, tab)
        return slot, 0, col

    recv_map = lambda s, r, j, c, tab, it: (s, r, 0)
    in_specs = [
        pl.BlockSpec((None, tr, d), recv_map),
        pl.BlockSpec((None, tc, d), xs_map),
        pl.BlockSpec((None, tr, 1), recv_map),
        pl.BlockSpec((None, 1, tc), ns_map),
    ]
    operands = [xp, xp, sqp[:, :, None], sqp[:, None, :]]
    if sd is not None:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(jnp.asarray(sd, jnp.float32))
    ins = (table, items, *operands)
    out_spec = pl.BlockSpec((None, tr, topk), recv_map)
    out_specs = [out_spec, out_spec]
    out_shape = [out_struct((k, bp, topk), jnp.float32, *ins),
                 out_struct((k, bp, topk), jnp.int32, *ins)]
    # the running lists, whole lane tiles with one +inf lane at least, so
    # a rank shift is one rotate
    lw = -(-(topk + 1) // 128) * 128
    scratch = [pltpu.VMEM((tr, tc), jnp.float32),
               pltpu.VMEM((tr, lw), jnp.float32),
               pltpu.VMEM((tr, lw), jnp.int32)]
    if count_rounds:  # one (8, 128) tile per (slot, row tile); lanes 0, 1
        out_specs.append(pl.BlockSpec((None, 8, 128), recv_map))
        out_shape.append(out_struct((k, nrt * 8, 128), jnp.int32, *ins))
        scratch.append(pltpu.SMEM((2,), jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(k, nrt, J, ntc),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    outs = pl.pallas_call(
        functools.partial(_directed_topk_kernel, J=J, tr=tr, tc=tc, ntc=ntc,
                          topk=topk, block_rows=block_rows, metric=metric,
                          quant=sd is not None, count=count_rounds),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
        name=name,
    )(*ins)
    vals, idx = outs[0][:, :block], outs[1][:, :block]
    if not count_rounds:
        return vals, idx
    return vals, idx, outs[2].reshape(k, nrt, 8, 128)[:, :, 0, :2]


def pairwise_topk_pallas(quorum: jax.Array, lo: jax.Array, hi: jax.Array,
                         meta: jax.Array, *, topk: int, block_rows: int,
                         metric: str = "dot", count_rounds: bool = False,
                         interpret: bool = False):
    """quorum: [k, block, d] corpus blocks; lo/hi: [n_pairs] concrete
    int32 slot ids; meta: [n_pairs, 6] int32 ``(active, is_self, ga, gb,
    nv_lo, nv_hi)`` (see ref.pairwise_topk, the bit-parity oracle).
    ``block_rows`` is the unpadded global block stride for row-id math
    (``block`` may be padded above it).  Returns the per-slot running
    top-k after all tiles: ``(vals f32 [k, block, topk], idx i32 [k,
    block, topk])``, and with ``count_rounds`` the merge counts of
    :func:`directed_topk_call` third.
    """
    quorum = quorum.astype(jnp.float32)
    sq = jnp.sum(quorum * quorum, axis=-1)
    return directed_topk_call(quorum, sq, lo, hi, meta, topk=topk,
                              block_rows=block_rows, metric=metric,
                              name="pairwise_topk", count_rounds=count_rounds,
                              interpret=interpret)
