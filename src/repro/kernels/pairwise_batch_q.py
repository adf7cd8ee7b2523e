"""Pallas TPU kernels: quantized pair scoring (int8 / bf16 operands,
f32 accumulation, dequant epilogue).

Quantized variants of the sparse-join and k-NN batched inner steps
(kernels/pairwise_threshold.py, kernels/pairwise_topk.py; DESIGN.md
section 17.3).  The corpus operand stays in its quantized storage dtype
(int8 or bfloat16) all the way into VMEM — the 4x / 2x byte saving is
the point — and is cast to f32 only inside the tile body, where the MXU
accumulates in f32 and a scalar dequant epilogue (``* s_lo * s_hi``)
restores score scale.  int8 products are exactly representable in f32,
so the dequantized tile matches the jnp oracle bitwise in interpret
mode.

Differences from the f32 kernels, per tile:

  * the per-block (scale, delta) pairs ride as one [k, 2] f32 SMEM
    operand indexed by the prefetched slot ids — scalars, not tiles,
  * L2 scores substitute the *exact* f32 squared row norms carried as
    [k, block] side arrays (``(2 s - sq_hi) - sq_lo``), so the L2 error
    bound stays exactly twice the dot bound,
  * the threshold kernel widens its keep test to ``s_q >= thr - eps``
    with the in-tile certified bound of ref.quant_eps_tile (built from
    the delta scalars and the [k, block] L1-norm side arrays) — every
    possible true hit is emitted and the host's exact f32 rescoring
    pass (core/quant.py) resolves the borderline band,
  * the top-k kernel applies no band (candidate lists are certified and
    rescored host-side), it only swaps the scoring arithmetic.

Compaction, running top-k merge, sentinels, overflow contract, and
layout notes are identical to the f32 kernels.  Interpret mode on CPU
mirrors kernels/ops.py conventions and is swept in tests/test_quant.py
against ref.pairwise_threshold_q / ref.pairwise_topk_q.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pairwise_topk import directed_topk_call
from .ref import IDX_SENTINEL as _IDX_SENTINEL
from .ref import FP_REL, NEG_INF, PRECISION, QUERY_METRICS, out_struct

IDX_SENTINEL = int(_IDX_SENTINEL)


def _eps_tile(d_lo, d_hi, l1_lo, l1_hi, *, dim: int, metric: str):
    # l1_lo: [block, 1] column, l1_hi: [1, block] row; expression order
    # matches ref.quant_eps_tile for bit parity
    eps = (d_lo * l1_hi + d_hi * l1_lo
           + 3.0 * dim * d_lo * d_hi
           + FP_REL * (l1_lo * l1_hi + 1.0))
    if metric == "l2":
        eps = 2.0 * eps
    return eps


def _threshold_q_kernel(lo_ref, hi_ref, meta_ref, q_lo_ref, q_hi_ref,
                        sd_ref, l1_lo_ref, l1_hi_ref, sq_lo_ref, sq_hi_ref,
                        ov_ref, oi_ref, oj_ref, oc_ref,
                        vacc_ref, iacc_ref, jacc_ref, cnt_ref, *,
                        n_pairs: int, block_rows: int, capacity: int,
                        threshold: float, metric: str, dim: int):
    p = pl.program_id(0)

    @pl.when(p == 0)
    def _init():
        vacc_ref[...] = jnp.zeros_like(vacc_ref)
        iacc_ref[...] = jnp.zeros_like(iacc_ref)
        jacc_ref[...] = jnp.zeros_like(jacc_ref)
        cnt_ref[0, 0] = 0

    @pl.when(meta_ref[p, 0] == 1)
    def _tile():
        bi = q_lo_ref[0].astype(jnp.float32)              # [block, d]
        bj = q_hi_ref[0].astype(jnp.float32)
        s_lo = sd_ref[lo_ref[p], 0]
        s_hi = sd_ref[hi_ref[p], 0]
        dots = jnp.dot(bi, bj.T, precision=PRECISION,
                       preferred_element_type=jnp.float32) * (s_lo * s_hi)
        if metric == "l2":  # exact norms: oracle parity + 2x-dot bound
            scores = (2.0 * dots - sq_hi_ref[0]) - sq_lo_ref[0]
        else:
            scores = dots
        eps = _eps_tile(sd_ref[lo_ref[p], 1], sd_ref[hi_ref[p], 1],
                        l1_lo_ref[0], l1_hi_ref[0], dim=dim, metric=metric)
        blk = scores.shape[0]
        r = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
        s = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
        keep = scores >= threshold - eps
        keep &= (r < meta_ref[p, 4]) & (s < meta_ref[p, 5])
        keep &= jnp.where(meta_ref[p, 1] == 1, r < s, True)
        gi = meta_ref[p, 2] * block_rows + r
        gj = meta_ref[p, 3] * block_rows + s
        ei = jnp.minimum(gi, gj)
        ej = jnp.maximum(gi, gj)

        M = blk * blk
        keep_f = keep.reshape(M, 1)
        base = cnt_ref[0, 0]
        pos = base + jnp.cumsum(keep_f.astype(jnp.int32), axis=0) - 1
        slots = jax.lax.broadcasted_iota(jnp.int32, (M, capacity), 1)
        onehot = ((pos == slots) & keep_f).astype(jnp.float32)  # [M, cap]
        vacc_ref[...] += jnp.dot(scores.reshape(1, M), onehot,
                                 preferred_element_type=jnp.float32)
        iacc_ref[...] += jnp.dot(ei.reshape(1, M).astype(jnp.float32),
                                 onehot, preferred_element_type=jnp.float32)
        jacc_ref[...] += jnp.dot(ej.reshape(1, M).astype(jnp.float32),
                                 onehot, preferred_element_type=jnp.float32)
        cnt_ref[0, 0] = base + jnp.sum(keep_f.astype(jnp.int32))

    @pl.when(p == n_pairs - 1)
    def _done():
        total = cnt_ref[0, 0]
        used = jax.lax.broadcasted_iota(jnp.int32, (1, capacity), 1) < total
        ov_ref[...] = jnp.where(used, vacc_ref[...], NEG_INF)
        oi_ref[...] = jnp.where(used, iacc_ref[...].astype(jnp.int32),
                                IDX_SENTINEL)
        oj_ref[...] = jnp.where(used, jacc_ref[...].astype(jnp.int32),
                                IDX_SENTINEL)
        oc_ref[0, 0] = total


def pairwise_threshold_q_pallas(q: jax.Array, sd: jax.Array,
                                l1: jax.Array, sq: jax.Array,
                                lo: jax.Array, hi: jax.Array,
                                meta: jax.Array, *, threshold: float,
                                capacity: int, block_rows: int,
                                metric: str = "dot",
                                interpret: bool = False):
    """q: [k, block, d] int8/bf16 quantized blocks; sd: [k, 2] f32
    per-block (scale, delta); l1/sq: [k, block] f32 row L1 norms and
    exact squared norms; lo/hi: [n_pairs] int32 slot ids; meta:
    [n_pairs, 6] int32 ``(active, is_self, ga, gb, nv_lo, nv_hi)`` (see
    ref.pairwise_threshold_q, the bit-parity oracle; DESIGN.md section
    17.3).  Emits the widened ``s_q >= threshold - eps`` band.  Returns
    ``(vals f32 [capacity], i i32 [capacity], j i32 [capacity],
    count i32 [1, 1])``.
    """
    if metric not in QUERY_METRICS:
        raise ValueError(f"metric must be one of {QUERY_METRICS}, "
                         f"got {metric!r}")
    k, block, d = q.shape
    n_pairs = lo.shape[0]
    assert hi.shape == (n_pairs,) and meta.shape == (n_pairs, 6), \
        (hi.shape, meta.shape)
    assert sd.shape == (k, 2) and l1.shape == (k, block) \
        and sq.shape == (k, block), (sd.shape, l1.shape, sq.shape)
    assert block >= block_rows, (block, block_rows)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,              # lo, hi, meta drive the tiles
        grid=(n_pairs,),
        in_specs=[
            pl.BlockSpec((1, block, d), lambda p, lo, hi, meta: (lo[p], 0, 0)),
            pl.BlockSpec((1, block, d), lambda p, lo, hi, meta: (hi[p], 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),        # sd: [k, 2]
            pl.BlockSpec((1, block, 1), lambda p, lo, hi, meta: (lo[p], 0, 0)),
            pl.BlockSpec((1, 1, block), lambda p, lo, hi, meta: (hi[p], 0, 0)),
            pl.BlockSpec((1, block, 1), lambda p, lo, hi, meta: (lo[p], 0, 0)),
            pl.BlockSpec((1, 1, block), lambda p, lo, hi, meta: (hi[p], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, capacity), lambda p, lo, hi, meta: (0, 0)),
            pl.BlockSpec((1, capacity), lambda p, lo, hi, meta: (0, 0)),
            pl.BlockSpec((1, capacity), lambda p, lo, hi, meta: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[pltpu.VMEM((1, capacity), jnp.float32),
                        pltpu.VMEM((1, capacity), jnp.float32),
                        pltpu.VMEM((1, capacity), jnp.float32),
                        pltpu.SMEM((1, 1), jnp.int32)],
    )
    l1 = jnp.asarray(l1, jnp.float32)
    sq = jnp.asarray(sq, jnp.float32)
    ins = (jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32),
           jnp.asarray(meta, jnp.int32), q, q, jnp.asarray(sd, jnp.float32),
           l1[:, :, None], l1[:, None, :], sq[:, :, None], sq[:, None, :])
    vals, gi, gj, count = pl.pallas_call(
        functools.partial(_threshold_q_kernel, n_pairs=n_pairs,
                          block_rows=block_rows, capacity=capacity,
                          threshold=float(threshold), metric=metric,
                          dim=d),
        grid_spec=grid_spec,
        out_shape=[out_struct((1, capacity), jnp.float32, *ins),
                   out_struct((1, capacity), jnp.int32, *ins),
                   out_struct((1, capacity), jnp.int32, *ins),
                   out_struct((1, 1), jnp.int32, *ins)],
        interpret=interpret,
    )(*ins)
    return vals[0], gi[0], gj[0], count[0, 0]


def pairwise_topk_q_pallas(q: jax.Array, sd: jax.Array, sq: jax.Array,
                           lo: jax.Array, hi: jax.Array, meta: jax.Array,
                           *, topk: int, block_rows: int,
                           metric: str = "dot", interpret: bool = False):
    """q: [k, block, d] int8/bf16 quantized blocks; sd: [k, 2] f32
    per-block (scale, delta) — only scale is read here; sq: [k, block]
    exact f32 squared row norms (l2); lo/hi: [n_pairs] int32 slot ids;
    meta: [n_pairs, 6] int32 ``(active, is_self, ga, gb, nv_lo, nv_hi)``
    (see ref.pairwise_topk_q, the bit-parity oracle; DESIGN.md section
    17.3).  Runs the tiled directed grid of kernels/pairwise_topk.py with
    the dequant epilogue.  Returns the per-slot running quantized top-k
    after all tiles: ``(vals f32 [k, block, topk], idx i32 [k, block,
    topk])``.
    """
    k = q.shape[0]
    assert sd.shape == (k, 2), sd.shape
    return directed_topk_call(q, sq, lo, hi, meta, topk=topk,
                              block_rows=block_rows, metric=metric,
                              name="pairwise_topk_q", sd=sd,
                              interpret=interpret)
