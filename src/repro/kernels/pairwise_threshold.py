"""Pallas TPU kernel: fused thresholded scoring + sparse compaction.

The sparse join's batched inner step (core/sparse.py, DESIGN.md section
11) materializes an [n_pairs, block, block] score tensor, thresholds it,
and cumsum-scatters the survivors.  This kernel fuses the whole step, one
grid step per scheduled slot pair:

  * slot gather — the scalar-prefetched pair slot ids index the quorum
    operand directly in the BlockSpec index maps (exactly the
    pairwise_batch pattern), so each grid step DMAs only its two
    [block, d] corpus blocks,
  * prefilter skip — the per-pair ``active`` flag (norm-bound prefilter x
    ownership dedup mask, computed outside) gates the whole tile body
    with ``pl.when``: a pruned tile costs neither the score matmul nor
    the compaction,
  * threshold compaction — passing entries' positions come from an
    in-tile cumsum offset by a running SMEM count, and land in the
    [capacity] output through a one-hot matmul
    (``values^T @ onehot(pos)``): scatter-free, MXU-shaped, exactly the
    compaction a TPU can do fast.  Entries past ``capacity`` match no
    one-hot column and drop, while the count keeps the true total — the
    overflow contract of DESIGN.md 11.2.

Global row ids ride the one-hot matmul as exact float32 integers, which
caps ids at 2^24 (enforced by the core wrapper).  Layout notes (v5e):
``block`` should be a multiple of 8 sublanes (the ops.py wrapper
zero-pads rows; padded rows are rejected by the valid-row bounds so
padding is exact) and ``capacity`` of the 128-lane tile; the [M,
capacity] one-hot (M = block^2) is the VMEM high-water mark — a
production variant would tile the compaction over M.  Interpret mode on
CPU mirrors kernels/ops.py conventions and is swept in
tests/test_kernels.py against ref.pairwise_threshold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import IDX_SENTINEL as _IDX_SENTINEL
from .ref import NEG_INF, PRECISION, out_struct

IDX_SENTINEL = int(_IDX_SENTINEL)


def _threshold_kernel(lo_ref, hi_ref, meta_ref, x_lo_ref, x_hi_ref,
                      ov_ref, oi_ref, oj_ref, oc_ref,
                      vacc_ref, iacc_ref, jacc_ref, cnt_ref, *,
                      n_pairs: int, block_rows: int, capacity: int,
                      threshold: float, metric: str):
    p = pl.program_id(0)

    @pl.when(p == 0)
    def _init():
        vacc_ref[...] = jnp.zeros_like(vacc_ref)
        iacc_ref[...] = jnp.zeros_like(iacc_ref)
        jacc_ref[...] = jnp.zeros_like(jacc_ref)
        cnt_ref[0, 0] = 0

    @pl.when(meta_ref[p, 0] == 1)
    def _tile():
        bi = x_lo_ref[0]                                  # [block, d]
        bj = x_hi_ref[0]
        dot = jnp.dot(bi, bj.T, precision=PRECISION,
                      preferred_element_type=jnp.float32)
        if metric == "l2":  # same formula as engine/oracle: bit parity
            scores = (2.0 * dot - jnp.sum(bj * bj, axis=-1)[None, :]
                      - jnp.sum(bi * bi, axis=-1)[:, None])
        else:
            scores = dot
        blk = scores.shape[0]
        r = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
        s = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
        keep = scores >= threshold
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


def pairwise_threshold_pallas(quorum: jax.Array, lo: jax.Array,
                              hi: jax.Array, meta: jax.Array, *,
                              threshold: float, capacity: int,
                              block_rows: int, metric: str = "dot",
                              interpret: bool = False):
    """quorum: [k, block, d] corpus blocks; lo/hi: [n_pairs] int32 slot
    ids; meta: [n_pairs, 6] int32 ``(active, is_self, ga, gb, nv_lo,
    nv_hi)`` (see ref.pairwise_threshold, the bit-parity oracle).
    ``block_rows`` is the unpadded global block stride for row-id math
    (``block`` may be sublane-padded above it).  Returns ``(vals f32
    [capacity], i i32 [capacity], j i32 [capacity], count i32 [1, 1])``.
    """
    if metric not in ("dot", "l2"):
        raise ValueError(f"metric must be one of ('dot', 'l2'), "
                         f"got {metric!r}")
    k, block, d = quorum.shape
    n_pairs = lo.shape[0]
    assert hi.shape == (n_pairs,) and meta.shape == (n_pairs, 6), \
        (hi.shape, meta.shape)
    assert block >= block_rows, (block, block_rows)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,              # lo, hi, meta drive the tiles
        grid=(n_pairs,),
        in_specs=[
            pl.BlockSpec((1, block, d), lambda p, lo, hi, meta: (lo[p], 0, 0)),
            pl.BlockSpec((1, block, d), lambda p, lo, hi, meta: (hi[p], 0, 0)),
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
    ins = (jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32),
           jnp.asarray(meta, jnp.int32), quorum.astype(jnp.float32),
           quorum.astype(jnp.float32))
    vals, gi, gj, count = pl.pallas_call(
        functools.partial(_threshold_kernel, n_pairs=n_pairs,
                          block_rows=block_rows, capacity=capacity,
                          threshold=float(threshold), metric=metric),
        grid_spec=grid_spec,
        out_shape=[out_struct((1, capacity), jnp.float32, *ins),
                   out_struct((1, capacity), jnp.int32, *ins),
                   out_struct((1, capacity), jnp.int32, *ins),
                   out_struct((1, 1), jnp.int32, *ins)],
        interpret=interpret,
    )(*ins)
    return vals[0], gi[0], gj[0], count[0, 0]
