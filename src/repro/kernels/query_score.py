"""Pallas TPU kernel: fused query scoring + dedup mask + running top-k.

The serving engine's batched local step (serving/engine.py) materializes a
[Q, k, block] score tensor, masks it, and sorts k*block candidates per
query.  This kernel fuses the whole step over a grid of (quorum slot s,
row tile t):

  * slot gather — the BlockSpec index map DMAs one [tn, d] row tile of
    slot s's corpus block (the quorum stack never round-trips through a
    gathered [Q, k, block] HBM intermediate, and VMEM holds one tile, so
    the block size is bounded by HBM, not VMEM),
  * scoring — the [Q, tn] dot (f32 on the MXU at HIGHEST precision) or
    L2 tile lives only in VMEM; L2 uses precomputed squared norms in the
    oracle's order,
  * dedup mask — cover mask and row validity fold in as a NEG_INF select,
  * running top-k — the [Q, topk] (value, index) outputs stay resident
    in VMEM for the whole grid and are merged with each tile by
    ``topk`` rounds of extract-the-maximum.

Selection follows the engine's total order (-score, global index): among
equal scores the smallest corpus index wins, so results are bit-identical
to the two-key-sort jnp path (kernels/ref.py `query_topk`) and the
brute-force oracle.  ``Q`` should be a multiple of 8 sublanes (the ops.py
wrapper pads query rows — exact: padded rows are dropped after the call).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pairwise_topk import merge_topk_rows, pad_rows
from .ref import IDX_SENTINEL as _IDX_SENTINEL
from .ref import NEG_INF, PRECISION, QUERY_METRICS, out_struct

IDX_SENTINEL = int(_IDX_SENTINEL)

# corpus rows per grid step; larger blocks are zero-padded to a multiple
# of it (padded rows carry mask 0)
TILE_ROWS = 2048


def _query_topk_kernel(x_ref, q_ref, xn_ref, qn_ref, m_ref, g_ref,
                       ov_ref, oi_ref, *, topk: int, metric: str):
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _init():
        ov_ref[...] = jnp.full(ov_ref.shape, NEG_INF, jnp.float32)
        oi_ref[...] = jnp.full(oi_ref.shape, IDX_SENTINEL, jnp.int32)

    dot = lax.dot_general(q_ref[...], x_ref[...], (((1,), (1,)), ((), ())),
                          precision=PRECISION,
                          preferred_element_type=jnp.float32)   # [Q, tn]
    if metric == "l2":  # same formula and order as the oracle
        scores = (2.0 * dot - xn_ref[...]) - qn_ref[...]
    else:
        scores = dot
    valid = m_ref[...] > 0                                      # [1, tn]
    cv = jnp.where(valid, scores, NEG_INF)
    ci = jnp.broadcast_to(jnp.where(valid, g_ref[...], IDX_SENTINEL),
                          scores.shape)
    ov, oi = merge_topk_rows(ov_ref[...], oi_ref[...], cv, ci, topk)
    ov_ref[...] = ov
    oi_ref[...] = oi


def query_topk_pallas(stack: jax.Array, queries: jax.Array, mask: jax.Array,
                      gidx: jax.Array, *, topk: int, metric: str = "dot",
                      interpret: bool = False):
    """stack: [k, block, d] quorum blocks; queries: [Q, d]; mask: [k, block]
    float32 (1 = this device scores the row: cover dedup x validity);
    gidx: [k, block] int32 global corpus row ids.  Returns the running
    top-k after all k slots: (values [Q, topk] f32, indices [Q, topk] i32).
    """
    if metric not in QUERY_METRICS:
        raise ValueError(f"metric must be one of {QUERY_METRICS}, "
                         f"got {metric!r}")
    k, block, d = stack.shape
    Q = queries.shape[0]
    assert queries.shape == (Q, d), (queries.shape, stack.shape)
    assert mask.shape == (k, block) and gidx.shape == (k, block), \
        (mask.shape, gidx.shape)
    stack = stack.astype(jnp.float32)
    queries = queries.astype(jnp.float32)
    tn = block if block <= TILE_ROWS else TILE_ROWS
    bp = -(-block // tn) * tn
    if metric == "l2":
        xn = jnp.sum(stack * stack, axis=-1)
        qn = jnp.sum(queries * queries, axis=-1)
    else:  # unread
        xn = jnp.zeros((k, block), jnp.float32)
        qn = jnp.zeros((Q,), jnp.float32)
    row = lambda a: pad_rows(a, bp)[:, None, :]             # [k, 1, bp]
    ins = (pad_rows(stack, bp), queries, row(xn), qn[:, None],
           row(jnp.asarray(mask, jnp.float32)),
           row(jnp.asarray(gidx, jnp.int32)))
    tile = pl.BlockSpec((None, 1, tn), lambda s, t: (s, 0, t))
    acc = pl.BlockSpec((Q, topk), lambda s, t: (0, 0))
    return pl.pallas_call(
        functools.partial(_query_topk_kernel, topk=topk, metric=metric),
        grid=(k, bp // tn),
        in_specs=[
            pl.BlockSpec((None, tn, d), lambda s, t: (s, t, 0)),
            pl.BlockSpec((Q, d), lambda s, t: (0, 0)),
            tile,
            pl.BlockSpec((Q, 1), lambda s, t: (0, 0)),
            tile,
            tile,
        ],
        out_specs=[acc, acc],
        out_shape=[out_struct((Q, topk), jnp.float32, *ins),
                   out_struct((Q, topk), jnp.int32, *ins)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*ins)
