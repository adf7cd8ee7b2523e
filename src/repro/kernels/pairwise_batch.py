"""Pallas TPU kernel: fused batched pair interaction + slot segment reduction.

The batched engine mode (core.allpairs, DESIGN.md section 4) evaluates all
n_pairs quorum-block interactions as one launch.  The generic jnp path
materializes [n_pairs, block, ...] gathered operands and a [2*n_pairs,
block, ...] contribution buffer before the segment_sum; this kernel fuses
the whole step for the n-body-shaped ``pair_fn``:

  * slot gather — the scalar-prefetched pair slot ids index the quorum
    operand directly in the BlockSpec index maps, so each grid step DMAs
    exactly the two body blocks it interacts: the lo block as [block, 4]
    columns (bodies on sublanes) and the hi block as [4, block] rows
    (bodies on lanes),
  * pair interaction — one [block, block] plane per coordinate lives only
    in VMEM (no 3-wide minor axis, which Mosaic cannot tile),
  * segment reduction — both sides accumulate lane-dense into a
    [k, 3, block] VMEM accumulator at their slot rows: the hi side by a
    sublane sum, the lo side by a ones-row matmul that lands the row sums
    on lanes; the output is written once at the final grid step.

Layout notes (v5e): the feature dims (4-wide bodies in, 3-wide forces out)
sit far below the 128-lane tile, so on hardware this kernel is VPU/DMA-bound
rather than MXU-bound — the win over the jnp path is the removed HBM
round-trip of the [n_pairs, block, block] distance intermediates.  ``block``
should be a multiple of 8 sublanes; the ops.py wrapper pads with zero-mass
bodies (exact: zero mass contributes zero force).  Interpret mode on CPU
mirrors kernels/ops.py conventions and is what tests/test_kernels.py sweeps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import PRECISION, out_struct

DEFAULT_SOFTENING = 1e-2


def _nbody_batch_kernel(lo_ref, hi_ref, x_lo_ref, x_hi_ref, w_ref, o_ref,
                        acc_ref, *, n_pairs: int, softening: float):
    p = pl.program_id(0)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bi = x_lo_ref[...]                                   # [block, 4]
    bj = x_hi_ref[...]                                   # [4, block]
    d = [bj[c:c + 1, :] - bi[:, c:c + 1] for c in range(3)]
    r2 = d[0] * d[0] + d[1] * d[1] + d[2] * d[2] + softening
    inv_r3 = lax.rsqrt(r2) / r2
    w = bi[:, 3:4] * bj[3:4, :] * inv_r3                 # [block, block]
    ones = jnp.ones((1, w.shape[1]), jnp.float32)
    wi = w_ref[p, 0]
    wj = w_ref[p, 1]
    for c in range(3):
        f = w * d[c]                                     # force ON i FROM j
        f_i = lax.dot_general(ones, f, (((1,), (1,)), ((), ())),
                              precision=PRECISION,
                              preferred_element_type=jnp.float32)  # [1, blk]
        f_j = -jnp.sum(f, axis=0, keepdims=True)                    # [1, blk]
        acc_ref[lo_ref[p], c:c + 1, :] += wi * f_i
        acc_ref[hi_ref[p], c:c + 1, :] += wj * f_j

    @pl.when(p == n_pairs - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def pairwise_batch_pallas(quorum: jax.Array, lo: jax.Array, hi: jax.Array,
                          w: jax.Array, *,
                          softening: float = DEFAULT_SOFTENING,
                          interpret=False) -> jax.Array:
    """quorum: [k, block, 4] body blocks (x, y, z, mass); lo/hi: [n_pairs]
    int32 slot ids; w: [n_pairs, 2] float32 (out_i, out_j) pair weights —
    wj = 0 for the self pair (count once) and masked d = P/2 orbits.
    Returns the slot-accumulated forces [k, block, 3] float32.
    """
    k, block, feat = quorum.shape
    assert feat == 4, quorum.shape
    n_pairs = lo.shape[0]
    assert hi.shape == (n_pairs,) and w.shape == (n_pairs, 2), (hi.shape, w.shape)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # lo, hi drive the index maps
        grid=(n_pairs,),
        in_specs=[
            pl.BlockSpec((None, block, 4), lambda p, lo, hi: (lo[p], 0, 0)),
            pl.BlockSpec((None, 4, block), lambda p, lo, hi: (hi[p], 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),    # w: [n_pairs, 2]
        ],
        out_specs=pl.BlockSpec((k, 3, block), lambda p, lo, hi: (0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((k, 3, block), jnp.float32)],
    )
    ins = (jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32),
           quorum, jnp.swapaxes(quorum, 1, 2), jnp.asarray(w, jnp.float32))
    out = pl.pallas_call(
        functools.partial(_nbody_batch_kernel, n_pairs=n_pairs,
                          softening=softening),
        grid_spec=grid_spec,
        out_shape=out_struct((k, 3, block), jnp.float32, *ins),
        interpret=interpret,
    )(*ins)
    return jnp.swapaxes(out, 1, 2)
