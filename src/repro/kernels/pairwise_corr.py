"""Pallas TPU kernel: correlation tile of standardized gene blocks.

The PCIT phase-2 hot spot ([6] optimized this loop for Xeon-Phi; on TPU it is
an MXU matmul).  C[bm, bn] = Xs_i [bm, G] @ Xs_j [bn, G]^T, tiled so each
(BM, BK) x (BN, BK) working set sits in VMEM and the contraction accumulates
in a float32 VMEM scratch across the k-grid dimension.

Tile choice (v5e): BM = BN = 256, BK = 512 -> VMEM use
(256*512 + 256*512 + 256*256) * 4B ~= 1.3 MB of ~16 MB/core, and all matmul
dims are multiples of the 128x128 MXU tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import out_struct

DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512


def _corr_kernel(x_ref, y_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], y_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def pairwise_corr_pallas(xs_i: jax.Array, xs_j: jax.Array, *,
                         bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                         bk: int = DEFAULT_BK, interpret: bool = False):
    """xs_i: [M, G], xs_j: [N, G] standardized rows -> corr tile [M, N]."""
    M, G = xs_i.shape
    N, G2 = xs_j.shape
    assert G == G2, (G, G2)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, G)
    assert M % bm == 0 and N % bn == 0 and G % bk == 0, (M, N, G, bm, bn, bk)
    n_k = G // bk

    return pl.pallas_call(
        functools.partial(_corr_kernel, n_k=n_k),
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=out_struct((M, N), jnp.float32, xs_i, xs_j),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xs_i, xs_j)
