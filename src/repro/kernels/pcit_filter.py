"""Pallas TPU kernel: PCIT significance filter (phase-4 hot spot).

For a pair tile (x-block, y-block) the filter reduces over ALL z genes:
  explained(x, y, z) = |r_xy| <= |eps * r_xz|  AND  |r_xy| <= |eps * r_yz|
  keep(x, y) = NOT OR_z explained(x, y, z)

The z axis is the long one (N = P * block genes), so the kernel tiles z into
BZ-wide VMEM strips and OR-accumulates into an int32 tile, visiting
(i, j, z-tile) grid cells with the z dimension innermost (sequential on TPU,
so the accumulator lives in the revisited output block).

VMEM per step: rows_x (BM, BZ) + rows_y (BN, BZ) + r_xy (BM, BN) + out
(BM, BN) in fp32/int32 — with BM = BN = 128, BZ = 512: ~0.8 MB.

The (BM, BN, BZ) broadcast intermediate stays in VREGs/VMEM as an
elementwise fused loop over the BZ lanes (no materialized cube in HBM —
exactly the restructuring [6] did for Xeon-Phi, here for the VPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import out_struct

EPS = 1e-12

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BZ = 512


def _pcit_kernel(rxy_ref, rowsx_ref, rowsy_ref, gx_ref, gy_ref,
                 out_ref, *, n_z: int, bz: int):
    zi = pl.program_id(2)

    @pl.when(zi == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rxy = rxy_ref[...][:, :, None].astype(jnp.float32)     # [BM, BN, 1]
    rxz = rowsx_ref[...][:, None, :].astype(jnp.float32)   # [BM, 1, BZ]
    ryz = rowsy_ref[...][None, :, :].astype(jnp.float32)   # [1, BN, BZ]

    den_z = jnp.sqrt(jnp.maximum((1 - rxz ** 2) * (1 - ryz ** 2), EPS))
    rxy_z = (rxy - rxz * ryz) / den_z
    den_y = jnp.sqrt(jnp.maximum((1 - rxy ** 2) * (1 - ryz ** 2), EPS))
    rxz_y = (rxz - rxy * ryz) / den_y
    den_x = jnp.sqrt(jnp.maximum((1 - rxy ** 2) * (1 - rxz ** 2), EPS))
    ryz_x = (ryz - rxy * rxz) / den_x

    eps = (rxy_z / (rxy + EPS) + rxz_y / (rxz + EPS) + ryz_x / (ryz + EPS)) / 3.0
    explained = ((jnp.abs(rxy) <= jnp.abs(eps * rxz))
                 & (jnp.abs(rxy) <= jnp.abs(eps * ryz)))

    z_ids = zi * bz + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bz), 2)
    gx = gx_ref[...][:, None, None]
    gy = gy_ref[...][None, :, None]
    explained &= (z_ids != gx) & (z_ids != gy)

    out_ref[...] |= jnp.any(explained, axis=-1).astype(jnp.int32)


def pcit_filter_pallas(r_xy, rows_x, rows_y, gx, gy, *,
                       bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                       bz: int = DEFAULT_BZ, interpret: bool = False):
    """r_xy: [M, N]; rows_x: [M, Z]; rows_y: [N, Z]; gx: [M]; gy: [N] int32.

    Returns keep [M, N] bool.
    """
    M, N = r_xy.shape
    Z = rows_x.shape[1]
    bm, bn, bz = min(bm, M), min(bn, N), min(bz, Z)
    assert M % bm == 0 and N % bn == 0 and Z % bz == 0, (M, N, Z, bm, bn, bz)
    n_z = Z // bz

    explained = pl.pallas_call(
        functools.partial(_pcit_kernel, n_z=n_z, bz=bz),
        grid=(M // bm, N // bn, n_z),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, z: (i, j)),
            pl.BlockSpec((bm, bz), lambda i, j, z: (i, z)),
            pl.BlockSpec((bn, bz), lambda i, j, z: (j, z)),
            pl.BlockSpec((bm,), lambda i, j, z: (i,)),
            pl.BlockSpec((bn,), lambda i, j, z: (j,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, z: (i, j)),
        out_shape=out_struct((M, N), jnp.int32, r_xy, rows_x, rows_y, gx, gy),
        interpret=interpret,
    )(r_xy, rows_x, rows_y, gx, gy)
    keep = explained == 0
    # diagonal (x == y) trivially kept
    return keep | (gx[:, None] == gy[None, :])
