"""PCIT (partial correlation + information theory) — the paper's section 5 app.

Pipeline (all inside one shard_map over the quorum axis):

  phase 1  quorum-gather standardized expression blocks  (k ppermutes,
           k*N/P*G floats resident — the paper's O(N/sqrt(P)) array)
  phase 2  per owned block pair: correlation tile  r[Bx, By] = Xs_x @ Xs_y^T
           (Pallas pairwise_corr kernel on TPU)
  phase 3  tile->row assembly: local strip writes + quorum_scatter(sum) give
           each block owner its full correlation rows R_b [block, N];
           quorum_gather hands every device the rows of its k quorum blocks
           (k*N/P*N floats — the N^2/sqrt(P) phase-2 footprint, vs N^2
           single-node)
  phase 4  per owned pair: PCIT significance filter over all z
           (Pallas pcit_filter kernel), then the same strip/scatter route
           returns the boolean adjacency strip to each block owner.

Oracle: ``pcit_reference`` — direct O(N^3) numpy implementation of
Reverter & Chan (2008) as described in the paper's refs [5, 6].
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.allpairs import (env_mode_override, mark_varying,
                             pair_mask_table, pair_ready_order,
                             quorum_gather, quorum_scatter)
from ..core.scheduler import PairSchedule, build_schedule

EPS = 1e-12


# ---------------------------------------------------------------------------
# Reference implementation (numpy, single node)
# ---------------------------------------------------------------------------

def standardize(X: np.ndarray) -> np.ndarray:
    """Rows -> zero mean, unit norm, so corr = Xs @ Xs.T exactly."""
    Xc = X - X.mean(axis=1, keepdims=True)
    nrm = np.linalg.norm(Xc, axis=1, keepdims=True)
    return Xc / np.maximum(nrm, EPS)

def correlation_reference(X: np.ndarray) -> np.ndarray:
    """Numpy correlation-matrix oracle over standardized rows."""
    Xs = standardize(X)
    return Xs @ Xs.T


def pcit_reference(X: np.ndarray) -> np.ndarray:
    """Direct PCIT: keep[x, y] iff no z explains the (x, y) correlation.

    For each trio (x, y, z):
      r_xy.z = (r_xy - r_xz r_yz) / sqrt((1-r_xz^2)(1-r_yz^2))
      eps    = (r_xy.z/r_xy + r_xz.y/r_xz + r_yz.x/r_yz) / 3
      edge (x, y) is explained by z if |r_xy| <= |eps * r_xz| and
                                       |r_xy| <= |eps * r_yz|.
    """
    r = correlation_reference(X)
    N = r.shape[0]
    keep = np.ones((N, N), bool)

    def pc(a, b, c):  # r_ab.c
        den = np.sqrt(max((1 - r[a, c] ** 2) * (1 - r[b, c] ** 2), EPS))
        return (r[a, b] - r[a, c] * r[b, c]) / den

    for x in range(N):
        for y in range(N):
            if x == y:
                continue
            for z in range(N):
                if z == x or z == y:
                    continue
                rxy_z = pc(x, y, z)
                rxz_y = pc(x, z, y)
                ryz_x = pc(y, z, x)
                eps = (rxy_z / (r[x, y] + EPS) + rxz_y / (r[x, z] + EPS)
                       + ryz_x / (r[y, z] + EPS)) / 3.0
                if (abs(r[x, y]) <= abs(eps * r[x, z])
                        and abs(r[x, y]) <= abs(eps * r[y, z])):
                    keep[x, y] = False
                    break
    np.fill_diagonal(keep, True)
    return keep


# ---------------------------------------------------------------------------
# Vectorized tile primitives (jnp reference path; Pallas kernels in
# repro.kernels are drop-in replacements for TPU)
# ---------------------------------------------------------------------------

def corr_tile(xs_i: jax.Array, xs_j: jax.Array) -> jax.Array:
    """Correlation tile between standardized blocks [bm, G] x [bn, G]."""
    return xs_i @ xs_j.T


def pcit_tile(r_xy: jax.Array, rows_x: jax.Array, rows_y: jax.Array,
              gx: jax.Array, gy: jax.Array) -> jax.Array:
    """PCIT keep-mask for one tile.

    r_xy:  [bm, bn] direct correlations of the pair tile.
    rows_x:[bm, N] correlation rows of the x block; rows_y: [bn, N].
    gx/gy: [bm]/[bn] global gene ids (to exclude z == x / z == y).
    Returns keep [bm, bn] bool.
    """
    N = rows_x.shape[-1]
    rxz = rows_x[:, None, :]            # [bm, 1, N]
    ryz = rows_y[None, :, :]            # [1, bn, N]
    rxy = r_xy[:, :, None]              # [bm, bn, 1]

    den_z = jnp.sqrt(jnp.maximum((1 - rxz ** 2) * (1 - ryz ** 2), EPS))
    rxy_z = (rxy - rxz * ryz) / den_z
    den_y = jnp.sqrt(jnp.maximum((1 - rxy ** 2) * (1 - ryz ** 2), EPS))
    rxz_y = (rxz - rxy * ryz) / den_y
    den_x = jnp.sqrt(jnp.maximum((1 - rxy ** 2) * (1 - rxz ** 2), EPS))
    ryz_x = (ryz - rxy * rxz) / den_x

    eps = (rxy_z / (rxy + EPS) + rxz_y / (rxz + EPS) + ryz_x / (ryz + EPS)) / 3.0
    explained = ((jnp.abs(rxy) <= jnp.abs(eps * rxz))
                 & (jnp.abs(rxy) <= jnp.abs(eps * ryz)))
    z_ids = jnp.arange(N)[None, None, :]
    valid_z = (z_ids != gx[:, None, None]) & (z_ids != gy[None, :, None])
    explained &= valid_z
    keep = ~jnp.any(explained, axis=-1)
    # diagonal (x == y) pairs are trivially kept
    keep |= (gx[:, None] == gy[None, :])
    return keep


# ---------------------------------------------------------------------------
# Distributed quorum PCIT (runs inside shard_map over axis `axis_name`)
# ---------------------------------------------------------------------------

def _tile_strips(make_tile, source: jax.Array, *, schedule: PairSchedule,
                 axis_name: str, mask: jax.Array, mode: str, out_dtype):
    """Gather ``source`` [block, F] over the quorum and assemble the masked
    per-slot [block, N] tile strips (DESIGN.md 3.2 strip assembly), with the
    engine's execution modes:

      * ``scan``    — serial lax.scan with a stacked [k, block, N] carry and
        dynamic slot indexing (low-memory oracle),
      * ``batched`` — unrolled static loop over the pre-gathered stack; slot
        ids become static so every tile is an independent op for XLA,
      * ``overlap`` — tiles computed as their later block lands in the
        gather, hiding the ppermutes behind tile compute.

    ``make_tile(lo_blk, hi_blk, glo, ghi) -> [block, block]``.  Tile layout:
    the (lo, hi) pair's tile lands at strip[lo][:, ghi*block:...] and its
    transpose accumulates at strip[hi][:, glo*block:...] (self pairs write
    once — the transpose write would double the diagonal tile).
    Returns [k, block, N] (scan) or a per-slot list (unrolled modes); both
    are accepted by quorum_scatter.
    """
    P, k, n_pairs = schedule.P, schedule.k, schedule.n_pairs
    block = source.shape[0]
    N = P * block
    i = lax.axis_index(axis_name)
    lo_np = schedule.pair_slots[:, 0]
    hi_np = schedule.pair_slots[:, 1]

    if mode == "scan":
        xq = quorum_gather(source, schedule, axis_name)
        shifts = jnp.asarray(schedule.shifts, jnp.int32)
        strips = mark_varying(jnp.zeros((k, block, N), out_dtype), axis_name)

        def body(strips, inp):
            lo, hi, w = inp
            glo = (i + jnp.take(shifts, lo)) % P
            ghi = (i + jnp.take(shifts, hi)) % P
            tile = (make_tile(jnp.take(xq, lo, axis=0),
                              jnp.take(xq, hi, axis=0), glo, ghi)
                    * w).astype(out_dtype)
            strips = lax.dynamic_update_slice(strips, tile[None],
                                              (lo, 0, ghi * block))
            tile_t = jnp.where(lo == hi, jnp.zeros_like(tile), tile.T)
            cur = lax.dynamic_slice(strips, (hi, 0, glo * block),
                                    (1, block, block))
            strips = lax.dynamic_update_slice(strips, cur + tile_t[None],
                                              (hi, 0, glo * block))
            return strips, None

        strips, _ = lax.scan(body, strips,
                             (jnp.asarray(lo_np), jnp.asarray(hi_np), mask))
        return strips

    # unrolled modes: per-slot strip list, static slot ids
    strip: list = [None] * k

    def get(slot):
        if strip[slot] is None:
            strip[slot] = mark_varying(jnp.zeros((block, N), out_dtype), axis_name)
        return strip[slot]

    def compute(idx, blocks):
        lo, hi = int(lo_np[idx]), int(hi_np[idx])
        glo = (i + int(schedule.shifts[lo])) % P
        ghi = (i + int(schedule.shifts[hi])) % P
        tile = (make_tile(blocks[lo], blocks[hi], glo, ghi)
                * mask[idx]).astype(out_dtype)
        strip[lo] = lax.dynamic_update_slice(get(lo), tile, (0, ghi * block))
        if lo != hi:  # self pair: the transpose write would double the tile
            cur = lax.dynamic_slice(get(hi), (0, glo * block), (block, block))
            strip[hi] = lax.dynamic_update_slice(get(hi), cur + tile.T,
                                                 (0, glo * block))

    if mode == "overlap":
        ready = pair_ready_order(schedule)
        landed: list = []

        def on_land(slot, blk):
            landed.append(blk)
            for idx in ready[slot]:
                compute(idx, landed)

        quorum_gather(source, schedule, axis_name, overlap_fn=on_land)
    else:  # batched
        xq = quorum_gather(source, schedule, axis_name)
        blocks = [xq[s] for s in range(k)]
        for idx in range(n_pairs):
            compute(idx, blocks)
    return [get(s) for s in range(k)]


def quorum_pcit_local(xs_block: jax.Array, mask: jax.Array, *,
                      schedule: PairSchedule, axis_name: str,
                      use_kernels: bool = False,
                      mode: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """Per-device body.  xs_block: [block, G] standardized rows (this
    device's dataset block); mask: [n_pairs] dedup mask (pair_mask_table row).
    ``mode``: engine execution mode for both tile phases (see _tile_strips);
    ``auto`` unrolls (batched) while the static pair count is small and falls
    back to the serial scan beyond that.

    Returns (corr_rows [block, N], keep_rows [block, N]) for the local block.
    """
    if use_kernels:
        from ..kernels import ops as kops
        _corr = kops.pairwise_corr
        _pcit = kops.pcit_filter
    else:
        _corr, _pcit = corr_tile, pcit_tile

    if mode == "auto":
        # env override first (same A/B hook as the engine), then: unroll
        # while the static pair count is small, serial scan beyond that
        mode = env_mode_override() or (
            "batched" if schedule.n_pairs <= 32 else "scan")
    if mode not in ("scan", "batched", "overlap"):
        raise ValueError(f"unknown mode {mode!r}")

    P = schedule.P
    block = xs_block.shape[0]
    mask = mask.reshape(-1)
    base_ids = jnp.arange(block)

    # ---- phase 2+3: correlation tiles -> row strips ----------------------
    strips = _tile_strips(lambda bx, by, glo, ghi: _corr(bx, by),
                          xs_block, schedule=schedule, axis_name=axis_name,
                          mask=mask, mode=mode, out_dtype=xs_block.dtype)
    corr_rows = quorum_scatter(strips, schedule, axis_name)   # [block, N]

    # ---- phase 4: PCIT filter tiles -> keep strips -----------------------
    # (the _tile_strips gather hands every device the corr rows of its k
    # quorum blocks — the N^2/sqrt(P) phase footprint vs N^2 single-node)
    def pcit_make(rows_x, rows_y, glo, ghi):
        r_xy = lax.dynamic_slice(rows_x, (0, ghi * block), (block, block))
        gx = glo * block + base_ids
        gy = ghi * block + base_ids
        return _pcit(r_xy, rows_x, rows_y, gx, gy).astype(jnp.float32)

    keep_strips = _tile_strips(pcit_make, corr_rows, schedule=schedule,
                               axis_name=axis_name, mask=mask, mode=mode,
                               out_dtype=jnp.float32)
    keep_rows = quorum_scatter(keep_strips, schedule, axis_name) > 0.5
    return corr_rows, keep_rows


def run_quorum_pcit(X: np.ndarray, mesh, axis_name: str = "q",
                    use_kernels: bool = False, mode: str = "auto"):
    """Driver: standardize on host, shard rows, run the quorum pipeline.

    X: [N, G] expression matrix; N must divide by the mesh axis size.
    ``mode``: engine execution mode for the tile phases (see _tile_strips).
    Returns (corr [N, N], keep [N, N]) gathered to host.
    """
    from jax.sharding import PartitionSpec as PS
    P = mesh.shape[axis_name]
    N = X.shape[0]
    assert N % P == 0, (N, P)
    sched = build_schedule(P)
    masks = pair_mask_table(sched)
    Xs = standardize(np.asarray(X, np.float32))

    def body(xb, mb):
        return quorum_pcit_local(xb, mb, schedule=sched, axis_name=axis_name,
                                 use_kernels=use_kernels, mode=mode)

    from ..kernels import ops as kops
    fn = jax.jit(jax.shard_map(body, mesh=mesh,
                               in_specs=(PS(axis_name), PS(axis_name)),
                               out_specs=(PS(axis_name), PS(axis_name)),
                               check_vma=kops.shard_map_check_vma(
                                   use_kernels)))
    corr, keep = fn(Xs, masks)
    return np.asarray(corr), np.asarray(keep)
