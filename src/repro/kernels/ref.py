"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-12
NEG_INF = -1e30
# shared "no candidate" index sentinel for every top-k path (query engine,
# fused kernel, jnp oracle) — cross-path index agreement depends on all of
# them using this exact value
IDX_SENTINEL = np.int32(np.iinfo(np.int32).max)
QUERY_METRICS = ("dot", "l2")
# Every f32 scoring dot states its precision.  At DEFAULT precision the
# TPU runs an f32 matmul as one bf16 pass, which reorders near-tied
# candidates and silently breaks the engine's index-exact contract; the
# CPU backend computes f32 either way.
PRECISION = jax.lax.Precision.HIGHEST
# relative float32-accumulation slack folded into the certified
# quantization error bound (core/quant.py; DESIGN.md section 17) — a few
# hundred ulps, orders of magnitude above what a <=2^13-term f32 dot can
# actually accumulate at the repo's block sizes, and still orders of
# magnitude below any real quantization error
FP_REL = 1e-6


def vma_of(*xs) -> frozenset:
    """The mesh axes any of ``xs`` varies over inside ``shard_map`` (empty
    outside one) — what a ``pallas_call`` output or a scan carry built
    from constants must be typed with under ``check_vma``."""
    out = frozenset()
    for x in xs:
        out |= jax.typeof(x).vma
    return out


def out_struct(shape, dtype, *inputs) -> jax.ShapeDtypeStruct:
    """A kernel output shape, varying over the axes its inputs vary over."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma_of(*inputs))


def varying_like(x, *refs):
    """Type the constant ``x`` as varying over the axes ``refs`` vary over
    (a scan's initial carry must match its body's output type)."""
    vma = tuple(sorted(vma_of(*jax.tree.leaves(refs))))
    if not vma:
        return x
    return jax.tree.map(lambda a: jax.lax.pcast(a, vma, to="varying"), x)


def quant_eps_tile(delta_lo, delta_hi, l1_lo, l1_hi, *, dim: int,
                   metric: str = "dot") -> jax.Array:
    """Certified per-entry error bound of one quantized score tile
    (DESIGN.md section 17.2).

    For rows quantized with per-block steps ``delta`` (max per-entry
    rounding error) and f32 row L1 norms ``l1``,

      |s_q - s_f32| <= d_lo*l1_hi + d_hi*l1_lo + 3*dim*d_lo*d_hi
                       + FP_REL*(l1_lo*l1_hi + 1)

    per (row, col) entry; the ``3*dim*d_lo*d_hi`` term absorbs the
    |x_hat|_1 <= |x|_1 + dim*delta slack of bounding via the quantized
    operands, and the FP_REL term covers f32 accumulation order.  L2
    scores are ``2*dot - |a|^2 - |b|^2`` with exact f32 norms carried as
    side arrays, so their bound is exactly twice the dot bound.

    delta_lo/delta_hi: scalars (or [1]); l1_lo/l1_hi: [block] f32.
    Returns the [block, block] bound, rows = lo side, cols = hi side.
    """
    delta_lo = jnp.asarray(delta_lo, jnp.float32).reshape(())
    delta_hi = jnp.asarray(delta_hi, jnp.float32).reshape(())
    eps = (delta_lo * l1_hi[None, :] + delta_hi * l1_lo[:, None]
           + 3.0 * dim * delta_lo * delta_hi
           + FP_REL * (l1_lo[:, None] * l1_hi[None, :] + 1.0))
    if metric == "l2":
        eps = 2.0 * eps
    return eps


def pairwise_corr(xs_i: jax.Array, xs_j: jax.Array) -> jax.Array:
    """Correlation tile of standardized blocks: [bm, G] x [bn, G] -> [bm, bn]."""
    return jnp.dot(xs_i, xs_j.T, precision=PRECISION)


def pcit_filter(r_xy, rows_x, rows_y, gx, gy) -> jax.Array:
    """PCIT keep-mask oracle — mirrors apps.pcit.pcit_tile."""
    rxz = rows_x[:, None, :]
    ryz = rows_y[None, :, :]
    rxy = r_xy[:, :, None]
    den_z = jnp.sqrt(jnp.maximum((1 - rxz ** 2) * (1 - ryz ** 2), EPS))
    rxy_z = (rxy - rxz * ryz) / den_z
    den_y = jnp.sqrt(jnp.maximum((1 - rxy ** 2) * (1 - ryz ** 2), EPS))
    rxz_y = (rxz - rxy * ryz) / den_y
    den_x = jnp.sqrt(jnp.maximum((1 - rxy ** 2) * (1 - rxz ** 2), EPS))
    ryz_x = (ryz - rxy * rxz) / den_x
    eps = (rxy_z / (rxy + EPS) + rxz_y / (rxz + EPS) + ryz_x / (ryz + EPS)) / 3.0
    explained = ((jnp.abs(rxy) <= jnp.abs(eps * rxz))
                 & (jnp.abs(rxy) <= jnp.abs(eps * ryz)))
    N = rows_x.shape[-1]
    z_ids = jnp.arange(N)[None, None, :]
    explained &= (z_ids != gx[:, None, None]) & (z_ids != gy[None, :, None])
    keep = ~jnp.any(explained, axis=-1)
    keep |= gx[:, None] == gy[None, :]
    return keep


def pairwise_batch_forces(quorum, lo, hi, wi, wj, *,
                          softening: float = 1e-2) -> jax.Array:
    """Batched n-body slot accumulation oracle (kernels/pairwise_batch.py).

    quorum: [k, block, 4] (x, y, z, mass); lo/hi: [n_pairs] slot ids;
    wi/wj: [n_pairs] per-side weights.  Returns [k, block, 3] float32.
    """
    def pair(bi, bj):
        pi, mi = bi[:, :3], bi[:, 3]
        pj, mj = bj[:, :3], bj[:, 3]
        d = pj[None, :, :] - pi[:, None, :]
        r2 = jnp.sum(d * d, axis=-1) + softening
        inv_r3 = jax.lax.rsqrt(r2) / r2
        w = (mi[:, None] * mj[None, :] * inv_r3)[..., None]
        f_ij = w * d
        return jnp.sum(f_ij, axis=1), -jnp.sum(f_ij, axis=0)

    lo = jnp.asarray(lo, jnp.int32)
    hi = jnp.asarray(hi, jnp.int32)
    out_i, out_j = jax.vmap(pair)(jnp.take(quorum, lo, axis=0).astype(jnp.float32),
                                  jnp.take(quorum, hi, axis=0).astype(jnp.float32))
    data = jnp.concatenate([out_i * wi[:, None, None],
                            out_j * wj[:, None, None]], axis=0)
    ids = jnp.concatenate([lo, hi])
    return jax.ops.segment_sum(data, ids, num_segments=quorum.shape[0])


def query_topk(stack, queries, mask, gidx, *, topk: int,
               metric: str = "dot"):
    """Fused query-scoring top-k oracle (kernels/query_score.py).

    stack: [k, block, d]; queries: [Q, d]; mask: [k, block] (1 = score the
    row); gidx: [k, block] int32 global row ids.  Selection is by the
    (-score, index) total order; masked rows become (NEG_INF, int32 max)
    sentinels.  Returns (values [Q, topk] f32, indices [Q, topk] i32).
    """
    if metric not in QUERY_METRICS:
        raise ValueError(f"metric must be one of {QUERY_METRICS}, "
                         f"got {metric!r}")
    sent = jnp.int32(IDX_SENTINEL)
    k, block, d = stack.shape
    Q = queries.shape[0]
    stack = stack.astype(jnp.float32)
    queries = queries.astype(jnp.float32)
    s = jnp.einsum("qd,sbd->qsb", queries, stack, precision=PRECISION)
    if metric == "l2":
        s = (2.0 * s - jnp.sum(stack * stack, axis=-1)[None]
             - jnp.sum(queries * queries, axis=-1)[:, None, None])
    valid = jnp.asarray(mask) > 0
    s = jnp.where(valid[None], s, NEG_INF).reshape(Q, k * block)
    ids = jnp.where(valid, jnp.asarray(gidx, jnp.int32), sent)
    ids = jnp.broadcast_to(ids.reshape(-1)[None], (Q, k * block))
    n = k * block
    if n < topk:
        s = jnp.pad(s, ((0, 0), (0, topk - n)), constant_values=NEG_INF)
        ids = jnp.pad(ids, ((0, 0), (0, topk - n)), constant_values=sent)
    sv, si = jax.lax.sort((-s, ids), num_keys=2)
    return -sv[:, :topk], si[:, :topk]


def pairwise_threshold(quorum, lo, hi, meta, *, threshold: float,
                       capacity: int, block_rows: int, metric: str = "dot"):
    """Thresholded sparse-join compaction oracle
    (kernels/pairwise_threshold.py; DESIGN.md section 11).

    quorum: [k, block, d]; lo/hi: [n_pairs] slot ids; meta: [n_pairs, 6]
    int32 rows ``(active, is_self, ga, gb, nv_lo, nv_hi)`` — tile skip
    flag (prefilter x dedup mask), self-pair flag, the two global block
    ids, and the two valid-row counts.  Emits each passing entry's
    ``(score, min_gid, max_gid)`` with ``gid = g * block_rows + row``,
    compacted in (pair-major, row-major) order into [capacity] buffers;
    entries past capacity are dropped while the returned count keeps the
    true total (the overflow contract).  Returns
    ``(vals f32 [capacity], i i32 [capacity], j i32 [capacity],
    count i32 [])``; unused slots are (NEG_INF, IDX_SENTINEL).
    """
    if metric not in ("dot", "l2"):
        raise ValueError(f"metric must be one of ('dot', 'l2'), "
                         f"got {metric!r}")
    quorum = quorum.astype(jnp.float32)
    lo = jnp.asarray(lo, jnp.int32)
    hi = jnp.asarray(hi, jnp.int32)
    meta = jnp.asarray(meta, jnp.int32)
    lhs = jnp.take(quorum, lo, axis=0)          # [n_pairs, block, d]
    rhs = jnp.take(quorum, hi, axis=0)
    dots = jnp.einsum("pbd,pcd->pbc", lhs, rhs, precision=PRECISION)
    if metric == "l2":
        scores = (2.0 * dots
                  - jnp.sum(rhs * rhs, axis=-1)[:, None, :]
                  - jnp.sum(lhs * lhs, axis=-1)[:, :, None])
    else:
        scores = dots
    active, is_self, ga, gb, nv_lo, nv_hi = (meta[:, c] for c in range(6))
    r = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    s = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
    keep = (scores >= threshold) & (active == 1)[:, None, None]
    keep &= (r < nv_lo[:, None, None]) & (s < nv_hi[:, None, None])
    keep &= jnp.where((is_self == 1)[:, None, None], r < s, True)
    gi = ga[:, None, None] * block_rows + r
    gj = gb[:, None, None] * block_rows + s
    ei = jnp.minimum(gi, gj).reshape(-1)
    ej = jnp.maximum(gi, gj).reshape(-1)
    keep = keep.reshape(-1)
    vals = scores.reshape(-1).astype(jnp.float32)
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    pos = jnp.where(keep, pos, capacity)
    count = jnp.sum(keep.astype(jnp.int32))
    vbuf = jnp.full((capacity,), NEG_INF, jnp.float32
                    ).at[pos].set(vals, mode="drop")
    ibuf = jnp.full((capacity,), jnp.int32(IDX_SENTINEL)
                    ).at[pos].set(ei, mode="drop")
    jbuf = jnp.full((capacity,), jnp.int32(IDX_SENTINEL)
                    ).at[pos].set(ej, mode="drop")
    used = jnp.arange(capacity) < count
    return (jnp.where(used, vbuf, NEG_INF),
            jnp.where(used, ibuf, jnp.int32(IDX_SENTINEL)),
            jnp.where(used, jbuf, jnp.int32(IDX_SENTINEL)),
            count)


def pairwise_threshold_q(q, scale, delta, l1, sq, lo, hi, meta, *,
                         threshold: float, capacity: int, block_rows: int,
                         metric: str = "dot"):
    """Quantized sparse-join compaction oracle with the widened keep band
    (kernels/pairwise_batch_q.py; DESIGN.md section 17.3).

    q: [k, block, d] int8 or bf16 quantized blocks; scale/delta: [k] (or
    [k, 1]) f32 per-block dequant scale and rounding step; l1/sq: [k,
    block] f32 row L1 norms and exact squared L2 norms of the *original*
    f32 rows; lo/hi/meta as in :func:`pairwise_threshold`.  Scores are
    the dequantized ``(qi_f32 @ qj_f32.T) * (s_lo * s_hi)`` (l2: ``(2 s -
    sq_hi) - sq_lo`` with the exact norms), and an entry is emitted when
    ``s_q >= threshold - eps`` with eps from :func:`quant_eps_tile` — the
    sound over-approximation the host-side exact rescoring pass then
    resolves.  Buffer layout, compaction order, overflow contract, and
    sentinels match :func:`pairwise_threshold` exactly.
    """
    if metric not in QUERY_METRICS:
        raise ValueError(f"metric must be one of {QUERY_METRICS}, "
                         f"got {metric!r}")
    qf = jnp.asarray(q).astype(jnp.float32)
    d = qf.shape[-1]
    scale = jnp.asarray(scale, jnp.float32).reshape(-1)
    delta = jnp.asarray(delta, jnp.float32).reshape(-1)
    l1 = jnp.asarray(l1, jnp.float32)
    sq = jnp.asarray(sq, jnp.float32)
    lo = jnp.asarray(lo, jnp.int32)
    hi = jnp.asarray(hi, jnp.int32)
    meta = jnp.asarray(meta, jnp.int32)
    lhs = jnp.take(qf, lo, axis=0)              # [n_pairs, block, d]
    rhs = jnp.take(qf, hi, axis=0)
    s_lo = jnp.take(scale, lo)                  # [n_pairs]
    s_hi = jnp.take(scale, hi)
    dots = (jnp.einsum("pbd,pcd->pbc", lhs, rhs, precision=PRECISION)
            * (s_lo * s_hi)[:, None, None])
    if metric == "l2":
        scores = (2.0 * dots
                  - jnp.take(sq, hi, axis=0)[:, None, :]) \
            - jnp.take(sq, lo, axis=0)[:, :, None]
    else:
        scores = dots
    d_lo = jnp.take(delta, lo)[:, None, None]
    d_hi = jnp.take(delta, hi)[:, None, None]
    l1_lo = jnp.take(l1, lo, axis=0)[:, :, None]
    l1_hi = jnp.take(l1, hi, axis=0)[:, None, :]
    eps = (d_lo * l1_hi + d_hi * l1_lo + 3.0 * d * d_lo * d_hi
           + FP_REL * (l1_lo * l1_hi + 1.0))
    if metric == "l2":
        eps = 2.0 * eps
    active, is_self, ga, gb, nv_lo, nv_hi = (meta[:, c] for c in range(6))
    r = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    s = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
    keep = (scores >= threshold - eps) & (active == 1)[:, None, None]
    keep &= (r < nv_lo[:, None, None]) & (s < nv_hi[:, None, None])
    keep &= jnp.where((is_self == 1)[:, None, None], r < s, True)
    gi = ga[:, None, None] * block_rows + r
    gj = gb[:, None, None] * block_rows + s
    ei = jnp.minimum(gi, gj).reshape(-1)
    ej = jnp.maximum(gi, gj).reshape(-1)
    keep = keep.reshape(-1)
    vals = scores.reshape(-1).astype(jnp.float32)
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    pos = jnp.where(keep, pos, capacity)
    count = jnp.sum(keep.astype(jnp.int32))
    vbuf = jnp.full((capacity,), NEG_INF, jnp.float32
                    ).at[pos].set(vals, mode="drop")
    ibuf = jnp.full((capacity,), jnp.int32(IDX_SENTINEL)
                    ).at[pos].set(ei, mode="drop")
    jbuf = jnp.full((capacity,), jnp.int32(IDX_SENTINEL)
                    ).at[pos].set(ej, mode="drop")
    used = jnp.arange(capacity) < count
    return (jnp.where(used, vbuf, NEG_INF),
            jnp.where(used, ibuf, jnp.int32(IDX_SENTINEL)),
            jnp.where(used, jbuf, jnp.int32(IDX_SENTINEL)),
            count)


def pairwise_topk_q(q, scale, sq, lo, hi, meta, *, topk: int,
                    block_rows: int, metric: str = "dot"):
    """Quantized per-slot batch top-k oracle
    (kernels/pairwise_batch_q.py; DESIGN.md section 17.3).

    q: [k, block, d] int8 or bf16 quantized blocks; scale: [k] (or
    [k, 1]) f32 dequant scales; sq: [k, block] exact f32 squared row
    norms (l2 only); lo/hi/meta as in :func:`pairwise_topk`.  Tiles are
    the dequantized ``(qi_f32 @ qj_f32.T) * (s_lo * s_hi)`` with the l2
    orientation formulas substituting the exact norms; the merge order,
    sentinels, and output layout match :func:`pairwise_topk` exactly.
    No error band is applied here — the caller certifies and rescores
    the quantized lists host-side (core/quant.py).
    """
    if metric not in QUERY_METRICS:
        raise ValueError(f"metric must be one of {QUERY_METRICS}, "
                         f"got {metric!r}")
    qf = jnp.asarray(q).astype(jnp.float32)
    k, block, d = qf.shape
    scale = jnp.asarray(scale, jnp.float32).reshape(-1)
    sq = jnp.asarray(sq, jnp.float32)
    lo = jnp.asarray(lo, jnp.int32)
    hi = jnp.asarray(hi, jnp.int32)
    meta = jnp.asarray(meta, jnp.int32)
    sent = jnp.int32(IDX_SENTINEL)

    def merge(cv, ci, sv, si):
        v = jnp.concatenate([cv, sv], axis=-1)
        i = jnp.concatenate([ci, si], axis=-1)
        nv, ni = jax.lax.sort((-v, i), num_keys=2)
        return -nv[..., :topk], ni[..., :topk]

    def body(carry, inp):
        vals, idx = carry
        lo_p, hi_p, m = inp
        active, is_self, ga, gb, nv_lo, nv_hi = (m[c] for c in range(6))
        bi = jnp.take(qf, lo_p, axis=0)
        bj = jnp.take(qf, hi_p, axis=0)
        dots = (jnp.dot(bi, bj.T, precision=PRECISION)
                * (scale[lo_p] * scale[hi_p]))             # [block, block]
        if metric == "l2":
            bin2 = jnp.take(sq, lo_p, axis=0)
            bjn2 = jnp.take(sq, hi_p, axis=0)
            t_lo = (2.0 * dots - bjn2[None, :]) - bin2[:, None]
            t_hi = (2.0 * dots - bin2[:, None]) - bjn2[None, :]
        else:
            t_lo = t_hi = dots
        r = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
        s = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
        keep = ((active == 1) & (s < nv_hi)
                & jnp.where(is_self == 1, r != s, True))
        cv = jnp.where(keep, t_lo, NEG_INF)
        ci = jnp.where(keep, gb * block_rows + s, sent)
        mv, mi = merge(jnp.take(vals, lo_p, axis=0),
                       jnp.take(idx, lo_p, axis=0), cv, ci)
        vals = vals.at[lo_p].set(mv)
        idx = idx.at[lo_p].set(mi)
        keep_t = ((active == 1) & (is_self == 0) & (r < nv_lo)).T
        cv_t = jnp.where(keep_t, t_hi.T, NEG_INF)
        ci_t = jnp.where(keep_t, (ga * block_rows + r).T, sent)
        mv2, mi2 = merge(jnp.take(vals, hi_p, axis=0),
                         jnp.take(idx, hi_p, axis=0), cv_t, ci_t)
        vals = vals.at[hi_p].set(mv2)
        idx = idx.at[hi_p].set(mi2)
        return (vals, idx), None

    init = (jnp.full((k, block, topk), NEG_INF, jnp.float32),
            jnp.full((k, block, topk), sent, jnp.int32))
    init = varying_like(init, qf, scale, sq, lo, hi, meta)
    (vals, idx), _ = jax.lax.scan(body, init, (lo, hi, meta))
    return vals, idx


def pairwise_topk(quorum, lo, hi, meta, *, topk: int, block_rows: int,
                  metric: str = "dot"):
    """Per-slot batch top-k accumulation oracle (kernels/pairwise_topk.py;
    DESIGN.md section 12.3 — the k-NN graph workload's batched step).

    quorum: [k, block, d]; lo/hi: [n_pairs] slot ids; meta: [n_pairs, 6]
    int32 rows ``(active, is_self, ga, gb, nv_lo, nv_hi)`` — the item
    mask (ownership dedup), self-pair flag, the two global block ids,
    and the two valid-row counts.  For each scheduled tile the rows of
    the ``lo`` block receive the ``hi`` block's valid rows as neighbor
    candidates (and vice versa for non-self tiles; self tiles exclude
    the diagonal and contribute one side only), folded into per-slot
    running [k, block, topk] (value, index) lists under the (-score,
    index) total order.  The two orientations of an L2 tile use the
    orientation-consistent subtraction order ``(2 d - |cand|^2) -
    |row|^2`` so both match the host oracle's matrix bitwise.  Masked
    candidates are (NEG_INF, IDX_SENTINEL) sentinels.  Returns
    ``(vals f32 [k, block, topk], idx i32 [k, block, topk])``; rows
    beyond a block's valid count carry unspecified (sentinel-merged)
    lists — callers slice them off.
    """
    if metric not in QUERY_METRICS:
        raise ValueError(f"metric must be one of {QUERY_METRICS}, "
                         f"got {metric!r}")
    quorum = quorum.astype(jnp.float32)
    k, block, d = quorum.shape
    lo = jnp.asarray(lo, jnp.int32)
    hi = jnp.asarray(hi, jnp.int32)
    meta = jnp.asarray(meta, jnp.int32)
    sent = jnp.int32(IDX_SENTINEL)

    def merge(cv, ci, sv, si):
        v = jnp.concatenate([cv, sv], axis=-1)
        i = jnp.concatenate([ci, si], axis=-1)
        nv, ni = jax.lax.sort((-v, i), num_keys=2)
        return -nv[..., :topk], ni[..., :topk]

    def body(carry, inp):
        vals, idx = carry
        lo_p, hi_p, m = inp
        active, is_self, ga, gb, nv_lo, nv_hi = (m[c] for c in range(6))
        bi = jnp.take(quorum, lo_p, axis=0)
        bj = jnp.take(quorum, hi_p, axis=0)
        dots = jnp.dot(bi, bj.T, precision=PRECISION)     # [block, block]
        if metric == "l2":
            bin2 = jnp.sum(bi * bi, axis=-1)
            bjn2 = jnp.sum(bj * bj, axis=-1)
            t_lo = (2.0 * dots - bjn2[None, :]) - bin2[:, None]
            t_hi = (2.0 * dots - bin2[:, None]) - bjn2[None, :]
        else:
            t_lo = t_hi = dots
        r = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
        s = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
        # lo side: rows of bi receive bj's valid rows as candidates
        keep = ((active == 1) & (s < nv_hi)
                & jnp.where(is_self == 1, r != s, True))
        cv = jnp.where(keep, t_lo, NEG_INF)
        ci = jnp.where(keep, gb * block_rows + s, sent)
        mv, mi = merge(jnp.take(vals, lo_p, axis=0),
                       jnp.take(idx, lo_p, axis=0), cv, ci)
        vals = vals.at[lo_p].set(mv)
        idx = idx.at[lo_p].set(mi)
        # hi side (transposed orientation; self tiles contribute once)
        keep_t = ((active == 1) & (is_self == 0) & (r < nv_lo)).T
        cv_t = jnp.where(keep_t, t_hi.T, NEG_INF)
        ci_t = jnp.where(keep_t, (ga * block_rows + r).T, sent)
        mv2, mi2 = merge(jnp.take(vals, hi_p, axis=0),
                         jnp.take(idx, hi_p, axis=0), cv_t, ci_t)
        vals = vals.at[hi_p].set(mv2)
        idx = idx.at[hi_p].set(mi2)
        return (vals, idx), None

    init = (jnp.full((k, block, topk), NEG_INF, jnp.float32),
            jnp.full((k, block, topk), sent, jnp.int32))
    init = varying_like(init, quorum, lo, hi, meta)
    (vals, idx), _ = jax.lax.scan(body, init, (lo, hi, meta))
    return vals, idx


def flash_attention(q, k, v, *, causal: bool) -> jax.Array:
    """Plain attention oracle: q [B, Tq, H, hd], k/v [B, Tk, KV, hd]."""
    B, Tq, H, hd = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, Tq, KV, H // KV, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32) / math.sqrt(hd),
                   k.astype(jnp.float32))
    if causal:
        Tk = k.shape[1]
        msk = np.tril(np.ones((Tq, Tk), np.bool_), k=Tk - Tq)
        s = jnp.where(msk, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bkgqh", w, v.astype(jnp.float32))
    return o.reshape(B, H, Tq, hd).transpose(0, 2, 1, 3).astype(q.dtype)


def ssd_chunk(x, dt, A, Bm, Cm) -> jax.Array:
    """Sequential (non-chunked) SSD oracle.

    x: [B, T, H, P]; dt: [B, T, H]; A: [H]; Bm/Cm: [B, T, N].
    Returns y [B, T, H, P] (fp32).
    """
    Bb, T, H, Pd = x.shape
    N = Bm.shape[-1]

    def step(h, inp):
        xt, dtt, bt, ct = inp
        a = jnp.exp(dtt * A)                              # [B, H]
        h = a[:, :, None, None] * h + jnp.einsum(
            "bh,bn,bhp->bhnp", dtt, bt, xt)
        y = jnp.einsum("bn,bhnp->bhp", ct, h)
        return h, y

    h0 = jnp.zeros((Bb, H, N, Pd), jnp.float32)
    xs = (jnp.moveaxis(x, 1, 0).astype(jnp.float32),
          jnp.moveaxis(dt, 1, 0).astype(jnp.float32),
          jnp.moveaxis(Bm, 1, 0).astype(jnp.float32),
          jnp.moveaxis(Cm, 1, 0).astype(jnp.float32))
    _, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1)                         # [B, T, H, P]
