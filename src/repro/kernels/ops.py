"""Jit'd public entry points for the Pallas kernels (DESIGN.md section 6).

On TPU the kernels run compiled; on the CPU backend (tests) they run in
interpret mode, which executes the kernel body in Python with identical
block semantics — the per-kernel sweeps in tests/test_kernels.py validate
every (shape, dtype) cell against ref.py.  There is no fallback to the
oracles: a kernel that cannot lower raises, so a run that asked for the
kernel never silently measures the jnp path instead.  The dispatch is
covered directly in tests/test_ops_dispatch.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .flash_attention import flash_attention_pallas
from .pairwise_batch import pairwise_batch_pallas
from .pairwise_corr import pairwise_corr_pallas
from .pcit_filter import pcit_filter_pallas


def _interpret() -> bool:
    """True when the Pallas kernels should run in interpret mode: on the
    CPU backend only (tests).  On TPU, and on any other backend, they
    compile — the dispatch every jit'd entry point below routes through
    (DESIGN.md section 6)."""
    return jax.default_backend() == "cpu"


def shard_map_check_vma(use_kernel: bool) -> bool:
    """``check_vma`` for an engine ``shard_map`` whose body may launch
    these kernels.  The Pallas HLO interpreter that runs them on the CPU
    evaluates a kernel body without shard_map's varying-axis typing and
    is refused under it, so on CPU a kernel-carrying program runs
    unchecked; on TPU the kernels compile and the typing stays checked
    (tests/test_tpu_compile.py compiles the k-NN sweep that way).
    Programs without a kernel are always checked (DESIGN.md section 6)."""
    return not (use_kernel and _interpret())


def _join_kernel_lowers(name: str) -> None:
    """The quantized join kernel compacts hits with an in-tile cumsum and
    a [block^2, capacity] one-hot matmul: Mosaic has no cumsum lowering
    and the one-hot overruns VMEM at any real block, so on a compiling
    backend it raises here with the reason (ROADMAP S4)."""
    if jax.default_backend() != "cpu":
        raise NotImplementedError(
            f"{name}: the fused join kernel does not lower for "
            f"{jax.default_backend()} (in-tile cumsum compaction, "
            "ROADMAP S4); run the join with use_kernel=False")


def _pad_to(x, multiple, axis):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def pairwise_corr(xs_i, xs_j, *, bm=128, bn=128, bk=128):
    """Correlation tile [M, N] of standardized blocks [M, G] x [N, G]
    (PCIT phase 2; DESIGN.md section 6).

    Pads every dim up to the tile multiple and slices back, so arbitrary
    shapes are accepted (padded K columns are zeros — exact for the dot).
    """
    xs_i, M = _pad_to(xs_i, bm, 0)
    xs_j, N = _pad_to(xs_j, bn, 0)
    xs_i, _ = _pad_to(xs_i, bk, 1)
    xs_j, _ = _pad_to(xs_j, bk, 1)
    out = pairwise_corr_pallas(xs_i, xs_j, bm=bm, bn=bn, bk=bk,
                               interpret=_interpret())
    return out[:M, :N]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bz"))
def pcit_filter(r_xy, rows_x, rows_y, gx, gy, *, bm=128, bn=128, bz=128):
    """PCIT keep tile [M, N]; see kernels/pcit_filter.py (DESIGN.md
    section 6).

    Padded z columns get rows == 0 which yields eps ratios that never
    explain an edge with |r_xy| > 0; padded z ids are also >= N so the
    z-exclusion mask keeps them inert.  Padded x/y rows are sliced off.
    """
    (r_xy, M) = _pad_to(r_xy, bm, 0)
    (r_xy, N) = _pad_to(r_xy, bn, 1)
    rows_x, _ = _pad_to(rows_x, bm, 0)
    rows_y, _ = _pad_to(rows_y, bn, 0)
    rows_x, _ = _pad_to(rows_x, bz, 1)
    rows_y, _ = _pad_to(rows_y, bz, 1)
    # pad gene ids with sentinels that can't collide with real z indices
    def pad_ids(g, to):
        pad = to - g.shape[0]
        if pad:
            g = jnp.concatenate([g, jnp.full((pad,), -1, g.dtype)])
        return g
    gx = pad_ids(gx, rows_x.shape[0])
    gy = pad_ids(gy, rows_y.shape[0])
    out = pcit_filter_pallas(r_xy, rows_x, rows_y, gx, gy,
                             bm=bm, bn=bn, bz=bz, interpret=_interpret())
    return out[:M, :N]


@functools.partial(jax.jit, static_argnames=("softening",))
def pairwise_batch_forces(quorum, lo, hi, wi, wj, *, softening=1e-2):
    """Fused batched n-body step for the engine's ``batch_fn`` hook
    (DESIGN.md section 6).

    quorum: [k, block, 4]; lo/hi: [n_pairs] slot ids; wi/wj: [n_pairs]
    per-side pair weights (engine passes mask and self-zeroed mask).
    Returns slot-accumulated forces [k, block, 3] float32.

    Pads block up to the 8-sublane multiple with zero-mass bodies at the
    origin — exact, since zero mass contributes zero force either way —
    and slices back.
    """
    q, block = _pad_to(quorum, 8, 1)
    w = jnp.stack([jnp.asarray(wi, jnp.float32),
                   jnp.asarray(wj, jnp.float32)], axis=1)
    out = pairwise_batch_pallas(q, lo, hi, w, softening=softening,
                                interpret=_interpret())
    return out[:, :block]


@functools.partial(jax.jit, static_argnames=("topk", "metric"))
def query_topk(stack, queries, mask, gidx, *, topk, metric="dot"):
    """Fused serving scoring step for the query engine's ``batch_fn``
    hook (DESIGN.md section 9.3).

    stack: [k, block, d] quorum blocks; queries: [Q, d]; mask: [k, block]
    float (cover dedup x row validity); gidx: [k, block] int32 global row
    ids.  Returns (scores [Q, topk] f32, indices [Q, topk] i32) under the
    engine's (-score, index) order.

    Pads Q up to the 8-sublane multiple with zero queries and slices the
    padded rows back off — exact, the extra rows never leave the wrapper.
    """
    from .query_score import query_topk_pallas
    q, Q = _pad_to(queries, 8, 0)
    vals, idx = query_topk_pallas(stack, q, mask, gidx, topk=topk,
                                  metric=metric, interpret=_interpret())
    return vals[:Q], idx[:Q]


def pairwise_threshold(quorum, lo, hi, meta, *, threshold, capacity,
                       block_rows, metric="dot", slot=None):
    """Fused thresholded-join step for the sparse engine's ``batch_fn``
    hook (core/sparse.py; DESIGN.md section 11).

    quorum: [k, block, d]; lo/hi: [n_pairs] concrete slot ids (the
    schedule's: which pairs are self tiles is read from them on the host,
    so this hook is not jitted itself and runs inside the caller's jit);
    meta: [n_pairs, 6] int32 ``(active, is_self, ga, gb, nv_lo, nv_hi)``.
    ``threshold`` is a *static* float (the kernel bakes it in; the host
    join program is cached per threshold), ``capacity`` the per-device
    buffer size, ``block_rows`` the global block stride for row-id math,
    ``slot`` the hits one tile step can hand over (default
    kernels/pairwise_threshold.py ``SLOT``).  Returns ``(vals f32
    [capacity], i i32 [capacity], j i32 [capacity], count i32 [], stats
    i32 [2])`` under the overflow contract of DESIGN.md 11.2; ``stats``
    is (tile steps that emitted, hits dropped by a full slot row).

    The kernel zero-pads block rows up to its tile multiple: exact, the
    valid-row bounds in ``meta`` reject them.
    """
    from .pairwise_threshold import SLOT, pairwise_threshold_pallas
    return pairwise_threshold_pallas(
        quorum, np.asarray(lo, np.int32), np.asarray(hi, np.int32), meta,
        threshold=float(threshold), capacity=int(capacity),
        block_rows=int(block_rows), metric=metric, slot=int(slot or SLOT),
        interpret=_interpret())


def pairwise_topk(quorum, lo, hi, meta, *, topk, block_rows, metric="dot",
                  count_rounds=False):
    """Fused pair-scoring top-k step for the k-NN graph engine's
    ``batch_fn`` hook (core/knn.py; DESIGN.md section 12.3).

    quorum: [k, block, d]; lo/hi: [n_pairs] concrete slot ids (the
    schedule's — the kernel's item table is built from them on the host,
    so this hook is not jitted itself and runs inside the caller's jit);
    meta: [n_pairs, 6] int32 ``(active, is_self, ga, gb, nv_lo, nv_hi)``.
    ``topk`` is the per-row neighbor-list length, ``block_rows`` the
    global block stride for row-id math.  Returns the per-slot running top-k
    ``(vals f32 [k, block, topk], idx i32 [k, block, topk])`` under the
    engine's (-score, index) order — bit-parity with ref.pairwise_topk.
    ``count_rounds`` adds a third output, the kernel's merge counts
    (kernels/pairwise_topk.py ``directed_topk_call``).

    The kernel zero-pads block rows up to its tile multiple — exact, the
    valid-row bounds in ``meta`` reject them as candidates and the padded
    rows' own lists are sliced back off.
    """
    from .pairwise_topk import pairwise_topk_pallas
    return pairwise_topk_pallas(quorum, lo, hi, meta, topk=topk,
                                block_rows=block_rows, metric=metric,
                                count_rounds=count_rounds,
                                interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("threshold", "capacity",
                                             "block_rows", "metric"))
def pairwise_threshold_q(q, sd, l1, sq, lo, hi, meta, *, threshold,
                         capacity, block_rows, metric="dot"):
    """Quantized thresholded-join step for the quant engine's
    ``batch_fn`` hook (core/quant.py; DESIGN.md section 17.3).

    q: [k, block, d] int8/bf16 quantized blocks; sd: [k, 2] f32
    per-block (scale, delta); l1/sq: [k, block] f32 row L1 norms and
    exact squared norms; lo/hi/meta and the static args as in
    :func:`pairwise_threshold`.  Emits the widened ``s_q >= threshold -
    eps`` band under the same overflow contract; the host rescoring pass
    resolves it exactly.

    Pads block rows up to the 8-sublane multiple with zero rows (zero
    quantized values and zero norms — the valid-row bounds in ``meta``
    already reject them) and capacity up to the 128-lane multiple,
    slicing back.  Raises on TPU (see :func:`_join_kernel_lowers`).
    """
    from .pairwise_batch_q import pairwise_threshold_q_pallas
    _join_kernel_lowers("pairwise_threshold_q")
    qp, _ = _pad_to(q, 8, 1)
    l1p, _ = _pad_to(l1, 8, 1)
    sqp, _ = _pad_to(sq, 8, 1)
    capp = -(-capacity // 128) * 128
    vals, gi, gj, count = pairwise_threshold_q_pallas(
        qp, sd, l1p, sqp, lo, hi, meta, threshold=threshold,
        capacity=capp, block_rows=block_rows, metric=metric,
        interpret=_interpret())
    return (vals[:capacity], gi[:capacity], gj[:capacity],
            count.reshape(()))


def pairwise_topk_q(q, sd, sq, lo, hi, meta, *, topk, block_rows,
                    metric="dot"):
    """Quantized pair-scoring top-k step for the quant engine's
    ``batch_fn`` hook (core/quant.py; DESIGN.md section 17.3).

    q: [k, block, d] int8/bf16 quantized blocks; sd: [k, 2] f32
    per-block (scale, delta); sq: [k, block] exact f32 squared row
    norms; lo/hi/meta and the static args as in :func:`pairwise_topk`.
    Returns the per-slot running *quantized* top-k — the host certifies
    and rescores the lists exactly.

    The kernel zero-pads block rows up to its tile multiple (zero
    quantized values and zero norms — the valid-row bounds in ``meta``
    reject them as candidates and padded rows' own lists are sliced back
    off).
    """
    from .pairwise_batch_q import pairwise_topk_q_pallas
    return pairwise_topk_q_pallas(q, sd, sq, lo, hi, meta, topk=topk,
                                  block_rows=block_rows, metric=metric,
                                  interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk"))
def flash_attention(q, k, v, *, causal=True, bq=128, bk=128):
    """4-d entry point: q [B, Tq, H, hd], k/v [B, Tk, KV, hd] (GQA; the
    attention substrate of DESIGN.md section 6).

    K/V heads are broadcast to H before flattening to the kernel's [BH, T,
    hd] layout.  (A production TPU kernel indexes kv-heads in the grid map
    instead of materializing the broadcast; that variant changes only the
    BlockSpec index_map — noted for the perf log.)
    """
    B, Tq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Tq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, -1, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, -1, hd)
    out = flash_attention_pallas(qf, kf, vf, causal=causal, bq=bq, bk=bk,
                                 interpret=_interpret())
    return out.reshape(B, H, Tq, hd).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_chunk(x, dt, A, Bm, Cm, *, chunk=256):
    """Full SSD via the Pallas intra-chunk kernel + jnp inter-chunk scan
    (the SSM substrate of DESIGN.md section 6).

    x: [B, T, H, P]; dt: [B, T, H]; A: [H]; Bm/Cm: [B, T, N].
    Returns y [B, T, H, P] float32 (parity with ref.ssd_chunk).
    """
    from .ssd_chunk import ssd_chunk_pallas
    B, T, H, P = x.shape
    N = Bm.shape[-1]
    L = min(chunk, T)
    assert T % L == 0
    nc = T // L
    # flatten (B, H) -> BH with per-bh A; B/C shared across heads
    xf = x.transpose(0, 2, 1, 3).reshape(B * H, nc, L, P)
    dtf = dt.transpose(0, 2, 1).reshape(B * H, nc, L)
    Af = jnp.tile(A, (B,))
    Bf = jnp.repeat(Bm.reshape(B, 1, nc, L, N), H, 1).reshape(B * H, nc, L, N)
    Cf = jnp.repeat(Cm.reshape(B, 1, nc, L, N), H, 1).reshape(B * H, nc, L, N)
    y_intra, S, cd = ssd_chunk_pallas(xf, dtf, Af, Bf, Cf,
                                      interpret=_interpret())

    # inter-chunk recurrence (tiny): h_c = cd_last * h_{c-1} + S_c
    def step(h, inp):
        s_c, cdl, c_c, cd_c = inp
        y_int = jnp.einsum("bln,bl,bnp->blp", c_c, cd_c, h)
        h = cdl[:, None, None] * h + s_c
        return h, y_int

    cd_last = cd[:, :, -1]                                # [BH, nc]
    h0 = jnp.zeros((B * H, N, P), jnp.float32)
    xs = (jnp.moveaxis(S, 1, 0), jnp.moveaxis(cd_last, 1, 0),
          jnp.moveaxis(Cf.astype(jnp.float32), 1, 0),
          jnp.moveaxis(cd, 1, 0))
    _, y_inter = jax.lax.scan(step, h0, xs)
    y = y_intra + jnp.moveaxis(y_inter, 0, 1)
    return y.reshape(B, H, T, P).transpose(0, 2, 1, 3)


# re-export oracles for convenience in benchmarks/tests
reference = ref
