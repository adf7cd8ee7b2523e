"""Per-kernel allclose sweeps: Pallas (interpret mode on CPU) vs ref.py."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("M,N,G", [(128, 128, 128), (64, 96, 50),
                                   (256, 128, 384), (32, 32, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_corr(M, N, G, dtype):
    xi = jnp.asarray(RNG.normal(size=(M, G)), dtype)
    xj = jnp.asarray(RNG.normal(size=(N, G)), dtype)
    out = ops.pairwise_corr(xi, xj)
    want = ref.pairwise_corr(xi.astype(jnp.float32), xj.astype(jnp.float32))
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("M,N,Z,bm", [(32, 32, 64, 16), (16, 48, 96, 16),
                                      (64, 64, 128, 32)])
def test_pcit_filter(M, N, Z, bm):
    rows = RNG.normal(size=(Z, 24))
    rows = rows / np.linalg.norm(rows, axis=1, keepdims=True)
    R = rows @ rows.T
    gx = jnp.arange(0, M, dtype=jnp.int32)
    gy = jnp.arange(Z - N, Z, dtype=jnp.int32)
    r_xy = jnp.asarray(R[:M, Z - N:], jnp.float32)
    rows_x = jnp.asarray(R[:M], jnp.float32)
    rows_y = jnp.asarray(R[Z - N:], jnp.float32)
    out = ops.pcit_filter(r_xy, rows_x, rows_y, gx, gy, bm=bm, bn=bm, bz=32)
    want = ref.pcit_filter(r_xy, rows_x, rows_y, gx, gy)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("B,Tq,Tk,H,KV,hd,causal",
                         [(2, 128, 128, 4, 2, 64, True),
                          (1, 64, 256, 4, 4, 32, True),
                          (2, 128, 128, 2, 1, 64, False),
                          (1, 256, 256, 8, 2, 128, True)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, Tq, Tk, H, KV, hd, causal, dtype):
    q = jnp.asarray(RNG.normal(size=(B, Tq, H, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Tk, KV, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Tk, KV, hd)), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, bq=64, bk=64)
    want = ref.flash_attention(q, k, v, causal=causal)
    tol = 2e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("B,T,H,P,N,chunk", [(2, 32, 3, 8, 16, 8),
                                             (1, 64, 2, 16, 8, 16),
                                             (2, 16, 4, 8, 32, 16)])
def test_ssd_chunk_pallas(B, T, H, P, N, chunk):
    x = jnp.asarray(RNG.normal(size=(B, T, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(B, T, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2, size=(H,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, T, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, T, N)), jnp.float32)
    got = ops.ssd_chunk(x, dt, A, Bm, Cm, chunk=chunk)
    want = ref.ssd_chunk(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_ssd_chunk_oracle_matches_model():
    """ref.ssd_chunk (sequential) == models.ssm.ssd_chunked for all chunkings."""
    from repro.models.ssm import ssd_chunked
    B, T, H, P, N = 2, 32, 3, 8, 16
    x = jnp.asarray(RNG.normal(size=(B, T, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(B, T, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2, size=(H,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, T, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, T, N)), jnp.float32)
    want = ref.ssd_chunk(x, dt, A, Bm, Cm)
    for chunk in [1, 4, 8, 32]:
        y, _ = ssd_chunked(x, dt, A, Bm, Cm, chunk)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("k,block,d,Q,topk", [(3, 16, 8, 4, 4),
                                              (4, 12, 24, 5, 8),
                                              (2, 32, 16, 12, 3),
                                              (5, 8, 4, 3, 40)])
@pytest.mark.parametrize("metric", ["dot", "l2"])
def test_query_topk_kernel(k, block, d, Q, topk, metric):
    """Fused query scoring + dedup mask + running top-k kernel vs the
    jnp two-key-sort oracle: exact index match (shared (-score, index)
    order), including masked rows, a fully-masked slot, non-multiple-of-8
    Q (wrapper pads), and topk > candidate count (sentinel fill)."""
    stack = jnp.asarray(RNG.normal(size=(k, block, d)), jnp.float32)
    queries = jnp.asarray(RNG.normal(size=(Q, d)), jnp.float32)
    mask = (RNG.uniform(size=(k, block)) > 0.3).astype(np.float32)
    mask[0] = 0.0                                   # fully-masked slot
    gidx = RNG.permutation(4 * k * block)[:k * block].reshape(k, block)
    got_v, got_i = ops.query_topk(stack, queries, jnp.asarray(mask),
                                  jnp.asarray(gidx, jnp.int32), topk=topk,
                                  metric=metric)
    want_v, want_i = ref.query_topk(stack, queries, mask, gidx, topk=topk,
                                    metric=metric)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v),
                               rtol=1e-5, atol=1e-5)


def _hit_rows(vals, gi, gj, n):
    """The first n (i, j, score) entries of a join buffer as an array of
    rows sorted by (i, j, score bits): the buffer order is the kernel's
    step order, the oracle's is row-major (DESIGN.md 11.3)."""
    vals, gi, gj = (np.asarray(a)[:n] for a in (vals, gi, gj))
    rows = np.stack([gi.astype(np.int64), gj.astype(np.int64),
                     vals.view(np.int32).astype(np.int64)], 1)
    return rows[np.lexsort(rows.T[::-1])]


def _assert_same_hits(got, want, n, exact):
    """Equal (i, j) sets, and scores bit-identical where ``exact``.  Else
    they agree to a few ulps: the CPU oracle's batched einsum may sum a
    dot's products in another order than the kernel's tile dot, and
    contracts its l2 epilogue differently (the thresholds sit in score
    gaps, so membership agrees)."""
    g, w = _hit_rows(*got[:3], n), _hit_rows(*want[:3], n)
    np.testing.assert_array_equal(g[:, :2], w[:, :2])
    gv, wv = (np.asarray(x[:, 2], np.int32).view(np.float32) for x in (g, w))
    if exact:
        np.testing.assert_array_equal(gv, wv)
    else:
        np.testing.assert_allclose(gv, wv, rtol=1e-6, atol=1e-5)


def _gap_threshold(quorum, metric, share=0.3):
    """A threshold in a score gap near the top ``share`` of the first and
    last slots' scores under the metric."""
    from repro.core.sparse import threshold_with_gap
    s = np.asarray(quorum[0] @ quorum[-1].T)
    if metric == "l2":
        n0 = np.asarray((quorum[0] ** 2).sum(-1))
        n1 = np.asarray((quorum[-1] ** 2).sum(-1))
        s = 2.0 * s - n1[None, :] - n0[:, None]
    return threshold_with_gap(s, share, min_gap=1e-4)


@pytest.mark.parametrize("k,block,d,n_pairs,capacity",
                         [(3, 16, 8, 4, 256), (4, 12, 24, 6, 64),
                          (2, 8, 4, 2, 128), (5, 8, 16, 8, 16)])
@pytest.mark.parametrize("metric", ["dot", "l2"])
def test_pairwise_threshold_kernel(k, block, d, n_pairs, capacity, metric):
    """Fused thresholded scoring + hit emission kernel vs the jnp cumsum
    oracle: the same (score, i, j) hits and true counts, including
    inactive (prefiltered) tiles, a self pair with the strict-triangle
    rule, partial row validity, capacity overflow (the (5, 8, 16, 8, 16)
    cell: the kept hits are a valid prefix) and non-multiple-of-8
    blocks."""
    rng = np.random.default_rng(k * 1000 + block)   # order-independent
    quorum = jnp.asarray(rng.normal(size=(k, block, d)), jnp.float32)
    lo = rng.integers(0, k, size=n_pairs).astype(np.int32)
    hi = rng.integers(0, k, size=n_pairs).astype(np.int32)
    lo[0] = hi[0] = 0                               # self pair
    meta = np.stack([
        np.ones(n_pairs),                           # active
        (lo == hi),                                 # is_self
        rng.permutation(2 * n_pairs)[:n_pairs],     # ga
        rng.permutation(2 * n_pairs)[:n_pairs],     # gb
        np.minimum(block, rng.integers(1, block + 1, n_pairs)),  # nv_lo
        np.minimum(block, rng.integers(1, block + 1, n_pairs)),  # nv_hi
    ], axis=1).astype(np.int32)
    if n_pairs > 1:
        meta[1, 0] = 0                              # a prefiltered tile
    thr = _gap_threshold(quorum, metric)
    got = ops.pairwise_threshold(quorum, lo, hi, jnp.asarray(meta),
                                 threshold=thr, capacity=capacity,
                                 block_rows=block, metric=metric)
    want = ref.pairwise_threshold(quorum, lo, hi, meta, threshold=thr,
                                  capacity=4096, block_rows=block,
                                  metric=metric)
    count = int(want[3])
    assert int(got[3]) == count
    assert int(got[4][1]) == 0                      # no slot row overflowed
    if count <= capacity:
        _assert_same_hits(got, want, count, exact=metric == "dot")
    else:                                           # the overflow cell
        assert (k, block, d, n_pairs, capacity) == (5, 8, 16, 8, 16)
        kept = _hit_rows(*got[:3], capacity)
        full = {tuple(r[:2]) for r in _hit_rows(*want[:3], count)}
        assert {tuple(r[:2]) for r in kept} <= full
        assert (np.asarray(got[1])[:capacity] != ops.ref.IDX_SENTINEL).all()


# (k, block, d, lo, hi, plan): several row and column steps a block, a
# self tile's diagonal steps, padded rows (block -> a multiple of tc), an
# odd column-tile count (a fold with one column tile), cross tiles; plan
# is the (rows, cols, wide) of a small ``tile_plan``
THRESHOLD_TILE_CASES = [
    (1, 40, 16, [0], [0], (8, 16, 16)),
    (3, 36, 8, [0, 0, 1, 2], [0, 1, 2, 2], (8, 8, 8)),
    (2, 30, 16, [0, 1, 0], [1, 1, 0], (8, 16, 16)),
]


def _small_tiles(monkeypatch, rows, cols, wide):
    """The kernel's tiling of a large block at a small size: the
    production ``tile_plan`` and padding, with small tiles."""
    from repro.kernels import pairwise_threshold as pt
    monkeypatch.setattr(pt, "tile_plan", functools.partial(
        pt.tile_plan, rows=rows, cols=cols, wide=wide))
    return pt.tile_plan


def _tile_case(k, block, d, lo, hi, active=None, seed=0):
    # small integers: every product and partial sum is exact in float32,
    # so a score is the same whatever order a path sums it in, and the
    # kernel's scores must equal the oracle's bit for bit
    rng = np.random.default_rng(seed + 17 * block)
    quorum = jnp.asarray(rng.integers(-4, 5, size=(k, block, d)),
                         jnp.float32)
    lo, hi = np.asarray(lo, np.int32), np.asarray(hi, np.int32)
    nv = np.full(k, block)
    nv[-1] = block - 5                              # a ragged last block
    meta = np.stack([np.ones(len(lo)) if active is None else active,
                     lo == hi, lo, hi, nv[lo], nv[hi]],
                    axis=1).astype(np.int32)
    return quorum, lo, hi, meta


@pytest.mark.parametrize("case", THRESHOLD_TILE_CASES,
                         ids=["self", "self+cross", "cross+self"])
@pytest.mark.parametrize("metric", ["dot", "l2"])
def test_pairwise_threshold_kernel_tiles(case, metric, monkeypatch):
    """The tiled kernel over blocks of several [tr, tc] steps against the
    oracle: the (i, j) set equal, scores bit-identical, the count true,
    and an inactive (masked) tile emitting nothing."""
    k, block, d, lo, hi, plan = case
    tr, tc, bp = _small_tiles(monkeypatch, *plan)(block)
    assert (tr, tc) == plan[:2] and bp > block and bp // tr > 2
    active = np.ones(len(lo))
    active[-1] = 0 if len(lo) > 2 else 1
    quorum, lo, hi, meta = _tile_case(k, block, d, lo, hi, active)
    thr = _gap_threshold(quorum, metric, share=0.2)   # between integers
    got = ops.pairwise_threshold(quorum, lo, hi, jnp.asarray(meta),
                                 threshold=thr, capacity=4096,
                                 block_rows=block, metric=metric)
    want = ref.pairwise_threshold(quorum, lo, hi, meta, threshold=thr,
                                  capacity=4096, block_rows=block,
                                  metric=metric)
    count = int(want[3])
    assert count > 0 and int(got[3]) == count
    assert int(got[4][1]) == 0 and int(got[4][0]) > 1   # several steps emit
    _assert_same_hits(got, want, count, exact=True)


@pytest.mark.parametrize("limit", ["capacity", "slot"])
def test_pairwise_threshold_kernel_overflow_contract(limit, monkeypatch):
    """Past the global capacity, or past a step's slot row, the count
    stays true and the kept hits are a valid prefix; the larger capacity
    or the wider slot gives back the full answer."""
    assert _small_tiles(monkeypatch, 8, 16, 16)(32) == (8, 16, 32)
    quorum, lo, hi, meta = _tile_case(2, 32, 8, [0, 0], [0, 1])
    thr = _gap_threshold(quorum, "dot", share=0.5)
    want = ref.pairwise_threshold(quorum, lo, hi, meta, threshold=thr,
                                  capacity=4096, block_rows=32)
    count = int(want[3])
    full = {tuple(r[:2]) for r in _hit_rows(*want[:3], count)}
    small = dict(capacity=count // 2, slot=None) if limit == "capacity" \
        else dict(capacity=4096, slot=4)
    got = ops.pairwise_threshold(quorum, lo, hi, jnp.asarray(meta),
                                 threshold=thr, block_rows=32, **small)
    assert int(got[3]) == count                     # the true count
    dropped = int(got[4][1])
    kept = min(count - dropped, small["capacity"])
    assert (dropped > 0) == (limit == "slot")
    assert kept < count
    rows = _hit_rows(*got[:3], kept)
    assert {tuple(r[:2]) for r in rows} <= full and len(rows) == kept
    assert (np.asarray(got[1])[kept:] == ops.ref.IDX_SENTINEL).all()
    wide = dict(capacity=4096, slot=8 * 16)
    again = ops.pairwise_threshold(quorum, lo, hi, jnp.asarray(meta),
                                   threshold=thr, block_rows=32, **wide)
    assert int(again[4][1]) == 0
    _assert_same_hits(again, want, count, exact=True)


@pytest.mark.parametrize("k,block,n_pairs", [(2, 8, 2), (3, 12, 5),
                                             (4, 16, 9), (3, 8, 4)])
def test_pairwise_batch_forces(k, block, n_pairs):
    """Fused batched n-body + slot segment-sum kernel vs the jnp oracle,
    including a self pair (wj = 0), masked-out pairs, and non-multiple-of-8
    block sizes (zero-mass padding)."""
    quorum = jnp.asarray(np.concatenate(
        [RNG.normal(size=(k, block, 3)),
         RNG.uniform(0.5, 2, (k, block, 1))], -1), jnp.float32)
    lo = RNG.integers(0, k, size=n_pairs).astype(np.int32)
    hi = RNG.integers(0, k, size=n_pairs).astype(np.int32)
    lo[0] = hi[0] = 0                               # self pair
    wi = RNG.integers(0, 2, size=n_pairs).astype(np.float32)
    wi[0] = 1.0
    wj = wi * (lo != hi)
    got = ops.pairwise_batch_forces(quorum, lo, hi, wi, wj)
    want = ref.pairwise_batch_forces(quorum, lo, hi, wi, wj)
    assert got.shape == (k, block, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("k,block,d,n_pairs,topk,metric",
                         [(3, 8, 16, 4, 4, "dot"),
                          (4, 16, 8, 7, 8, "l2"),
                          (3, 7, 5, 4, 3, "dot"),     # non-multiple-of-8 rows
                          (5, 8, 16, 8, 2, "l2"),
                          (2, 8, 4, 2, 16, "dot")])   # topk > candidates
def test_pairwise_topk_kernel(k, block, d, n_pairs, topk, metric):
    """Fused pair-scoring running-top-k kernel vs the jnp scan oracle:
    identical neighbor indices and scores per slot row, including a self
    pair with the diagonal excluded, an inactive (masked) tile, partial
    row validity, sentinel padding when topk exceeds the candidate
    count, and non-multiple-of-8 handling through the ops wrapper."""
    rng = np.random.default_rng(k * 777 + block)     # order-independent
    quorum = jnp.asarray(rng.normal(size=(k, block, d)), jnp.float32)
    lo = rng.integers(0, k, size=n_pairs).astype(np.int32)
    hi = rng.integers(0, k, size=n_pairs).astype(np.int32)
    lo[0] = hi[0] = 0                                # self pair
    meta = np.stack([
        np.ones(n_pairs),                            # active
        (lo == hi),                                  # is_self
        rng.permutation(2 * n_pairs)[:n_pairs],      # ga (distinct ids)
        rng.permutation(2 * n_pairs)[:n_pairs],      # gb
        np.minimum(block, rng.integers(1, block + 1, n_pairs)),  # nv_lo
        np.minimum(block, rng.integers(1, block + 1, n_pairs)),  # nv_hi
    ], axis=1).astype(np.int32)
    if n_pairs > 1:
        meta[1, 0] = 0                               # a masked-out tile
    got_v, got_i = ops.pairwise_topk(quorum, lo, hi, jnp.asarray(meta),
                                     topk=topk, block_rows=block,
                                     metric=metric)
    pad = (-block) % 8                               # ref sees padded rows
    qp = jnp.pad(quorum, ((0, 0), (0, pad), (0, 0)))
    want_v, want_i = ref.pairwise_topk(qp, lo, hi, meta, topk=topk,
                                       block_rows=block, metric=metric)
    np.testing.assert_array_equal(np.asarray(got_i),
                                  np.asarray(want_i)[:, :block])
    np.testing.assert_allclose(np.asarray(got_v),
                               np.asarray(want_v)[:, :block],
                               rtol=1e-5, atol=1e-5)


def _merge_case(case, dtype=np.float32):
    """(blocks [k, B, d], lo, hi, meta) of one merge-order case of the
    gated k-NN kernel: every row list sees several 512-column steps."""
    rng = np.random.default_rng(sum(map(ord, case)))
    quorum4 = case in ("cyclic4", "duplicates")
    k, B, d = (3, 1024, 16) if quorum4 else (1, 1024, 16)
    if case == "random":
        B = 2048
        x = rng.normal(size=(k, B, d))
    elif case == "wide":           # wide column tiles over a ragged block
        B, d = 2048, 8
        x = rng.normal(size=(k, B, d))
    elif case == "ascending":      # every later column scores higher
        x = np.zeros((k, B, d))
        x[..., 0] = 1.0 + np.arange(B) / B
    elif case == "duplicates":     # scores tie at the k-th entry, and
        # later items bring lower indices: only the tie-break admits them
        x = rng.normal(size=(48, d))[rng.integers(0, 48, (k, B))]
    else:
        x = rng.normal(size=(k, B, d))
    if dtype == np.int8:
        x = np.clip(np.round(x * 40), -127, 127)
    if quorum4:                    # a self tile, two cross pairs, a dedup
        lo = np.array([0, 0, 0, 1], np.int32)
        hi = np.array([0, 1, 2, 2], np.int32)
        blocks = np.array([3, 0, 1], np.int32)
        nv = np.array([B, B, B - 100], np.int32)
        meta = np.stack([[1, 1, 1, 0], lo == hi, blocks[lo], blocks[hi],
                         nv[lo], nv[hi]], axis=1)
    else:                          # one self tile (P = 1)
        lo = hi = np.zeros(1, np.int32)
        # fewer candidates than topk; a ragged block
        nv = {"underfull": 6, "wide": B - 37}.get(case, B)
        meta = np.array([[1, 1, 0, 0, nv, nv]])
    return x.astype(dtype), lo, hi, meta.astype(np.int32)


GATED_CASES = [("random", "l2"), ("random", "dot"), ("wide", "dot"),
               ("ascending", "dot"),
               ("duplicates", "l2"), ("duplicates", "dot"),
               ("underfull", "l2"), ("cyclic4", "l2"), ("cyclic4", "dot")]


@pytest.mark.parametrize("case,metric", GATED_CASES,
                         ids=[f"{c}-{m}" for c, m in GATED_CASES])
def test_pairwise_topk_kernel_merge_orders(case, metric, monkeypatch):
    """The threshold-gated merge keeps ref.pairwise_topk's lists exactly
    whatever the candidate order: random, random over wide column tiles
    with a ragged block, ascending (every step runs all rounds), tied
    scores at the k-th entry (index breaks the tie), fewer valid
    candidates than topk, and a slot receiving several items (the cyclic
    P = 4 layout, with a dedup-masked item)."""
    x, lo, hi, meta = _merge_case(case)
    if case == "wide":  # the tiling of a large block, at a small size
        from repro.kernels import pairwise_topk as ptk
        monkeypatch.setattr(ptk, "tile_plan", functools.partial(
            ptk.tile_plan, rows=64, cols=64, wide=256))
        assert ptk.tile_plan(x.shape[1]) == (64, 256, 2048)
    quorum = jnp.asarray(x)
    got_v, got_i = ops.pairwise_topk(quorum, lo, hi, jnp.asarray(meta),
                                     topk=10, block_rows=x.shape[1],
                                     metric=metric)
    want_v, want_i = ref.pairwise_topk(quorum, lo, hi, meta, topk=10,
                                       block_rows=x.shape[1], metric=metric)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    if metric == "dot":
        np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    else:  # the CPU oracle contracts its l2 epilogue differently
        np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v),
                                   rtol=1e-6, atol=1e-5)


QUANT_CASES = [("random", "dot"), ("ascending", "dot"), ("duplicates", "l2"),
               ("underfull", "dot"), ("cyclic4", "l2")]


@pytest.mark.parametrize("case,metric", QUANT_CASES,
                         ids=[f"{c}-{m}" for c, m in QUANT_CASES])
def test_pairwise_topk_q_kernel_merge_orders(case, metric):
    """The quantized k-NN kernel runs the same gated merge: its lists
    equal ref.pairwise_topk_q's in every merge-order case (int8 blocks
    tie often on their own)."""
    q, lo, hi, meta = _merge_case(case, np.int8)
    k, B, _ = q.shape
    scale = np.linspace(0.01, 0.02, k).astype(np.float32)
    sd = np.stack([scale, np.zeros(k, np.float32)], axis=1)
    sq = (q.astype(np.float32) * scale[:, None, None]) ** 2
    sq = sq.sum(-1).astype(np.float32)
    got_v, got_i = ops.pairwise_topk_q(
        jnp.asarray(q), jnp.asarray(sd), jnp.asarray(sq), lo, hi,
        jnp.asarray(meta), topk=10, block_rows=B, metric=metric)
    want_v, want_i = ref.pairwise_topk_q(q, scale, sq, lo, hi, meta,
                                         topk=10, block_rows=B,
                                         metric=metric)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    if metric == "dot":
        np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    else:
        np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v),
                                   rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("block,plan", [
    (7, (8, 8, 8)), (512, (512, 512, 512)), (513, (256, 512, 1024)),
    (8191, (256, 512, 8192)), (8192, (256, 1024, 8192)),
    (65_536, (256, 2048, 65_536)), (131_072, (256, 2048, 131_072)),
    (70_000, (256, 2048, 71_680))])
def test_tile_plan(block, plan):
    """Blocks up to 512 rows are one tile; larger ones take 256-row tiles
    and the widest column tile up to 2,048 that pads at most an eighth
    of the block."""
    from repro.kernels.pairwise_topk import tile_plan
    tr, tc, bp = tile_plan(block)
    assert (tr, tc, bp) == plan
    assert bp % tr == 0 and bp % tc == 0 and bp >= block
    assert bp == -(-block // 8) * 8 or bp - block <= max(block // 8, tc)


def _round_counts(x, lo, hi, meta, metric):
    from repro.kernels.pairwise_topk import pairwise_topk_pallas
    *_, counts = pairwise_topk_pallas(
        jnp.asarray(x), lo, hi, jnp.asarray(meta), topk=10,
        block_rows=x.shape[1], metric=metric, count_rounds=True,
        interpret=True)
    return np.asarray(counts)                      # [k, row tiles, 2]


def test_pairwise_topk_round_counts_random_order():
    """Random order: a row list fed 64 column steps (one receiving slot,
    64 sender blocks) needs at most 4 extraction rounds a step on
    average at topk 10, against the 10 of an ungated merge."""
    rng = np.random.default_rng(3)
    J, B = 64, 512
    x = rng.normal(size=(J + 1, B, 8)).astype(np.float32)
    lo = np.zeros(J, np.int32)
    hi = np.arange(1, J + 1, dtype=np.int32)
    meta = np.stack([np.ones(J), np.zeros(J), lo, hi, np.full(J, B),
                     np.full(J, B)], axis=1).astype(np.int32)
    counts = _round_counts(x, lo, hi, meta, "dot")
    rounds, steps = counts[0].sum(axis=0)
    assert steps == J
    assert rounds / steps <= 4, (rounds, steps)
    # each sender slot receives one item into an empty list: 10 rounds
    np.testing.assert_array_equal(counts[1:, 0], [[10, 1]] * J)


def test_pairwise_topk_round_counts_ascending_order():
    """Ascending order: every candidate tile beats the whole list, so
    every tile step runs all topk rounds."""
    from repro.kernels.pairwise_topk import tile_plan
    x, lo, hi, meta = _merge_case("ascending")
    counts = _round_counts(x, lo, hi, meta, "dot")
    tr, tc, bp = tile_plan(x.shape[1])
    nrt, ntc = bp // tr, bp // tc
    np.testing.assert_array_equal(counts[0], [[10 * ntc, ntc]] * nrt)
