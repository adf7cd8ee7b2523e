"""Plain reference for exact top-k retrieval and the exact k-NN graph.

For each query row, the ``topk`` corpus rows of highest score under the
(-score, index) order, with ``dot`` scores ``q.x`` and ``l2`` scores
``2 q.x - |x|^2 - |q|^2`` (= -|q - x|^2), computed in float64.  It imports
nothing of the program under test.

:func:`topk` scans the corpus on the device in float32 at HIGHEST
precision to keep a candidate list per query, rescores the candidates in
float64 on the host, and certifies that no row outside the candidates
could reach the top ``topk + 1`` given a bound on the float32 error; a
row that cannot be certified falls back to a full float64 scan on the
host (:func:`topk_host`).  :func:`control_topk` is the same scan with the
dot products in three bfloat16 passes (what ``Precision.HIGH`` does on a
TPU) and no float64 rescoring: the reference computed one precision
below the configuration's float32 at HIGHEST.

``topk_host`` is copied from ``chip_smoke.py`` (``ref_topk``).
"""

from __future__ import annotations

import functools

import numpy as np

CHUNK_ROWS = 1 << 18
CANDIDATES = 64


def topk_host(queries, corpus, topk, metric, exclude=None, chunk=1 << 16):
    """float64 top-k of every query row over ``corpus`` rows under the
    (-score, index) order, plus the (k+1)-th score (the cut).
    ``exclude[q]`` drops corpus row exclude[q] (the k-NN self match)."""
    q = np.asarray(queries, np.float64)
    qn = (q * q).sum(1)
    best_s = np.full((len(q), 0), -np.inf)
    best_i = np.zeros((len(q), 0), np.int64)
    keep = topk + 1
    for start in range(0, len(corpus), chunk):
        c = np.asarray(corpus[start:start + chunk], np.float64)
        s = q @ c.T
        if metric == "l2":
            s = (2.0 * s - (c * c).sum(1)[None, :]) - qn[:, None]
        if exclude is not None:
            rows = np.nonzero((exclude >= start)
                              & (exclude < start + len(c)))[0]
            s[rows, exclude[rows] - start] = -np.inf
        ids = np.broadcast_to(np.arange(start, start + len(c)), s.shape)
        s = np.concatenate([best_s, s], 1)
        ids = np.concatenate([best_i, ids], 1)
        part = np.argpartition(-s, min(keep, s.shape[1] - 1), axis=1)[:, :keep]
        best_s = np.take_along_axis(s, part, 1)
        best_i = np.take_along_axis(ids, part, 1)
    order = np.lexsort((best_i, -best_s), axis=1)
    return (np.take_along_axis(best_i, order, 1),
            np.take_along_axis(best_s, order, 1))


def true_scores(queries, corpus, idx, metric):
    """float64 score of each ``idx[r, j]`` for query ``r``; NaN where the
    index is not a corpus row."""
    q = np.asarray(queries, np.float64)
    idx = np.asarray(idx, np.int64)
    ok = (idx >= 0) & (idx < len(corpus))
    out = np.full(idx.shape, np.nan)
    for r0 in range(0, len(q), 64):          # bounded host memory
        qb, ib = q[r0:r0 + 64], idx[r0:r0 + 64]
        okb = ok[r0:r0 + 64]
        rows = np.asarray(corpus[np.where(okb, ib, 0).reshape(-1)],
                          np.float64).reshape(ib.shape + (q.shape[1],))
        s = np.einsum("rd,rjd->rj", qb, rows)
        if metric == "l2":
            s = (2.0 * s - (rows * rows).sum(-1)) - (qb * qb).sum(1)[:, None]
        out[r0:r0 + 64] = np.where(okb, s, np.nan)
    return out


@functools.lru_cache(maxsize=None)
def _scan_fn(metric: str, keep: int, passes: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    def dot(q, c):
        if passes == 6:
            return jnp.dot(q, c.T, precision=lax.Precision.HIGHEST)
        # three bfloat16 passes, hi*hi + hi*lo + lo*hi with f32
        # accumulation (what Precision.HIGH does on a TPU).  The split uses
        # reduce_precision, which no compiler pass folds away, where a
        # f32 -> bf16 -> f32 round trip can be removed as a no-op.
        def split(a):
            hi = lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
            return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)
        qh, ql = split(q)
        ch, cl = split(c)
        mm = lambda a, b: lax.dot_general(
            a, b, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return mm(qh, ch) + (mm(qh, cl) + mm(ql, ch))

    def scan(q, c, base, n_valid, exclude):
        s = dot(q, c)
        if metric == "l2":
            s = (2.0 * s - jnp.sum(c * c, axis=1)[None, :]) \
                - jnp.sum(q * q, axis=1)[:, None]
        col = base + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        dead = (col >= n_valid) | (col == exclude[:, None])
        s = jnp.where(dead, -jnp.inf, s)
        v, i = lax.top_k(s, keep)
        return v, i + base

    return jax.jit(scan)


def _scan(queries, corpus, metric, keep, passes, exclude):
    """Per chunk of corpus rows, the ``keep`` best (f32 score, index) of
    every query, and the last kept score (the chunk's cut)."""
    import jax
    import jax.numpy as jnp
    N, d = corpus.shape
    m = len(queries)
    chunk = min(CHUNK_ROWS, N)
    keep = min(keep, chunk)
    fn = _scan_fn(metric, keep, passes)
    q = jnp.asarray(np.asarray(queries, np.float32))
    ex = jnp.asarray(np.full(m, -1, np.int32) if exclude is None
                     else np.asarray(exclude, np.int32))
    vals, ids, cuts = [], [], []
    for start in range(0, N, chunk):
        c = np.zeros((chunk, d), np.float32)
        part = corpus[start:start + chunk]
        c[:len(part)] = part
        v, i = fn(q, jnp.asarray(c), jnp.int32(start), jnp.int32(N), ex)
        v, i = jax.device_get((v, i))
        vals.append(v)
        ids.append(i)
        cut = v[:, -1].astype(np.float64)
        if len(part) <= keep:          # every row of the chunk was kept
            cut = np.full(m, -np.inf)
        cuts.append(cut)
    return (np.concatenate(vals, 1), np.concatenate(ids, 1).astype(np.int64),
            np.stack(cuts, 1))


def topk(queries, corpus, topk, metric, exclude=None):
    """Certified exact top-(topk + 1) of every query: ``(indices [m,
    topk + 1] int64, float64 scores [m, topk + 1])`` under the (-score,
    index) order.  ``exclude[r]`` drops corpus row exclude[r] for query r.
    """
    queries = np.asarray(queries, np.float32)
    corpus = np.asarray(corpus, np.float32)
    keep = topk + 1
    v, cand, cuts = _scan(queries, corpus, metric, CANDIDATES, 6, exclude)
    s = true_scores(queries, corpus, cand, metric)
    # slots the scan filled with masked rows (padding, the excluded row)
    s = np.where(np.isfinite(v) & np.isfinite(s), s, -np.inf)
    order = np.lexsort((cand, -s), axis=1)[:, :keep]
    idx = np.take_along_axis(cand, order, 1)
    sc = np.take_along_axis(s, order, 1)
    # a row outside the candidates scored at most its chunk's cut in
    # float32; its float64 score lies within `bound` of that
    q64 = queries.astype(np.float64)
    qn = np.sqrt((q64 * q64).sum(1))
    xmax = float(np.sqrt((corpus.astype(np.float64) ** 2).sum(1).max()))
    scale = qn * xmax if metric == "dot" else 2 * qn * xmax + xmax ** 2 + qn ** 2
    bound = 4.0 * corpus.shape[1] * 2.0 ** -24 * scale
    certified = (cuts + bound[:, None] < sc[:, -1:]).all(1)
    for r in np.nonzero(~certified)[0]:
        ex = None if exclude is None else np.asarray(exclude)[r:r + 1]
        i, v = topk_host(queries[r:r + 1], corpus, topk, metric, exclude=ex)
        idx[r], sc[r] = i[0, :keep], v[0, :keep]
    return idx, sc


def control_topk(queries, corpus, topk, metric, exclude=None):
    """The control: top-``topk`` by scores whose dot products take three
    bfloat16 passes, selected and returned in float32 as the program
    returns them: ``(indices [m, topk] int64, scores [m, topk] f32)``."""
    v, i, _ = _scan(np.asarray(queries, np.float32),
                    np.asarray(corpus, np.float32), metric, topk, 3, exclude)
    order = np.lexsort((i, -v.astype(np.float64)), axis=1)[:, :topk]
    return (np.take_along_axis(i, order, 1),
            np.take_along_axis(v, order, 1))
