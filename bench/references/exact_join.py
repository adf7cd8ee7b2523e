"""Plain reference for the exact similarity self-join.

Every unordered pair i < j of corpus rows whose score reaches the
threshold, with ``dot`` scores ``x_i.x_j`` and ``l2`` scores
``2 x_i.x_j - |x_j|^2 - |x_i|^2`` (= -|x_i - x_j|^2), in float64.  It
imports nothing of the program under test.

:func:`exact_join` scans the corpus on the device in float32 at HIGHEST
precision, a chunk of rows against every row at a time, and keeps per row
the best ``CANDIDATES`` partners above it whose float32 score reaches the
threshold less a margin, where the margin bounds the float32 error.  It
rescores those pairs in float64 on the host and keeps the ones that reach
the threshold, so the float64 set is certified: a row whose candidates
fill its list is rescored over the whole corpus on the host.
:func:`control_join` is the same scan with the dot products in three
bfloat16 passes (what ``Precision.HIGH`` does on a TPU) and no float64
rescoring: the reference computed one precision below the configuration's
float32 at HIGHEST.
"""

from __future__ import annotations

import functools

import numpy as np

CHUNK_ROWS = 1024
CANDIDATES = 32


@functools.lru_cache(maxsize=None)
def _scan_fn(metric: str, keep: int, passes: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    def dot(q, c):
        if passes == 6:
            return jnp.dot(q, c.T, precision=lax.Precision.HIGHEST)
        # three bfloat16 passes, hi*hi + hi*lo + lo*hi with f32
        # accumulation; reduce_precision makes the split, which no
        # compiler pass folds away
        def split(a):
            hi = lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
            return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)
        qh, ql = split(q)
        ch, cl = split(c)
        mm = lambda a, b: lax.dot_general(
            a, b, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return mm(qh, ch) + (mm(qh, cl) + mm(ql, ch))

    def scan(q, c, base, lower):
        s = dot(q, c)
        if metric == "l2":
            s = (2.0 * s - jnp.sum(c * c, axis=1)[None, :]) \
                - jnp.sum(q * q, axis=1)[:, None]
        row = base + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((col > row) & (s >= lower), s, -jnp.inf)
        return lax.top_k(s, keep)

    return jax.jit(scan)


def _scan(corpus, metric, lower, passes):
    """Per row r, up to ``CANDIDATES`` rows j > r whose score (float32 by
    ``passes``) reaches ``lower``: ``(rows, cols, f32 scores, full)``,
    ``full`` the rows whose list was filled."""
    import jax
    import jax.numpy as jnp
    N, d = corpus.shape
    chunk = min(CHUNK_ROWS, N)
    keep = min(CANDIDATES, N)
    fn = _scan_fn(metric, keep, passes)
    c = jnp.asarray(corpus)
    rows, cols, vals, full = [], [], [], []
    for start in range(0, N, chunk):
        q = np.zeros((chunk, d), np.float32)
        part = corpus[start:start + chunk]
        q[:len(part)] = part
        v, i = jax.device_get(fn(jnp.asarray(q), c, jnp.int32(start),
                                 jnp.float32(lower)))
        v, i = v[:len(part)], i[:len(part)]
        r, k = np.nonzero(np.isfinite(v))
        rows.append(start + r)
        cols.append(i[r, k])
        vals.append(v[r, k])
        full.append(start + np.nonzero(np.isfinite(v[:, -1]))[0])
    return (np.concatenate(rows).astype(np.int64),
            np.concatenate(cols).astype(np.int64),
            np.concatenate(vals), np.concatenate(full))


def pair_scores(corpus, i, j, metric):
    """float64 score of each pair (i[k], j[k]); NaN where either index
    is not a corpus row."""
    i = np.asarray(i, np.int64)
    j = np.asarray(j, np.int64)
    N = len(corpus)
    ok = (i >= 0) & (i < N) & (j >= 0) & (j < N)
    out = np.full(i.shape, np.nan)
    for k0 in range(0, len(i), 1 << 16):           # bounded host memory
        sl = slice(k0, k0 + (1 << 16))
        a = np.asarray(corpus[np.where(ok[sl], i[sl], 0)], np.float64)
        b = np.asarray(corpus[np.where(ok[sl], j[sl], 0)], np.float64)
        s = np.einsum("kd,kd->k", a, b)
        if metric == "l2":
            s = (2.0 * s - (b * b).sum(1)) - (a * a).sum(1)
        out[sl] = np.where(ok[sl], s, np.nan)
    return out


def _row_host(corpus, r, threshold, metric):
    """Every j > r with float64 score >= threshold, over the whole
    corpus on the host."""
    x = np.asarray(corpus[r], np.float64)
    c = np.asarray(corpus[r + 1:], np.float64)
    s = c @ x
    if metric == "l2":
        s = (2.0 * s - (c * c).sum(1)) - x @ x
    j = np.nonzero(s >= threshold)[0]
    return np.full(len(j), r, np.int64), r + 1 + j, s[j]


def exact_join(corpus, threshold, metric):
    """Certified exact join: ``(i int64, j int64, float64 scores)`` of
    every pair i < j whose float64 score reaches ``threshold``, sorted
    by (i, j)."""
    corpus = np.asarray(corpus, np.float32)
    N, d = corpus.shape
    norms = np.sqrt((corpus.astype(np.float64) ** 2).sum(1))
    xmax = float(norms.max(initial=0.0))
    scale = xmax ** 2 if metric == "dot" else 4 * xmax ** 2
    # a float32 dot of d terms at HIGHEST lies within this of the exact
    # one (4 d ulps of the largest product sum)
    margin = 4.0 * d * 2.0 ** -24 * scale
    i, j, _, full = _scan(corpus, metric, threshold - margin, 6)
    s = pair_scores(corpus, i, j, metric)
    hit = s >= threshold
    i, j, s = i[hit], j[hit], s[hit]
    if len(full):
        again = np.isin(i, full)
        extra = [_row_host(corpus, int(r), threshold, metric) for r in full]
        i = np.concatenate([i[~again]] + [e[0] for e in extra])
        j = np.concatenate([j[~again]] + [e[1] for e in extra])
        s = np.concatenate([s[~again]] + [e[2] for e in extra])
    order = np.lexsort((j, i))
    return i[order], j[order], s[order]


def control_join(corpus, threshold, metric):
    """The control: the pairs whose scores, their dot products in three
    bfloat16 passes, reach ``threshold``, with those float32 scores, as
    the program returns them: ``(i int64, j int64, f32 scores)``."""
    i, j, v, _ = _scan(np.asarray(corpus, np.float32), metric, threshold, 3)
    order = np.lexsort((j, i))
    return i[order], j[order], v[order]
