"""Batch traffic: exact similarity self-joins run back to back for the
window.

Traffic file key: ``corpora`` (distinct corpora generated from the seed
and joined in turn, so no join can return an earlier join's answer).  The
configuration's ``data`` says how a corpus is made (:func:`neardup_rows`):
unit rows, a share of them in planted near-duplicate groups.  Every pair
of every join is compared with the reference after the window.

End-to-end metric: ``batch_pairs_per_s``, the distinct unordered pairs
N(N - 1)/2 of every join completed in the window over the window's
seconds (Gpair/s), the window ending when its last join completes.
"""

from __future__ import annotations

import functools
import time

import numpy as np

from yardstick import compare, data

# a passing pair is "near" the threshold within this much of the score
NEAR = 0.01


@functools.lru_cache(maxsize=None)
def _rows_fn(rows: int, d: int):
    import jax
    import jax.numpy as jnp

    def gen(w0, w1, chunk, base, gid, sigma):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.key(w0), w1),
                                 chunk)
        noise = jax.random.normal(key, (rows, d), jnp.float32)
        grouped = gid >= 0
        near = (jnp.take(base, jnp.maximum(gid, 0), axis=0)
                + sigma[:, None] * noise / jnp.sqrt(jnp.float32(d)))
        x = jnp.where(grouped[:, None], near, noise)
        return x / jnp.linalg.norm(x, axis=1, keepdims=True)

    return jax.jit(gen)


def neardup_rows(seed: int, stream: int, n: int, d: int, *, dup_share: float,
                 group_size: int, cos_lo: float, cos_hi: float) -> np.ndarray:
    """[n, d] float32 unit rows, the same for the same (seed, stream).

    ``dup_share`` of the rows form groups of ``group_size`` noisy copies
    of a random unit base row, at random row positions; a copy's cosine
    to its base is drawn uniformly in [cos_lo, cos_hi] (its noise is
    orthogonal to the base in expectation), so two copies' cosine is
    about the product of theirs.  The other rows are i.i.d. random unit
    rows.  Generated on the device in chunks of one shape."""
    import jax
    import jax.numpy as jnp
    rng = data.host_rng(seed, stream, 7)
    n_groups = int(n * dup_share) // group_size
    members = rng.permutation(n)[:n_groups * group_size]
    gid = np.full(n, -1, np.int32)
    gid[members] = np.repeat(np.arange(n_groups, dtype=np.int32), group_size)
    cos = rng.uniform(cos_lo, cos_hi, size=n)
    sigma = np.sqrt(1.0 / cos ** 2 - 1.0).astype(np.float32)
    w0, w1 = data.seed_words(seed, stream)
    b0, _ = data.seed_words(seed, stream, 1)
    base = jax.random.normal(jax.random.key(b0), (max(1, n_groups), d),
                             jnp.float32)
    base = base / jnp.linalg.norm(base, axis=1, keepdims=True)
    rows = min(data.GEN_ROWS, max(8, -(-n // 8) * 8))
    fn = _rows_fn(rows, d)
    out = np.empty((n, d), np.float32)
    for c, start in enumerate(range(0, n, rows)):
        g = np.full(rows, -1, np.int32)
        s = np.zeros(rows, np.float32)
        m = min(rows, n - start)
        g[:m], s[:m] = gid[start:start + m], sigma[start:start + m]
        part = np.asarray(fn(w0, w1, c, base, g, s))
        out[start:start + m] = part[:m]
    return out


def compare_pairs(i, j, s, ref_i, ref_j, ref_s, n_rows, threshold, true_s,
                  tie_rel=compare.TIE_REL):
    """Compare a join's returned pairs with the reference's.

    i/j/s: returned pairs and float32 scores; ref_i/ref_j/ref_s: every
    pair i < j whose float64 score reaches ``threshold``, with that
    score; true_s: the float64 score of each returned pair (NaN where it
    is no pair of rows).  A pair whose float64 score lies within
    ``tie_rel`` of the threshold, relatively, is a tie: neither missing
    nor extra.  Returns ``missing_pairs`` (reference pairs not returned),
    ``extra_pairs`` (returned pairs not in the reference),
    ``invalid_pairs`` (returned pairs that cannot be right whatever the
    data: not i < j, out of range, repeated, or a score that is not
    finite or below the threshold in float32) and ``score_rel_err``, the
    largest gap between a returned score and its float64 score over the
    latter."""
    i = np.asarray(i, np.int64)
    j = np.asarray(j, np.int64)
    s = np.asarray(s, np.float32)
    true_s = np.asarray(true_s, np.float64)
    ok = (i >= 0) & (i < j) & (j < n_rows)
    key = np.where(ok, i * n_rows + j, -1)
    uniq, first, times = np.unique(key, return_index=True, return_counts=True)
    repeated = np.zeros(len(key), bool)
    repeated[np.isin(key, uniq[times > 1])] = True
    repeated[first] = False
    bad = ~ok | repeated | ~np.isfinite(s) | ~(s >= np.float32(threshold))
    tol = tie_rel * abs(threshold)
    ref_key = np.asarray(ref_i, np.int64) * n_rows + np.asarray(ref_j,
                                                              np.int64)
    ref_tie = np.abs(np.asarray(ref_s, np.float64) - threshold) <= tol
    returned = key[ok & ~repeated]
    missing = ~np.isin(ref_key, returned) & ~ref_tie
    got_tie = np.abs(true_s - threshold) <= tol
    extra = ok & ~repeated & ~np.isin(key, ref_key) & ~got_tie
    gap = np.abs(s.astype(np.float64) - true_s) / np.maximum(np.abs(true_s),
                                                             1e-30)
    gap = np.where(ok, gap, 0.0)
    gap = np.where(np.isfinite(gap), gap, np.inf)
    return {"missing_pairs": int(missing.sum()),
            "extra_pairs": int(extra.sum()),
            "invalid_pairs": int(bad.sum()),
            "score_rel_err": float(gap.max(initial=0.0))}


class Driver:
    def __init__(self, run):
        self.run = run
        cfg = run.config
        self.N, self.d = int(cfg["rows"]), int(cfg["dim"])
        self.threshold, self.metric = float(cfg["threshold"]), cfg["metric"]

    def setup(self):
        import jax
        from repro.core.sparse import similarity_join
        from repro.launch.mesh import make_mesh
        run, cfg = self.run, self.run.config
        n_corpora = int(run.traffic["corpora"])
        t = time.monotonic()
        self.corpora = [neardup_rows(run.seed, 1 + c, self.N, self.d,
                                     **cfg["data"])
                        for c in range(n_corpora)]
        self.mesh = make_mesh((run.chips,), ("q",), devices=run.devices)
        self.similarity_join = similarity_join
        self.annotate = jax.profiler.TraceAnnotation
        run.notes["setup_data_s"] = time.monotonic() - t
        t = time.monotonic()
        # compiles, or loads the cache, and settles the capacity
        run.notes["setup_escalations"] = self.join(0).escalations
        run.notes["setup_warm_s"] = time.monotonic() - t

    def join(self, c):
        cfg = self.run.config
        return self.similarity_join(
            self.corpora[c], self.mesh, threshold=self.threshold,
            metric=self.metric, mode=cfg["mode"], placement=cfg["placement"],
            use_kernel=bool(cfg["use_kernel"]), quant=cfg["quant"])

    def window(self, t0):
        run = self.run
        self.results = []
        end = t0 + run.seconds
        t_last = t_done = t0
        attempted = failed = escalations = 0
        while t_last < end:
            c = attempted % len(self.corpora)
            attempted += 1
            try:
                with self.annotate("bench.similarity_join"):
                    res = self.join(c)
            except Exception:
                failed += 1
                t_last = time.monotonic()
                continue
            t_last = t_done = time.monotonic()
            escalations += res.escalations
            self.results.append((c, res.i, res.j, res.scores))
        n = len(self.results)
        run.attempted, run.failed = attempted, failed
        run.e2e["batch_pairs_per_s"] = (
            n * self.N * (self.N - 1) / 2 / (t_done - t0) / 1e9 if n else 0.0)
        run.counters["join.escalations"] = escalations
        hits = sum(len(r[1]) for r in self.results)
        run.work.update(joins=n, rows=self.N, dim=self.d,
                        hits_per_join=hits / max(1, n),
                        selectivity=hits / max(1, n) / (self.N * (self.N - 1)
                                                         / 2))

    def release(self):
        run = self.run
        run.keep.update(corpora=self.corpora, results=self.results)
        self.corpora = self.results = self.mesh = None


def check(run):
    """Every pair of every join against the reference's float64 set; the
    run's notes also get ``near_threshold_share``, the smallest over the
    joined corpora of :func:`near_threshold_share`."""
    corpora, results = run.keep["corpora"], run.keep["results"]
    thr, metric = float(run.config["threshold"]), run.config["metric"]
    ref = run.reference()
    want = {c: ref.exact_join(corpora[c], thr, metric)
            for c in sorted({c for c, *_ in results})}
    control = {}
    missing = extra = invalid = 0
    err = 0.0
    for c, i, j, s in results:
        if run.control:
            if c not in control:
                control[c] = ref.control_join(corpora[c], thr, metric)
            i, j, s = control[c]
        N = len(corpora[c])
        got = compare_pairs(i, j, s, *want[c], N, thr,
                            ref.pair_scores(corpora[c], i, j, metric))
        missing += got["missing_pairs"]
        extra += got["extra_pairs"]
        invalid += got["invalid_pairs"]
        err = max(err, got["score_rel_err"])
    run.checks += [("missing_pairs", missing, 0), ("extra_pairs", extra, 0),
                   ("invalid_pairs", invalid, 0),
                   ("score_rel_err", err, run.limit("score_rel_err"))]
    run.notes["pairs_checked"] = int(sum(len(r[1]) for r in results))
    if want:
        run.notes["near_threshold_share"] = min(
            near_threshold_share(w[2], thr) for w in want.values())


def near_threshold_share(ref_s, threshold, within=NEAR):
    """Share of the reference's passing pairs whose float64 score lies
    within ``within`` of the threshold (the data's calibration: pairs the
    float32 scoring can misplace are common, not rare)."""
    ref_s = np.asarray(ref_s, np.float64)
    if not len(ref_s):
        return 0.0
    return float(np.mean(ref_s - threshold <= within))
