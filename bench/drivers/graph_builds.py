"""Batch traffic: exact k-NN graphs built back to back for the window.

Traffic file keys: ``corpora`` (distinct corpora generated from the seed
and built in turn, so no build can return an earlier build's answer) and
``check_rows_per_build`` (rows of each build compared with the reference,
drawn from the seed).  Every row of every build is also checked for
answers that cannot be right whatever the data.

End-to-end metric: ``batch_pairs_per_s``, the distinct unordered pairs
N(N - 1)/2 of every graph completed in the window over the window's
seconds (Gpair/s), the window ending when its last build completes.
"""

from __future__ import annotations

import time

import numpy as np

from yardstick import compare, data


class Driver:
    def __init__(self, run):
        self.run = run
        cfg = run.config
        self.N, self.d = int(cfg["rows"]), int(cfg["dim"])
        self.topk, self.metric = int(cfg["topk"]), cfg["metric"]

    def setup(self):
        import jax
        from repro.core.knn import knn_graph
        from repro.launch.mesh import make_mesh
        run, cfg = self.run, self.run.config
        n_corpora = int(run.traffic["corpora"])
        t = time.monotonic()
        self.corpora = [data.gaussian_rows(run.seed, 1 + c, self.N, self.d)
                        for c in range(n_corpora)]
        self.mesh = make_mesh((run.chips,), ("q",), devices=run.devices)
        self.knn_graph = knn_graph
        self.annotate = jax.profiler.TraceAnnotation
        run.notes["setup_data_s"] = time.monotonic() - t
        t = time.monotonic()
        self.build(0)                        # compiles, or loads the cache
        run.notes["setup_warm_s"] = time.monotonic() - t

    def build(self, c):
        cfg = self.run.config
        return self.knn_graph(
            self.corpora[c], self.mesh, topk=self.topk, metric=self.metric,
            mode=cfg["mode"], placement=cfg["placement"],
            use_kernel=bool(cfg["use_kernel"]), quant=cfg["quant"])

    def window(self, t0):
        run = self.run
        self.results = []
        end = t0 + run.seconds
        t_last = t0
        while t_last < end:
            c = len(self.results) % len(self.corpora)
            with self.annotate("bench.knn_graph"):
                res = self.build(c)
            t_last = time.monotonic()
            self.results.append((c, res.indices, res.scores))
        elapsed = t_last - t0
        n = len(self.results)
        run.attempted, run.failed = n, 0
        run.e2e["batch_pairs_per_s"] = \
            n * self.N * (self.N - 1) / 2 / elapsed / 1e9
        run.work.update(builds=n, rows=self.N, dim=self.d, topk=self.topk)

    def release(self):
        run = self.run
        run.keep.update(corpora=self.corpora, results=self.results)
        self.corpora = self.results = self.mesh = None


def check(run):
    """Sampled rows of every build against the reference; every row
    against what no right answer can be."""
    corpora, results = run.keep["corpora"], run.keep["results"]
    N = len(corpora[0])
    topk, metric = int(run.config["topk"]), run.config["metric"]
    per = int(run.traffic["check_rows_per_build"])
    rng = data.host_rng(run.seed, 99)
    picks = [np.sort(rng.choice(N, size=min(per, N), replace=False))
             for _ in results]
    ref = run.reference()
    wanted = {}
    for (c, _, _), rows in zip(results, picks):
        wanted.setdefault(c, set()).update(rows.tolist())
    refs = {}
    for c, rows in wanted.items():
        rows = np.array(sorted(rows))
        ri, rs = ref.topk(corpora[c][rows], corpora[c], topk, metric,
                          exclude=rows)
        refs[c] = {r: k for k, r in enumerate(rows)}, ri, rs
    wrong, err, invalid = 0, 0.0, 0
    self_rows = np.arange(N)
    for (c, idx, sc), rows in zip(results, picks):
        invalid += compare.invalid_rows(idx, sc, N, self_rows)
        pos, ri, rs = refs[c]
        k = np.array([pos[r] for r in rows])
        got_i, got_s = idx[rows], sc[rows]
        if run.control:
            got_i, got_s = ref.control_topk(corpora[c][rows], corpora[c],
                                            topk, metric, exclude=rows)
        true = ref.true_scores(corpora[c][rows], corpora[c], got_i, metric)
        got = compare.compare_topk(got_i, got_s, ri[k], rs[k], true, topk)
        wrong += got["wrong_rows"]
        err = max(err, got["score_rel_err"])
    run.checks += [("wrong_rows", wrong, 0), ("invalid_rows", invalid, 0),
                   ("score_rel_err", err, run.limit("score_rel_err"))]
    run.notes["rows_checked"] = int(sum(len(p) for p in picks))
