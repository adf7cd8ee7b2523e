"""Serving traffic: an open loop of top-k requests on a fixed schedule.

Traffic file keys: ``rate_per_s`` (one fixed rate), or ``rates_per_s`` (a
list the run steps through in equal parts of the window: the knee
sweep), ``topk``, and ``check_requests`` (completed requests compared with
the reference, drawn from the seed).  Requests go to the program's
``BatchScheduler`` running its background loop, each when it is due
whether or not earlier ones have finished.  Each is timed from its due
time to its result on the host; one that fails, is refused or never
finishes is infinitely late.

End-to-end metrics: ``serve_p90_ms`` over every request due in the
window, and ``serve_qps``, the requests completed in the window over its
seconds.  The 50th and 99th percentiles are reported beside them in the
result line's ``run`` record: on a one-chip machine the host pauses the
whole process for ~110 ms a few times a minute, and one such pause moves
the 99th percentile of a 20 s window by a factor of four.
"""

from __future__ import annotations

import math
import queue
import sys
import threading
import time

import numpy as np

from yardstick import arrivals, compare, data, stats

GRACE_S = 60.0          # how long past the window a result is waited for
INF_MS = 1e9            # reported for a percentile on a lost request


def q_buckets(max_batch: int):
    """Every launch width a batcher of ``max_batch`` can use (powers of
    two up to it)."""
    out, q = [], 1
    while q < max_batch:
        out.append(q)
        q *= 2
    return out + [max_batch]


class Driver:
    def __init__(self, run):
        self.run = run
        cfg, tr = run.config, run.traffic
        self.N, self.d = int(cfg["rows"]), int(cfg["dim"])
        self.topk, self.metric = int(tr["topk"]), cfg["metric"]
        self.rates = tr.get("rates_per_s") or [tr["rate_per_s"]]

    def setup(self):
        import jax
        from repro.launch.mesh import make_mesh
        from repro.serving.batching import BatchScheduler
        from repro.serving.engine import ServingCorpus
        run, cfg = self.run, self.run.config
        t = time.monotonic()
        self.corpus = data.gaussian_rows(run.seed, 1, self.N, self.d,
                                         unit=True)
        mesh = make_mesh((run.chips,), ("q",), devices=run.devices)
        self.sc = ServingCorpus.build(self.corpus, mesh,
                                      placement=cfg["placement"],
                                      quant=cfg["quant"])
        jax.block_until_ready(self.sc.state.stack)
        run.notes["setup_build_s"] = time.monotonic() - t
        t = time.monotonic()
        kw = dict(topk=self.topk, metric=self.metric, mode=cfg["mode"],
                  use_kernel=bool(cfg["use_kernel"]))
        max_batch = int(cfg["max_batch"])
        for q in q_buckets(max_batch):       # every width a launch can have
            out = self.sc.query(np.zeros((q, self.d), np.float32), **kw)
            jax.device_get(out)
        rng = data.host_rng(run.seed, 2)
        self.offsets, self.step = arrivals.stepped_offsets(
            self.rates, run.seconds, rng)
        self.queries = data.gaussian_rows(run.seed, 2, len(self.offsets),
                                          self.d, unit=True)
        self.sched = BatchScheduler(
            self.sc, max_batch=max_batch, max_queue=int(cfg["max_queue"]),
            mode=cfg["mode"], use_kernel=bool(cfg["use_kernel"]))
        self.sched.start()
        warm = [self.sched.submit(q, kind="topk", topk=self.topk,
                                  metric=self.metric)
                for q in self.queries[:max_batch]]
        for r in warm:
            r.result(timeout=600)
        run.notes["setup_warm_s"] = time.monotonic() - t
        self.annotate = jax.profiler.TraceAnnotation

    def window(self, t0):
        from repro.serving.batching import AdmissionError
        run, n = self.run, len(self.offsets)
        due = t0 + self.offsets
        sub = np.full(n, np.nan)
        done = [None] * n
        status = ["unsent"] * n
        answers = {}
        c0 = dict(self.sched.counters)
        inbox: queue.SimpleQueue = queue.SimpleQueue()
        give_up = t0 + run.seconds + GRACE_S

        def collect():
            while True:
                item = inbox.get()
                if item is None:
                    return
                j, req = item
                try:
                    res = req.result(timeout=max(0.0, give_up - time.monotonic()))
                except TimeoutError:
                    status[j] = "unfinished"
                    continue
                t = time.monotonic()
                status[j] = res.status
                if res.ok:
                    done[j] = t
                    answers[j] = (res.indices, res.scores)

        collector = threading.Thread(target=collect, name="bench-collector")
        collector.start()
        try:
            for j in range(n):
                delay = due[j] - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                sub[j] = time.monotonic()
                try:
                    req = self.sched.submit(self.queries[j], kind="topk",
                                            topk=self.topk,
                                            metric=self.metric)
                except AdmissionError:
                    status[j] = "rejected"
                    continue
                inbox.put((j, req))
        finally:
            inbox.put(None)
            collector.join()
        c1 = dict(self.sched.counters)
        run.counters = {k: c1.get(k, 0) - c0.get(k, 0) for k in c1}
        end = t0 + run.seconds
        lat = stats.latencies_from_due(due, done)
        ok = [t is not None for t in done]
        ms = {q: stats.percentile(lat, q) * 1e3 for q in (50.0, 90.0, 99.0)}
        ms = {q: v if math.isfinite(v) else INF_MS for q, v in ms.items()}
        run.e2e["serve_p90_ms"] = ms[90.0]
        run.e2e["serve_qps"] = sum(t is not None and t <= end
                                   for t in done) / run.seconds
        run.attempted, run.failed = n, n - sum(ok)
        late = sub - due
        run.notes.update(
            rate_per_s=float(np.mean(self.rates)), requests=n,
            serve_p50_ms=ms[50.0], serve_p99_ms=ms[99.0],
            generator_late_p99_ms=stats.percentile(late, 99.0) * 1e3,
            generator_late_max_ms=float(np.max(late)) * 1e3,
            unfinished=status.count("unfinished"),
            rejected=status.count("rejected"))
        run.work.update(rows=self.N, dim=self.d, topk=self.topk,
                        requests=n)
        if len(self.rates) > 1:
            self._report_steps(due, sub, done, lat)
        run.keep.update(status=status, answers=answers)

    def _report_steps(self, due, sub, done, lat):
        """One stderr line per rate of a stepped run: the offered and the
        completed rate, latency, and the backlog in the middle and at the
        end of the step (growing past the knee)."""
        step_s = self.run.seconds / len(self.rates)
        t0 = due[0] - self.offsets[0]
        fin = np.array([np.inf if t is None else t for t in done])
        for k, rate in enumerate(self.rates):
            mine = self.step == k
            a, b = t0 + k * step_s, t0 + (k + 1) * step_s

            def backlog(t):
                return int((sub <= t).sum() - (fin <= t).sum())
            l = [x for x, m in zip(lat, mine) if m]
            print(f"sweep rate_per_s={rate} offered={mine.sum() / step_s:.1f} "
                  f"completed_per_s={((fin > a) & (fin <= b)).sum() / step_s:.1f} "
                  f"p50_ms={stats.percentile(l, 50) * 1e3:.3f} "
                  f"p99_ms={stats.percentile(l, 99) * 1e3:.3f} "
                  f"backlog_mid={backlog((a + b) / 2)} backlog_end={backlog(b)} "
                  f"late_p99_ms={stats.percentile((sub - due)[mine], 99) * 1e3:.3f}",
                  file=sys.stderr, flush=True)

    def release(self):
        self.sched.stop()
        self.run.keep.update(corpus=self.corpus, queries=self.queries)
        self.sched = self.sc = None


def check(run):
    """A sample of the completed requests, drawn from the seed, against
    the reference; every completed answer against what no right answer
    can be; every request that never finished is wrong."""
    corpus, queries = run.keep["corpus"], run.keep["queries"]
    answers, status = run.keep["answers"], run.keep["status"]
    topk, metric = int(run.traffic["topk"]), run.config["metric"]
    done = np.array(sorted(answers))
    invalid = 0
    if len(done):
        idx = np.stack([answers[j][0] for j in done])
        sc = np.stack([answers[j][1] for j in done])
        invalid = compare.invalid_rows(idx, sc, len(corpus))
    rng = data.host_rng(run.seed, 99)
    m = min(int(run.traffic["check_requests"]), len(done))
    pick = np.sort(rng.choice(done, size=m, replace=False)) if m else done
    wrong, err = 0, 0.0
    if m:
        ref = run.reference()
        got_i = np.stack([answers[j][0] for j in pick])
        got_s = np.stack([answers[j][1] for j in pick])
        if run.control:
            got_i, got_s = ref.control_topk(queries[pick], corpus, topk,
                                            metric)
        ri, rs = ref.topk(queries[pick], corpus, topk, metric)
        true = ref.true_scores(queries[pick], corpus, got_i, metric)
        got = compare.compare_topk(got_i, got_s, ri, rs, true, topk)
        wrong, err = got["wrong_rows"], got["score_rel_err"]
    run.checks += [("wrong_rows", wrong, 0), ("invalid_rows", invalid, 0),
                   ("unfinished", status.count("unfinished"), 0),
                   ("score_rel_err", err, run.limit("score_rel_err"))]
    run.notes["rows_checked"] = int(m)
