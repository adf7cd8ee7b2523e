#!/usr/bin/env python3
"""Smoke test of the quorum all-pairs engine on TPU chips.

Drives the engine's main path once through the entry points a user calls,
at data sizes a deployment would hold, and checks every answer against a
plain numpy reference computed in float64:

  python chip_smoke.py             # one chip: k-NN graph, serving, join
  python chip_smoke.py --chips 4   # four chips: the quorum gather/scatter
                                   # path (cyclic P=4) against all-gather

One process drives every chip.  Each phase prints one line (platform,
device kind, sizes, compile and run seconds, rows checked, max |err|);
the last line is ``{"ok": true, "device": {...}}`` and appears only when
every phase matched its reference.  Without a TPU the script exits
nonzero before any phase.  Indices are compared exactly; the only rows
allowed to differ are those whose reference scores tie within 1e-6
relative among the top k + 1 (for the join: pairs within 1e-6 of the
threshold), and their count is printed.

Compile seconds are JAX's own trace, lowering and backend-compile (or
compile-cache retrieval) durations; run seconds are host wall time of a
second, warm call that ends with the results on the host.  The compile
cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TIE_REL = 1e-6
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Sums JAX's compile-duration events (process-wide listener)."""

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.total += duration


def timed(clock, fn):
    """(result, compile seconds, wall seconds) of one call of ``fn``."""
    c0, t0 = clock.total, time.perf_counter()
    out = fn()
    return out, clock.total - c0, time.perf_counter() - t0


def ref_topk(queries, corpus, topk, metric, exclude=None, chunk=1 << 16):
    """float64 top-k of every query row over ``corpus`` rows under the
    engine's (-score, index) order, plus the (k+1)-th score (the cut).
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


def near_tie(scores, topk):
    """Rows whose reference scores tie within TIE_REL among the first
    topk + 1 (order inside the list or membership at the cut)."""
    a, b = scores[:, :topk], scores[:, 1:topk + 1]
    return (np.abs(a - b) <= TIE_REL * np.maximum(np.abs(a), np.abs(b))).any(1)


def check_topk(got_i, got_s, want_i, want_s, topk, what):
    """Exact index match outside near-tie rows; returns (mismatched,
    near-tie rows, max |score err| over matching rows)."""
    ties = near_tie(want_s, topk)
    bad = (got_i != want_i[:, :topk]).any(1)
    if (bad & ~ties).any():
        rows = np.nonzero(bad & ~ties)[0][:5]
        raise AssertionError(f"{what}: indices differ from the reference "
                             f"at rows {rows.tolist()}")
    ok = ~bad
    err = np.abs(got_s[ok].astype(np.float64) - want_s[ok, :topk])
    return int(bad.sum()), int(ties.sum()), float(err.max(initial=0.0))


def compare_baseline(name, got_i, got_s, base_i, base_s, want_i, want_s,
                     topk, rows=None):
    """Cyclic against the ``name`` placement on every row (a row may
    differ only by swapping candidates of equal score), and the baseline
    against the reference on the checked ``rows``."""
    differ = (base_i != got_i).any(1)
    s_a, s_b = got_s[differ], base_s[differ]
    tol = TIE_REL * np.maximum(np.abs(s_a), np.abs(s_b))
    if not (np.abs(s_a - s_b) <= tol).all():
        raise AssertionError(f"cyclic and {name} disagree")
    if rows is not None:
        base_i, base_s = base_i[rows], base_s[rows]
    bad, _, err = check_topk(base_i, base_s, want_i, want_s, topk, name)
    return {f"{name}_rows_differing": int(differ.sum()),
            f"{name}_mismatched": bad, f"{name}_max_abs_err": err}


def device_fields():
    import jax
    dev = jax.devices()[0]
    return dev.platform, dev.device_kind, len(jax.devices())


def report(phase, **fields):
    platform, kind, count = device_fields()
    items = [f"phase={phase}", f"platform={platform}", f"kind={kind!r}",
             f"count={count}"] + [f"{k}={v}" for k, v in fields.items()]
    print(" ".join(items), flush=True)


def knn_run(corpus, mesh, placement, topk, metric):
    from repro.core.knn import knn_graph
    return knn_graph(corpus, mesh, topk=topk, metric=metric, mode="batched",
                     placement=placement, use_kernel=True, quant="off")


def phase_knn(clock, rng, *, N, d, P, topk=10, metric="l2", sample=256,
              baseline=None):
    """Exact k-NN graph through knn_graph with the fused tiled kernel;
    with ``baseline`` (a placement name) also against that placement."""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((P,), ("q",))
    corpus = rng.standard_normal((N, d), dtype=np.float32)
    first, c_s, first_s = timed(
        clock, lambda: knn_run(corpus, mesh, "cyclic", topk, metric))
    got, _, run_s = timed(
        clock, lambda: knn_run(corpus, mesh, "cyclic", topk, metric))
    np.testing.assert_array_equal(first.indices, got.indices)
    rows = np.sort(rng.choice(N, size=min(sample, N), replace=False))
    want_i, want_s = ref_topk(corpus[rows], corpus, topk, metric,
                              exclude=rows)
    bad, ties, err = check_topk(got.indices[rows], got.scores[rows],
                                want_i, want_s, topk, "knn")
    fields = dict(N=N, d=d, P=P, placement="cyclic", topk=topk,
                  metric=metric, kernel="pairwise_topk", compile_s=c_s,
                  first_s=first_s, run_s=run_s, rows_checked=len(rows),
                  mismatched=bad, near_tie_rows=ties, max_abs_err=err)
    if baseline is not None:
        base, bc_s, _ = timed(
            clock, lambda: knn_run(corpus, mesh, baseline, topk, metric))
        base, _, brun_s = timed(
            clock, lambda: knn_run(corpus, mesh, baseline, topk, metric))
        fields.update(compare_baseline(
            baseline, got.indices, got.scores, base.indices, base.scores,
            want_i, want_s, topk, rows=rows))
        fields.update({f"{baseline}_compile_s": bc_s,
                       f"{baseline}_run_s": brun_s})
    report("knn", **fields)


def serve_drain(sc, queries, topk):
    from repro.serving.batching import BatchScheduler
    sched = BatchScheduler(sc, max_batch=32, max_queue=len(queries),
                           use_kernel=True, pad_queries_to=32)
    reqs = [sched.submit(q, kind="topk", topk=topk, metric="dot")
            for q in queries]
    sched.drain()
    out = [r.result(timeout=0) for r in reqs]
    if any(not o.ok for o in out):
        raise AssertionError("serving: a request did not complete")
    return (np.stack([o.indices for o in out]),
            np.stack([o.scores for o in out]))


def serve_run(clock, corpus, mesh, placement, queries, topk):
    """(corpus handle, build seconds, first drain, its compile seconds
    and wall seconds, warm drain wall seconds) of one placement."""
    from repro.serving.engine import ServingCorpus
    t0 = time.perf_counter()
    sc = ServingCorpus.build(corpus, mesh, placement=placement, quant="off")
    sc.state.stack.block_until_ready()
    build_s = time.perf_counter() - t0
    first, c_s, first_s = timed(clock, lambda: serve_drain(sc, queries, topk))
    got, _, run_s = timed(clock, lambda: serve_drain(sc, queries, topk))
    np.testing.assert_array_equal(first[0], got[0])
    return sc, got, build_s, c_s, first_s, run_s


def phase_serve(clock, rng, *, N, d, P, n_queries=256, topk=10,
                baseline=None):
    """Top-k dot serving: ServingCorpus behind BatchScheduler, fused
    query kernel, cover-routed over the cyclic quorum; with ``baseline``
    (a placement name) also against that placement."""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((P,), ("q",))
    corpus = rng.standard_normal((N, d), dtype=np.float32)
    queries = rng.standard_normal((n_queries, d), dtype=np.float32)
    sc, (got_i, got_s), build_s, c_s, first_s, run_s = serve_run(
        clock, corpus, mesh, "cyclic", queries, topk)
    want_i, want_s = ref_topk(queries, corpus, topk, "dot")
    bad, ties, err = check_topk(got_i, got_s, want_i, want_s, topk,
                                "serving")
    fields = dict(N=N, d=d, P=P, placement="cyclic", cover=sc.plan.n_cover,
                  k=sc.plan.k,
                  resident_bytes_per_chip=int(sc.state.stack.nbytes // P),
                  microbatches=n_queries // 32, Q=32, topk=topk,
                  metric="dot", kernel="query_topk", build_s=build_s,
                  compile_s=c_s, first_s=first_s, run_s=run_s,
                  rows_checked=n_queries, mismatched=bad, near_tie_rows=ties,
                  max_abs_err=err)
    if baseline is not None:
        del sc
        _, (base_i, base_s), bb_s, bc_s, _, brun_s = serve_run(
            clock, corpus, mesh, baseline, queries, topk)
        fields.update(compare_baseline(baseline, got_i, got_s, base_i,
                                       base_s, want_i, want_s, topk))
        fields.update({f"{baseline}_build_s": bb_s,
                       f"{baseline}_compile_s": bc_s,
                       f"{baseline}_run_s": brun_s})
    report("serving", **fields)


def phase_join(clock, rng, *, N, d, P=1, dup_frac=0.01, threshold=0.9):
    """Thresholded self-join over unit-norm rows with planted
    near-duplicates, through the fused join kernel in the batched mode."""
    from repro.core.sparse import similarity_join
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((P,), ("q",))
    x = rng.standard_normal((N, d), dtype=np.float32)
    n_dup = int(N * dup_frac)
    perm = rng.permutation(N)
    src, dst = perm[:n_dup], perm[n_dup:2 * n_dup]
    x[dst] = x[src] + 0.01 * rng.standard_normal((n_dup, d), dtype=np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    run = lambda: similarity_join(x, mesh, threshold=threshold, metric="dot",
                                  mode="batched", placement="cyclic",
                                  use_kernel=True, quant="off")
    first, c_s, first_s = timed(clock, run)
    got, _, run_s = timed(clock, run)
    np.testing.assert_array_equal(first.i, got.i)
    x64 = x.astype(np.float64)
    s = x64 @ x64.T
    # every pair at or above the threshold, and every pair within
    # TIE_REL of it, lies in this upper-triangle candidate set
    iu, ju = np.nonzero(np.triu(s >= threshold * (1 - TIE_REL), 1))
    sv = s[iu, ju]
    ties = np.abs(sv - threshold) <= TIE_REL * threshold
    keep = sv >= threshold
    want = set(zip(iu[keep & ~ties].tolist(), ju[keep & ~ties].tolist()))
    tied = set(zip(iu[ties].tolist(), ju[ties].tolist()))
    have = set(zip(got.i.tolist(), got.j.tolist()))
    if (have - tied) != want:
        raise AssertionError(
            f"join: {len(have - tied - want)} extra and "
            f"{len(want - have)} missing pairs against the reference")
    err = np.abs(got.scores.astype(np.float64) - s[got.i, got.j])
    report("join", N=N, d=d, P=P, placement="cyclic", threshold=threshold,
           metric="dot", mode="batched", kernel="pairwise_threshold",
           pairs=len(have), planted=n_dup, compile_s=c_s, first_s=first_s,
           run_s=run_s, rows_checked=N, near_tie_pairs=int(ties.sum()),
           max_abs_err=float(err.max(initial=0.0)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip quorum phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # the TPU library otherwise logs to /tmp/tpu_logs whatever TMPDIR says
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from repro.launch.cache import use_compile_cache
    cache_dir = use_compile_cache()
    platform, kind, count = device_fields()
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 2
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {count}", file=sys.stderr)
        return 2
    print(f"chip_smoke: jax {jax.__version__} cache={cache_dir}", flush=True)
    clock = CompileClock()
    rng = np.random.default_rng(args.seed)
    if args.chips == 4:
        phase_knn(clock, rng, N=262_144, d=128, P=4, baseline="full")
        phase_serve(clock, rng, N=4_194_304, d=128, P=4, baseline="full")
    else:
        phase_knn(clock, rng, N=65_536, d=128, P=1)
        phase_serve(clock, rng, N=1_048_576, d=128, P=1)
        phase_join(clock, rng, N=16_384, d=128)
    print(json.dumps({"ok": True, "device": {"platform": platform,
                                             "kind": kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
