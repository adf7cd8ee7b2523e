"""Sparse similarity-join microbenchmark (CPU, subprocess-isolated fake
devices): the thresholded engine with and without the norm-bound
prefilter, per execution mode — the sparse third of the benchmark JSON
family (DESIGN.md section 11.5).

The corpus is crafted so block-level pruning has teeth: two of the P
blocks hold full-scale vectors, the rest are down-scaled, and the
threshold sits at a ~2% pair selectivity — so only big-block tiles can
pass and the prefilter skips ~90% of tiles whole.  ``scan`` mode turns
each skip into a real ``lax.cond`` FLOP saving, which is the
``prefilter_speedup`` headline (sparse-with-prefilter vs the same engine
computing every tile — the dense-scoring configuration); ``batched``
cannot skip inside one fused einsum and is timed for contrast.  Timings
are steady-state medians of the cached jitted program (the host
compaction is excluded), for the same load-noise reasons as
bench_engine.  Writes BENCH_sparse.json at the repo root (CI uploads it
next to BENCH_engine.json / BENCH_serve.json).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"
JSON_PATH = ROOT / "BENCH_sparse.json"

_CHILD = r"""
import json, statistics, sys, time
import numpy as np, jax, jax.numpy as jnp
from repro.core.placement import get_placement
from repro.core.sparse import (_join_fn, brute_force_join, default_capacity,
                               similarity_join, threshold_for_selectivity)

P = int(sys.argv[1]); N = int(sys.argv[2]); d = int(sys.argv[3])
rng = np.random.default_rng(0)
corpus = rng.normal(size=(N, d)).astype(np.float32)
block = -(-N // P)
corpus[2 * block:] *= 0.02          # only blocks 0-1 can clear the threshold
thr = threshold_for_selectivity(corpus, 0.02, "dot")
wi, _, _ = brute_force_join(corpus, thr, "dot")
selectivity = len(wi) / (N * (N - 1) // 2)

mesh = jax.make_mesh((P,), ("q",), axis_types=(jax.sharding.AxisType.Auto,))
plc = get_placement("cyclic", P)
sched = plc.schedule()

# host-side prune accounting: fraction of the tiles the engine actually
# computes (dedup-mask survivors — at even P one copy of each d=P/2
# orbit tile is mask-killed before any compute) whose bound misses the
# threshold, i.e. what the prefilter skips on top of the mask
from repro.core.allpairs import pair_mask_table
x = np.zeros((P * block, d), np.float32); x[:N] = corpus
norms = np.linalg.norm(x.reshape(P, block, d), axis=-1)
maxn = norms.max(axis=1)
mask = pair_mask_table(sched)                  # [P, n_pairs]
active = 0; total = 0
for dev in range(P):
    for s_i, (ga, gb) in enumerate(sched.global_pairs_of(dev)):
        if mask[dev, s_i] == 0:
            continue
        total += 1
        active += maxn[ga] * maxn[gb] >= thr
pruned_frac = 1.0 - active / total

xs = jnp.asarray(x)
cap = default_capacity(sched.n_pairs * block * block)
# one escalation-checked reference pass (also warms nothing: fresh caches)
res = similarity_join(corpus, mesh, threshold=thr, mode="scan",
                      placement=plc, capacity=cap)
assert res.n_pairs == len(wi), (res.n_pairs, len(wi))
cap = res.capacity

def bench(fn, reps=15):
    jax.block_until_ready(fn())                 # compile
    jax.block_until_ready(fn())                 # warm caches
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)   # median: fake devices oversubscribe cores

out = {}
for name, mode, prefilter in [("scan_prefilter", "scan", True),
                              ("scan_dense", "scan", False),
                              ("batched_prefilter", "batched", True),
                              ("batched_dense", "batched", False)]:
    run = _join_fn(mesh, "q", N, block, float(thr), "dot", mode, cap,
                   prefilter, False, plc)
    out[name] = bench(lambda run=run: run(xs))
out["selectivity"] = selectivity
out["pruned_tile_frac"] = pruned_frac
out["capacity"] = cap
out["threshold"] = float(thr)
out["n_hits"] = len(wi)
print(json.dumps(out))
"""


def run(csv_rows, N: int = 2048, d: int = 32):
    results: dict = {"N": N, "d": d, "timings_s": {}}
    for P in [8]:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={P}"
        env["JAX_PLATFORMS"] = "cpu"     # virtual devices, never the chip
        env["PYTHONPATH"] = str(SRC)
        r = subprocess.run([sys.executable, "-c", _CHILD, str(P), str(N),
                            str(d)],
                           env=env, capture_output=True, text=True,
                           timeout=900)
        assert r.returncode == 0, r.stderr[-2000:]
        res = json.loads(r.stdout.strip().splitlines()[-1])
        timings = {k: v for k, v in res.items()
                   if k.endswith(("_prefilter", "_dense"))}
        results["timings_s"][str(P)] = timings
        results["selectivity"] = res["selectivity"]
        results["pruned_tile_frac"] = res["pruned_tile_frac"]
        results["threshold"] = res["threshold"]
        results["n_hits"] = res["n_hits"]
        results["capacity"] = res["capacity"]
        # the headline: prefilter vs dense scoring, same engine/mode, and
        # best-sparse vs best-dense across modes
        results["prefilter_speedup"] = {
            str(P): timings["scan_dense"] / timings["scan_prefilter"]}
        best_sparse = min(timings["scan_prefilter"],
                          timings["batched_prefilter"])
        best_dense = min(timings["scan_dense"], timings["batched_dense"])
        results["sparse_vs_dense"] = {str(P): best_dense / best_sparse}
        csv_rows.append((
            f"sparse_join_P{P}",
            f"{timings['scan_prefilter'] * 1e6:.0f}",
            f"selectivity={res['selectivity']:.4f}"
            f";pruned={res['pruned_tile_frac']:.2f}"
            f";prefilter_speedup="
            f"{results['prefilter_speedup'][str(P)]:.2f}"
            f";sparse_vs_dense={results['sparse_vs_dense'][str(P)]:.2f}"
            + ";" + ";".join(f"{k}_us={v * 1e6:.0f}"
                             for k, v in timings.items())))
    JSON_PATH.write_text(json.dumps(results, indent=2) + "\n")
