"""k-NN graph microbenchmark (CPU, subprocess-isolated fake devices):
the all-pairs per-row top-k engine per execution mode, fused kernel vs
the unfused batched path — the fourth member of the benchmark JSON
family (DESIGN.md section 12.3).

Timings are steady-state medians of the cached jitted program (one
graph construction per call over the quorum-sharded corpus), for the
same load-noise reasons as bench_engine.  The oracle pass doubles as a
correctness gate: the timed program's output must match the dense
brute-force graph exactly before any number is recorded.  Writes
BENCH_knn.json at the repo root (CI uploads it next to the other
BENCH_*.json artifacts and diffs it with ``benchmarks.run --compare``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"
JSON_PATH = ROOT / "BENCH_knn.json"

_CHILD = r"""
import json, statistics, sys, time
import numpy as np, jax, jax.numpy as jnp
from repro.core.knn import _knn_fn, brute_force_knn, knn_graph
from repro.core.placement import get_placement

P = int(sys.argv[1]); N = int(sys.argv[2]); d = int(sys.argv[3])
topk = int(sys.argv[4])
rng = np.random.default_rng(0)
corpus = rng.normal(size=(N, d)).astype(np.float32)
mesh = jax.make_mesh((P,), ("q",), axis_types=(jax.sharding.AxisType.Auto,))
plc = get_placement("cyclic", P)
block = -(-N // P)

# correctness gate: the timed configuration must be oracle-exact
want = brute_force_knn(corpus, topk)
got = knn_graph(corpus, mesh, topk=topk, mode="scan", placement=plc)
assert (got.indices == want.indices).all(), "scan mode oracle mismatch"

x = np.zeros((P * block, d), np.float32); x[:N] = corpus
xs = jnp.asarray(x)

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
for name, mode, uk in [("batched", "batched", False),
                       ("kernel", "batched", True),
                       ("overlap", "overlap", False),
                       ("scan", "scan", False)]:
    run = _knn_fn(mesh, "q", N, block, topk, "dot", mode, uk, plc)
    gv, gi = run(xs)
    assert (np.asarray(gi)[:N] == want.indices).all(), name
    out[name] = bench(lambda run=run: run(xs))
out["block"] = block
print(json.dumps(out))
"""


def run(csv_rows, N: int = 2048, d: int = 32, topk: int = 8):
    results: dict = {"N": N, "d": d, "topk": topk, "timings_s": {}}
    for P in [8]:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={P}"
        env["JAX_PLATFORMS"] = "cpu"     # virtual devices, never the chip
        env["PYTHONPATH"] = str(SRC)
        r = subprocess.run([sys.executable, "-c", _CHILD, str(P), str(N),
                            str(d), str(topk)],
                           env=env, capture_output=True, text=True,
                           timeout=900)
        assert r.returncode == 0, r.stderr[-2000:]
        res = json.loads(r.stdout.strip().splitlines()[-1])
        timings = {k: v for k, v in res.items() if k != "block"}
        results["timings_s"][str(P)] = timings
        best = min(timings, key=timings.get)
        results["best_mode"] = {str(P): best}
        results["fused_vs_batched"] = {
            str(P): timings["batched"] / timings["kernel"]}
        csv_rows.append((
            f"knn_graph_P{P}",
            f"{timings[best] * 1e6:.0f}",
            f"best={best};topk={topk}"
            f";fused_vs_batched={results['fused_vs_batched'][str(P)]:.2f}"
            + ";" + ";".join(f"{k}_us={v * 1e6:.0f}"
                             for k, v in timings.items())))
    JSON_PATH.write_text(json.dumps(results, indent=2) + "\n")
