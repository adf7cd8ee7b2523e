"""Serving microbenchmark: steady-state query throughput per engine mode
(CPU, subprocess-isolated fake devices), fused Pallas kernel vs the
unfused jnp reference path — the online half of BENCH_engine.json.

Times the cover-routed top-k program at P=8 in steady state (the jitted
program is built once via serving.engine.query_fn's cache) for every
local-scoring mode plus the fused-kernel batched path, and the
re-jit-per-call baseline (``cold_jit``: query_fn cache cleared every
call — what serving costs without the program cache).  Writes raw
queries/sec to BENCH_serve.json at the repo root (CI uploads it next to
BENCH_engine.json).

Caveat baked into the numbers: on CPU the Pallas kernel runs in interpret
mode (the kernel body is traced into XLA rather than compiled for TPU), so
``fused`` here measures the *algorithmic* fusion win — the running
extract-max top-k (O(topk * block) per slot) replacing the full two-key
sort over k*block candidates — not the TPU DMA/VMEM effects; medians for
the same load-noise reason as bench_engine.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"
JSON_PATH = ROOT / "BENCH_serve.json"

_CHILD = r"""
import json, statistics, sys, time
import numpy as np, jax, jax.numpy as jnp
from repro.serving import ServingCorpus
from repro.serving.engine import query_fn

P = int(sys.argv[1]); N = int(sys.argv[2]); Q = int(sys.argv[3])
topk = int(sys.argv[4]); d = 64
rng = np.random.default_rng(0)
corpus = rng.normal(size=(N, d)).astype(np.float32)
queries = rng.normal(size=(Q, d)).astype(np.float32)
mesh = jax.make_mesh((P,), ("q",), axis_types=(jax.sharding.AxisType.Auto,))
sc = ServingCorpus.build(corpus, mesh)

def bench(fn, reps=15):
    fn()                                        # compile
    fn()                                        # warm caches
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return Q / statistics.median(ts)            # queries/sec

def run(mode, uk):
    v, i = sc.query(queries, topk=topk, mode=mode, use_kernel=uk)
    jax.block_until_ready((v, i))

out = {}
for name, mode, uk in [("batched", "batched", False),
                       ("fused", "batched", True),
                       ("overlap", "overlap", False),
                       ("scan", "scan", False)]:
    out[name] = bench(lambda: run(mode, uk))

def cold():
    query_fn.cache_clear()
    run("batched", False)
out["cold_jit"] = bench(cold, reps=3)
out["n_cover"] = sc.plan.n_cover
print(json.dumps(out))
"""


def run(csv_rows, N: int = 4096, Q: int = 32, topk: int = 8):
    results: dict = {"N": N, "Q": Q, "topk": topk, "qps": {},
                     "n_cover": {}}
    for P in [8]:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={P}"
        env["JAX_PLATFORMS"] = "cpu"     # virtual devices, never the chip
        env["PYTHONPATH"] = str(SRC)
        r = subprocess.run([sys.executable, "-c", _CHILD, str(P), str(N),
                            str(Q), str(topk)],
                           env=env, capture_output=True, text=True,
                           timeout=900)
        assert r.returncode == 0, r.stderr[-2000:]
        res = json.loads(r.stdout.strip().splitlines()[-1])
        n_cover = res.pop("n_cover")
        results["qps"][str(P)] = res
        results["n_cover"][str(P)] = n_cover
        best = max((m for m in res), key=lambda m: res[m])
        csv_rows.append((
            f"query_serve_P{P}", f"{1e6 / res[best]:.0f}",
            f"best={best};cover={n_cover}/{P};" + ";".join(
                f"{m}_qps={res[m]:.1f}" for m in res) +
            f";fused_vs_batched={res['fused'] / res['batched']:.3f}"))
    results["fused_vs_batched"] = {
        P: r["fused"] / r["batched"] for P, r in results["qps"].items()}
    JSON_PATH.write_text(json.dumps(results, indent=2) + "\n")
