"""Continuous-batching latency benchmark: per-request p50/p99 and
steady-state qps for a heterogeneous request stream (CPU,
subprocess-isolated fake devices) — the serving-front-end half of the
online numbers, next to BENCH_serve.json's per-mode program throughput.

Drives ``serving.batching.BatchScheduler`` at P=8 with the traffic the
scheduler was built for (DESIGN.md section 15): a deterministic mix of
top-k requests with different k, threshold requests with different
thresholds and capacities, dot and l2 — packed into shared padded
launches.  A warmup wave containing every (kind, metric, bucket)
combination compiles the full program set first, so the measured window
is steady-state: what the scheduler serves once its handful of
quantized programs (DESIGN.md section 15.2) is hot.  Per-request
latency is submit-to-resolve from the scheduler's own trace; qps is
requests / wall over the measured window.  Writes BENCH_latency.json at
the repo root (CI uploads it next to the other BENCH_*.json files;
p50/p99 live under ``timings_s`` and throughput under ``qps`` so the
``--compare`` guard covers both directions).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"
JSON_PATH = ROOT / "BENCH_latency.json"

_CHILD = r"""
import json, sys, time
import numpy as np, jax
from repro.serving import ServingCorpus
from repro.serving.batching import BatchScheduler, latency_summary

P = int(sys.argv[1]); N = int(sys.argv[2]); R = int(sys.argv[3]); d = 64
rng = np.random.default_rng(0)
corpus = rng.normal(size=(N, d)).astype(np.float32)
mesh = jax.make_mesh((P,), ("q",), axis_types=(jax.sharding.AxisType.Auto,))
sc = ServingCorpus.build(corpus, mesh)
sched = BatchScheduler(sc, max_batch=32)

# deterministic heterogeneous mix: mixed k, mixed thresholds/capacities,
# both metrics — cycled so every wave packs all four (kind, metric)
# groups.  Thresholds are far enough out that matches stay sparse and
# the capacity ladder is exercised without escalating to the full
# corpus.
MIX = [
    dict(kind="topk", topk=1, metric="dot"),
    dict(kind="topk", topk=4, metric="dot"),
    dict(kind="topk", topk=8, metric="dot"),
    dict(kind="topk", topk=16, metric="dot"),
    dict(kind="topk", topk=4, metric="l2"),
    dict(kind="topk", topk=8, metric="l2"),
    dict(kind="threshold", threshold=24.0, capacity=32, metric="dot"),
    dict(kind="threshold", threshold=16.0, capacity=64, metric="dot"),
    dict(kind="threshold", threshold=-9.0, metric="l2"),
    dict(kind="threshold", threshold=-10.0, capacity=32, metric="l2"),
]

def wave(n, seed):
    qs = np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)
    reqs = [sched.submit(qs[i], **MIX[i % len(MIX)]) for i in range(n)]
    sched.step()
    sched.drain()                       # finish capacity-escalated requeues
    return reqs

for w in range(2):       # compile + warm every program at measured widths
    wave(32, seed=100 + w)

n0 = len(sched.latencies_s)
t0 = time.perf_counter()
done = 0
while done < R:
    n = min(32, R - done)
    wave(n, seed=done)
    done += n
span = time.perf_counter() - t0
lat = latency_summary(sched.latencies_s[n0:], span)
out = {"qps": lat["qps"], "p50_s": lat["p50_s"], "p99_s": lat["p99_s"],
       "mean_s": lat["mean_s"], "n": lat["n"],
       "launches": sched.counters["launches"],
       "steps": sched.counters["steps"],
       "escalations": sched.counters["escalations"],
       "programs": len(sched.program_keys)}
print(json.dumps(out))
"""


def run(csv_rows, N: int = 4096, R: int = 256):
    results: dict = {"N": N, "R": R, "mix": "topk k in {1,4,8,16} x "
                     "{dot,l2} + threshold (mixed thr/capacity) x {dot,l2}",
                     "qps": {}, "timings_s": {}, "counters": {}}
    for P in [8]:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={P}"
        env["JAX_PLATFORMS"] = "cpu"     # virtual devices, never the chip
        env["PYTHONPATH"] = str(SRC)
        r = subprocess.run([sys.executable, "-c", _CHILD, str(P), str(N),
                            str(R)],
                           env=env, capture_output=True, text=True,
                           timeout=900)
        assert r.returncode == 0, r.stderr[-2000:]
        res = json.loads(r.stdout.strip().splitlines()[-1])
        results["qps"][str(P)] = res["qps"]
        results["timings_s"][str(P)] = {"p50": res["p50_s"],
                                        "p99": res["p99_s"],
                                        "mean": res["mean_s"]}
        results["counters"][str(P)] = {
            k: res[k] for k in ("launches", "steps", "escalations",
                                "programs", "n")}
        csv_rows.append((
            f"serve_latency_P{P}", f"{res['p50_s'] * 1e6:.0f}",
            f"qps={res['qps']:.1f};p99_us={res['p99_s'] * 1e6:.0f};"
            f"launches={res['launches']};programs={res['programs']};"
            f"escalations={res['escalations']}"))
    JSON_PATH.write_text(json.dumps(results, indent=2) + "\n")
