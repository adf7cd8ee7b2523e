"""Paper Fig. 2 (left): PCIT runtime vs number of processes.

Replicates the experiment's structure: quorum PCIT under shard_map with
P in {1, 2, 4, 8} fake host devices (subprocess per P so device counts do
not leak into the caller).

IMPORTANT measurement note: this container exposes ONE physical core, so
the P fake devices execute sequentially and wall-clock stays ~flat in P —
the honest observable here is TOTAL work, which is ~constant in P (the
quorum schedule computes each pair once).  The paper's 7x-on-8-nodes
wall-clock speedup corresponds to the derived ``ideal_speedup`` column
(total work / max per-process work = P * (P+1)/2 / ceil((P+1)/2)), which
the static per-difference balance achieves exactly on real parallel
hardware.  Fig. 2's memory panel is bench_memory.py (fully measurable).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"

_CHILD = r"""
import json, sys, time
import numpy as np, jax
from repro.apps.pcit import run_quorum_pcit
P = int(sys.argv[1]); N = int(sys.argv[2]); G = int(sys.argv[3])
rng = np.random.default_rng(0)
Z = rng.normal(size=(8, G)); W = rng.normal(size=(N, 8))
X = (W @ Z + 0.3 * rng.normal(size=(N, G))).astype(np.float32)
mesh = jax.make_mesh((P,), ("q",), axis_types=(jax.sharding.AxisType.Auto,))
run_quorum_pcit(X, mesh)               # compile warmup
t0 = time.perf_counter()
for _ in range(3):
    corr, keep = run_quorum_pcit(X, mesh)
dt = (time.perf_counter() - t0) / 3
print(json.dumps({"P": P, "sec": dt, "kept": float(keep.mean())}))
"""


def run(csv_rows, N: int = 192, G: int = 32):
    results = {}
    for P in [1, 2, 4, 8]:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={P}"
        env["JAX_PLATFORMS"] = "cpu"     # virtual devices, never the chip
        env["PYTHONPATH"] = str(SRC)
        r = subprocess.run([sys.executable, "-c", _CHILD, str(P), str(N),
                            str(G)], env=env, capture_output=True, text=True,
                           timeout=900)
        assert r.returncode == 0, r.stderr[-2000:]
        results[P] = json.loads(r.stdout.strip().splitlines()[-1])
    t1 = results[1]["sec"]
    for P, res in results.items():
        wall = t1 / res["sec"]
        # total pair-work = P*(P+1)/2 block pairs; per-process = ceil((P+1)/2)
        total_pairs = P * (P + 1) / 2
        per_proc = (P + 1 + 1) // 2 if P > 1 else 1
        ideal = total_pairs / per_proc if P > 1 else 1.0
        csv_rows.append((f"pcit_speedup_P{P}", f"{res['sec']*1e6:.0f}",
                         f"N={N};wall_ratio_1core={wall:.2f}x;"
                         f"ideal_speedup={ideal:.2f}x;"
                         f"kept={res['kept']:.3f}"))
