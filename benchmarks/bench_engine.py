"""Engine microbenchmarks: per-mode quorum vs all-gather all-pairs wall time
(CPU, subprocess-isolated fake devices) on the n-body kernel — the paper's
motivating algorithm family.

Times every engine execution mode (batched / overlap / scan, DESIGN.md
section 4) in steady state (jitted callable built once via
nbody.forces_fn's cache), the atom-decomposition all-gather baseline, and
``seed_scan`` — the seed engine's as-shipped behavior (serial scan plus a
fresh jax.jit per call), kept as the PR-over-PR reference point.  Writes
the raw per-mode seconds to BENCH_engine.json at the repo root so the perf
trajectory is tracked across PRs (CI uploads it as an artifact).

Caveats baked into the numbers: medians (the fake-device harness
oversubscribes host cores, so minima collapse to the collective-sync floor
and means are load-noise); on a few-core host the mode spread at small
n_pairs (P=4 -> 3 pairs) sits near that noise floor, while P=8 (5 pairs)
separates clearly.

Alongside the timings, a host-side ``placements`` section records, for
every registered placement defined at each benchmarked P (plus the
plane-friendly P = 13), the replication factor and the resident
bytes/device for the N-body working set — the storage axis the placement
layer trades against (DESIGN.md section 10).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"
JSON_PATH = ROOT / "BENCH_engine.json"

def _modes() -> list[str]:
    """Engine mode list, single-sourced from the engine (imported lazily so
    merely importing this module keeps the parent process jax-free — the
    benchmarks run in subprocess-isolated fake-device children)."""
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro.core.allpairs import ENGINE_MODES
    return list(ENGINE_MODES)

_CHILD = r"""
import json, statistics, sys, time
import numpy as np, jax, jax.numpy as jnp
from repro.apps import nbody
from repro.apps.nbody import distributed_forces
P = int(sys.argv[1]); N = int(sys.argv[2]); modes = sys.argv[3].split(",")
rng = np.random.default_rng(0)
bodies = np.concatenate([rng.normal(size=(N,3)),
                         rng.uniform(0.5,2,(N,1))], -1).astype(np.float32)
mesh = jax.make_mesh((P,), ("q",), axis_types=(jax.sharding.AxisType.Auto,))
out = {}

def bench(fn, reps=15):
    fn().block_until_ready()                    # compile
    fn().block_until_ready()                    # warm caches
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn().block_until_ready()
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)   # median: fake devices oversubscribe cores

xb = jnp.asarray(bodies)
for mode in modes:
    out[mode] = bench(lambda: distributed_forces(xb, mesh, strategy="quorum",
                                                 mode=mode))
out["atom"] = bench(lambda: distributed_forces(xb, mesh, strategy="atom"))

def seed_scan():
    # the seed engine as shipped: serial scan AND a fresh jax.jit every call
    nbody.forces_fn.cache_clear()
    return distributed_forces(xb, mesh, strategy="quorum", mode="scan")
out["seed_scan"] = bench(seed_scan, reps=3)

# traced comm volume (AFTER the timings, so they stay tracing-free):
# one fresh traced batched sweep; actuals must equal the analytical
# predictor exactly (DESIGN.md section 14.3)
from repro.obs import trace as obs_trace
from repro.obs.comm import predict_sweep_comm, traced_sweep_comm
from repro.core.placement import get_placement
tracer = obs_trace.configure()
nbody.forces_fn.cache_clear()
distributed_forces(xb, mesh, strategy="quorum",
                   mode="batched").block_until_ready()
got = traced_sweep_comm(tracer)
rows = N // P
pred = predict_sweep_comm(get_placement("cyclic", P), rows * 4 * 4,
                          partial_bytes=rows * 3 * 4)  # forces are [m, 3]
assert got["gather_bytes"] == pred.gather_bytes, (got, pred.as_dict())
assert got["scatter_bytes"] == pred.scatter_bytes, (got, pred.as_dict())
out["comm"] = {"traced": got, "predicted": pred.as_dict()}
obs_trace.reset()
nbody.forces_fn.cache_clear()
print(json.dumps(out))
"""


def placement_stats(N: int, Ps=(4, 8, 13)) -> dict:
    """Per-placement replication + resident bytes/device (host-side math,
    no jax): the n-body working set is [N, 4] float32 rows, so a device
    resident under replication k holds k * ceil(N/P) rows.  ``full`` is
    the all-gather baseline (N rows), cyclic/planes are O(sqrt(P))."""
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro.core.placement import supported_placements
    row_bytes = 4 * 4                      # 4 float32 features per body
    out: dict[str, dict] = {}
    for P in Ps:
        rows_per_block = -(-N // P)
        out[str(P)] = {
            plc.name: {
                "replication": plc.replication,
                "bytes_per_device": plc.replication * rows_per_block * row_bytes,
            }
            for plc in supported_placements(P)
        }
    return out


def run(csv_rows, N: int = 1024):
    modes = _modes()
    results: dict[str, dict] = {"N": N, "timings_s": {}, "comm": {},
                                "placements": placement_stats(N)}
    if JSON_PATH.exists():
        # bench_memory.py owns the quantized-vs-f32 "memory" section
        # (read-modify-write); carry it across this full rewrite
        prev = json.loads(JSON_PATH.read_text())
        if "memory" in prev:
            results["memory"] = prev["memory"]
    for P, stats in results["placements"].items():
        csv_rows.append((
            f"placement_bytes_P{P}", "",
            ";".join(f"{name}_k={s['replication']}"
                     f";{name}_B={s['bytes_per_device']}"
                     for name, s in stats.items())))
    for P in [4, 8]:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={P}"
        env["JAX_PLATFORMS"] = "cpu"     # virtual devices, never the chip
        env["PYTHONPATH"] = str(SRC)
        r = subprocess.run([sys.executable, "-c", _CHILD, str(P), str(N),
                            ",".join(modes)],
                           env=env, capture_output=True, text=True,
                           timeout=900)
        assert r.returncode == 0, r.stderr[-2000:]
        res = json.loads(r.stdout.strip().splitlines()[-1])
        results["comm"][str(P)] = res.pop("comm")
        results["timings_s"][str(P)] = res
        best = min(modes, key=lambda m: res[m])
        csv_rows.append((
            f"nbody_engine_P{P}", f"{res[best]*1e6:.0f}",
            f"best={best};" + ";".join(
                f"{m}_us={res[m]*1e6:.0f}"
                for m in modes + ["atom", "seed_scan"]) +
            f";speedup_vs_scan={res['scan']/res[best]:.2f}"
            f";speedup_vs_seed={res['seed_scan']/res[best]:.1f}"))
    results["speedup_vs_scan"] = {
        P: {m: t["scan"] / t[m] for m in modes}
        for P, t in results["timings_s"].items()}
    results["speedup_vs_seed_scan"] = {
        P: {m: t["seed_scan"] / t[m] for m in modes}
        for P, t in results["timings_s"].items()}
    JSON_PATH.write_text(json.dumps(results, indent=2) + "\n")
