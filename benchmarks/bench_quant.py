"""Quantized-scoring benchmark (CPU, subprocess-isolated fake devices):
the int8/bf16 band-emit + exact-rescoring join against the pure f32
join, plus the quantized-only answer quality the rescoring pass repairs
(DESIGN.md section 17.6).

Three axes, one JSON (BENCH_quant.json at the repo root, uploaded by CI
next to the other BENCH_*.json files):

  * ``bytes_per_device`` — resident working set of the quantized stack
    vs f32 under the cyclic placement (host-side math; the int8 line is
    the >= 2x reduction headline).
  * ``recall_quant_only`` — what the *unrescored* quantized scores get
    wrong: join membership recall/precision at the threshold and k-NN
    top-k overlap, straight off the device lists.  The rescored path
    returns exactly the f32 answer (asserted here), so this is the
    quality gap the certified rescoring closes.
  * ``timings_s`` — steady-state medians of the cached device programs
    (``*_device``) and of the full host entry points including the
    rescoring pass (``*_e2e``), f32 vs int8 vs bf16.
  * ``modeled`` — the sweep-time model (bench_attention_comm's
    byte-counting idiom) at comm-bound geometries: per-device flops /
    compute-rate + gather bytes / link-bandwidth, f32 vs quantized
    payloads, with NO int8 compute advantage assumed.

Measured-caveat, baked into the numbers like bench_engine's: the
single-host fake-device harness moves gather payloads by memcpy and XLA
CPU runs int8 dots at exactly the f32 rate (no VNNI path), so the
*measured* wall-clock axis can only show parity — the quantized path's
win is a bytes-moved/bytes-resident effect.  The measured rows pin that
parity (and the exactness of the rescored answer); the ``modeled``
section is where the 4x-smaller payload turns into sweep-time speedup,
using the repo's own schedule geometry (DESIGN.md section 17.7).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"
JSON_PATH = ROOT / "BENCH_quant.json"

_CHILD = r"""
import json, statistics, sys, time
import numpy as np, jax, jax.numpy as jnp
from repro.core.placement import get_placement
from repro.core.quant import (_qjoin_fn, _qknn_fn, _shard_quant,
                              quant_knn_graph, quant_similarity_join)
from repro.core.sparse import (_join_fn, brute_force_join, similarity_join,
                               threshold_for_selectivity)
from repro.core.knn import brute_force_knn

P = int(sys.argv[1]); N = int(sys.argv[2]); d = int(sys.argv[3])
topk = 8
rng = np.random.default_rng(0)
corpus = rng.normal(size=(N, d)).astype(np.float32)
block = -(-N // P)
corpus[:2 * block] *= 0.05            # vary per-block quant scales
thr = threshold_for_selectivity(corpus, 0.02, "dot")
wi, wj, wv = brute_force_join(corpus, thr, "dot")
true = set(zip(wi.tolist(), wj.tolist()))

mesh = jax.make_mesh((P,), ("q",), axis_types=(jax.sharding.AxisType.Auto,))
plc = get_placement("cyclic", P)

def bench(fn, reps=11):
    jax.block_until_ready(fn())                 # compile
    jax.block_until_ready(fn())                 # warm caches
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)   # median: fake devices oversubscribe cores

def bench_host(fn, reps=7):
    fn(); fn()                                  # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)

out = {"threshold": float(thr), "n_hits": len(wi)}

# correctness anchors: every path is bit-exact vs the f32 oracle
res_f32 = similarity_join(corpus, mesh, threshold=thr, mode="batched",
                          placement=plc, quant="off")
assert res_f32.n_pairs == len(wi)
cap = res_f32.capacity
stats = {}
for qm in ("int8", "bf16"):
    r = quant_similarity_join(corpus, mesh, threshold=thr, quant=qm,
                              mode="batched", placement=plc, capacity=cap,
                              stats=stats if qm == "int8" else None)
    assert np.array_equal(r.i, wi) and np.array_equal(r.j, wj), qm
    kq = quant_knn_graph(corpus, mesh, topk=topk, quant=qm,
                         mode="batched", placement=plc)
    ref_knn = brute_force_knn(corpus, topk, "dot")
    assert np.array_equal(kq.indices, ref_knn.indices), qm
out["band"] = stats

# quantized-only quality: membership by s_q >= thr off the device band,
# k-NN overlap off the raw quantized top-k lists (no rescoring)
qc, x, n2 = _shard_quant(corpus, P, "int8")
run_q = _qjoin_fn(mesh, "q", N, block, float(thr), "dot", "batched",
                  cap, False, plc, "int8")
vals, gi, gj, counts = (np.asarray(a) for a in run_q(qc.device_arrays()))
vals = vals.reshape(P, -1); gi = gi.reshape(P, -1); gj = gj.reshape(P, -1)
counts = counts.reshape(-1)
qpairs = set()
for dev in range(P):
    n = min(int(counts[dev]), cap)
    for a, b, v in zip(gi[dev, :n], gj[dev, :n], vals[dev, :n]):
        if v >= thr:
            qpairs.add((int(a), int(b)))
join_recall = len(qpairs & true) / max(1, len(true))
join_precision = len(qpairs & true) / max(1, len(qpairs))
run_k = _qknn_fn(mesh, "q", N, block, topk, "dot", "batched", False,
                 plc, "int8")
kv, ki = (np.asarray(a) for a in run_k(qc.device_arrays()))
ref_knn = brute_force_knn(corpus, topk, "dot")
knn_recall = float(np.mean([
    len(set(ki[r].tolist()) & set(ref_knn.indices[r].tolist())) / topk
    for r in range(N)]))
out["recall_quant_only"] = {"join_recall": join_recall,
                            "join_precision": join_precision,
                            "knn_recall_at_k": knn_recall}

# timings: cached device programs + full e2e entry points
xs = jnp.asarray(x)
run_f = _join_fn(mesh, "q", N, block, float(thr), "dot", "batched", cap,
                 True, False, plc)
out["f32_device"] = bench(lambda: run_f(xs))
out["f32_e2e"] = bench_host(lambda: similarity_join(
    corpus, mesh, threshold=thr, mode="batched", placement=plc,
    capacity=cap, quant="off"))
for qm in ("int8", "bf16"):
    qcm, _, _ = _shard_quant(corpus, P, qm)
    leaves = qcm.device_arrays()
    run_qm = _qjoin_fn(mesh, "q", N, block, float(thr), "dot", "batched",
                       cap, False, plc, qm)
    out[f"{qm}_device"] = bench(lambda: run_qm(leaves))
    out[f"{qm}_e2e"] = bench_host(lambda: quant_similarity_join(
        corpus, mesh, threshold=thr, quant=qm, mode="batched",
        placement=plc, capacity=cap))
print(json.dumps(out))
"""


def _bytes_per_device(N: int, d: int, P: int) -> dict:
    """The resident-bytes section (host-side math, no jax): f32 vs
    int8/bf16 under the cyclic placement, with reduction ratios
    (DESIGN.md section 17.1) — same formula as
    bench_memory.quant_resident_bytes."""
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro.core.scheduler import build_schedule

    from .bench_memory import quant_resident_bytes
    k = build_schedule(P).k
    f32 = quant_resident_bytes(N, d, P, k, "off")
    out = {"k": k, "f32": f32}
    for mode in ("int8", "bf16"):
        b = quant_resident_bytes(N, d, P, k, mode)
        out[mode] = b
        out[f"{mode}_reduction_x"] = round(f32 / b, 4)
    return out


def modeled_sweep_speedup(P: int, block: int, d: int,
                          compute_flops: float = 50e12,
                          link_bw: float = 25e9) -> dict:
    """Sweep-time model at one geometry: per-device tile flops over an
    accelerator compute rate plus per-device gather bytes over a
    cross-device link — the regime the quantized payload targets
    (DESIGN.md section 17.7).  Deliberately conservative: int8/bf16 are
    charged the SAME compute rate as f32 (no VNNI/matrix-unit credit),
    so any modeled speedup is purely the comm term shrinking."""
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro.core.scheduler import build_schedule

    s = build_schedule(P)
    t_compute = 2.0 * s.n_pairs * block * block * d / compute_flops
    payloads = {
        "f32": block * d * 4,
        "int8": block * d * 1 + 8 + 8 * block,
        "bf16": block * d * 2 + 8 + 8 * block,
    }
    times = {m: t_compute + (s.k - 1) * b / link_bw
             for m, b in payloads.items()}
    out = {"k": s.k, "n_pairs": s.n_pairs, "block": block, "d": d,
           "compute_flops": compute_flops, "link_bw": link_bw,
           "t_compute_s": t_compute}
    for m in ("f32", "int8", "bf16"):
        out[f"{m}_gather_bytes"] = (s.k - 1) * payloads[m]
        out[f"{m}_sweep_s"] = times[m]
    out["int8_speedup_x"] = times["f32"] / times["int8"]
    out["bf16_speedup_x"] = times["f32"] / times["bf16"]
    return out


def run(csv_rows, N: int = 2048, d: int = 128):
    results: dict = {"N": N, "d": d, "timings_s": {}, "bytes_per_device": {},
                     "speedup": {}, "modeled": {}}
    results["measured_caveat"] = (
        "single-host fake devices: gather is memcpy and XLA CPU runs int8 "
        "dots at the f32 rate, so measured wall-clock shows parity; the "
        "payload win is carried by bytes_per_device and the modeled "
        "comm-bound sweep times")
    for P in (64, 256):
        # comm-bound geometry: small blocks, wide rows — compute is
        # block^2*d per tile, gather is block*d per hop
        results["modeled"][str(P)] = modeled_sweep_speedup(P, 256, 256)
    m = results["modeled"]["256"]
    csv_rows.append((
        "quant_modeled_P256", f"{m['int8_sweep_s'] * 1e6:.0f}",
        f"int8_sweep_us;f32_sweep_us={m['f32_sweep_s'] * 1e6:.0f}"
        f";int8_speedup={m['int8_speedup_x']:.2f}"
        f";bf16_speedup={m['bf16_speedup_x']:.2f}"
        f";k={m['k']};gather_MB_f32={m['f32_gather_bytes'] / 1e6:.1f}"))
    for P in [8]:
        results["bytes_per_device"][str(P)] = _bytes_per_device(N, d, P)
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
                   if k.endswith(("_device", "_e2e"))}
        results["timings_s"][str(P)] = timings
        results["recall_quant_only"] = res["recall_quant_only"]
        results["band"] = res["band"]
        results["threshold"] = res["threshold"]
        results["n_hits"] = res["n_hits"]
        results["speedup"][str(P)] = {
            "int8_device_vs_f32": res["f32_device"] / res["int8_device"],
            "bf16_device_vs_f32": res["f32_device"] / res["bf16_device"],
            "int8_e2e_vs_f32": res["f32_e2e"] / res["int8_e2e"],
            "bf16_e2e_vs_f32": res["f32_e2e"] / res["bf16_e2e"]}
        bpd = results["bytes_per_device"][str(P)]
        rq = res["recall_quant_only"]
        csv_rows.append((
            f"quant_join_P{P}", f"{res['int8_e2e'] * 1e6:.0f}",
            f"int8_e2e_us;f32_e2e_us={res['f32_e2e'] * 1e6:.0f}"
            f";e2e_speedup={results['speedup'][str(P)]['int8_e2e_vs_f32']:.2f}"
            f";device_speedup="
            f"{results['speedup'][str(P)]['int8_device_vs_f32']:.2f}"
            f";bytes_reduction={bpd['int8_reduction_x']:.2f}"
            f";quant_only_recall={rq['join_recall']:.4f}"
            f";knn_recall={rq['knn_recall_at_k']:.4f}"))
    JSON_PATH.write_text(json.dumps(results, indent=2) + "\n")
