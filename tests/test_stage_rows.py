"""Corpus staging (``launch/mesh.py`` ``stage_rows``, DESIGN.md section
14.1): a host corpus of N rows becomes the [P * block, d] float32 array
sharded over the quorum axis, rows past N zero.

Where P divides N the caller's rows reach ``shard_rows`` as they are,
with no host copy; otherwise each device gets a row-slice view and only
the short blocks are padded, on their devices.  Checked at P = 1
in-process, and at P in {3, 4, 5} with N % P in {0, 1, P - 1} and with
a last device that holds no rows, in one fake-device subprocess: the
staged rows, the shard shapes, the caller's corpus left unchanged, the
``knn.stage`` span's ``padded`` attribute, and ``knn_graph`` and
``similarity_join`` against their dense oracles on the staged corpora.
"""

import json
import os
import subprocess
import sys
import tracemalloc
from pathlib import Path

import numpy as np
import pytest

SRC = Path(__file__).resolve().parents[1] / "src"

# (P, N): N % P == 0, 1 and P - 1, then a last device past N
CASES = [(P, N) for P, empty in ((3, 4), (4, 6), (5, 7))
         for N in (6 * P, 6 * P + 1, 7 * P - 1, empty)]
IDS = [f"P{P}-N{N}" for P, N in CASES]
KNN_RUNS = [(metric, path) for metric in ("dot", "l2")
            for path in ("batched", "kernel")]

STAGED = r"""
import json, sys
import numpy as np, jax
from repro.core.knn import brute_force_knn, knn_graph
from repro.core.sparse import (brute_force_join, similarity_join,
                               threshold_for_selectivity)
from repro.launch import mesh as mesh_mod
from repro.obs import trace

cases = json.loads(sys.argv[1])
devs = jax.devices()
passed = []
real_shard_rows = mesh_mod.shard_rows

def recording_shard_rows(x, mesh, axis_name):
    passed.append(x)
    return real_shard_rows(x, mesh, axis_name)

mesh_mod.shard_rows = recording_shard_rows
out = {}
for P, N in cases:
    mesh = mesh_mod.make_mesh((P,), ("q",), devices=devs[:P])
    block = -(-N // P)
    rng = np.random.default_rng(P * 1000 + N)
    corpus = rng.normal(size=(N, 8)).astype(np.float32)
    before = corpus.copy()
    passed.clear()
    xs = mesh_mod.stage_rows(corpus, mesh, "q", block)
    want = np.zeros((P * block, 8), np.float32)
    want[:N] = corpus
    got = {
        "equal": bool(np.array_equal(np.asarray(xs), want)),
        "dtype": str(xs.dtype),
        "shards": sorted(list(s.data.shape) for s in xs.addressable_shards),
        "devices": len({s.device for s in xs.addressable_shards}),
        "spec": str(xs.sharding.spec),
        "shared": [bool(np.shares_memory(x, corpus)) for x in passed],
    }
    knn = {}
    for metric in ("dot", "l2"):
        oracle = brute_force_knn(corpus, 3, metric)
        for path in ("batched", "kernel"):
            tr = trace.configure()
            try:
                res = knn_graph(corpus, mesh, topk=3, metric=metric,
                                mode="batched", placement="cyclic",
                                use_kernel=path == "kernel", quant="off")
            finally:
                trace.reset()
            knn[metric + "-" + path] = bool(
                np.array_equal(res.indices, oracle.indices)
                and np.allclose(res.scores, oracle.scores, rtol=1e-5,
                                atol=1e-5))
    got["knn"] = knn
    got["padded"] = [e["args"]["padded"] for e in tr.events
                     if e["name"] == "knn.stage"]
    thr = threshold_for_selectivity(corpus, 0.1, "dot")
    wi, wj, _ = brute_force_join(corpus, thr, "dot")
    tr = trace.configure()
    try:
        res = similarity_join(corpus, mesh, threshold=thr, mode="batched",
                              placement="cyclic", quant="off")
    finally:
        trace.reset()
    got["join"] = bool(np.array_equal(res.i, wi)
                       and np.array_equal(res.j, wj))
    got["join_padded"] = [e["args"]["padded"] for e in tr.events
                          if e["name"] == "join.stage"]
    got["unchanged"] = bool(np.array_equal(corpus, before))
    out[f"{P}-{N}"] = got
print("STAGED " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def staged():
    env = dict(os.environ)
    env.update(XLA_FLAGS="--xla_force_host_platform_device_count=5",
               PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu")
    for knob in ("REPRO_TRACE", "REPRO_METRICS", "REPRO_ALLPAIRS_MODE",
                 "REPRO_PLACEMENT", "REPRO_QUANT"):
        env.pop(knob, None)
    r = subprocess.run([sys.executable, "-c", STAGED, json.dumps(CASES)],
                       env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    line = next(x for x in r.stdout.splitlines() if x.startswith("STAGED "))
    return json.loads(line[len("STAGED "):])


@pytest.mark.parametrize("P,N", CASES, ids=IDS)
def test_staged_rows_are_the_zero_padded_corpus(staged, P, N):
    got = staged[f"{P}-{N}"]
    assert got["equal"] and got["dtype"] == "float32"
    assert got["spec"] == "PartitionSpec('q',)"


@pytest.mark.parametrize("P,N", CASES, ids=IDS)
def test_every_device_holds_one_block(staged, P, N):
    got = staged[f"{P}-{N}"]
    assert got["shards"] == [[-(-N // P), 8]] * P
    assert got["devices"] == P


@pytest.mark.parametrize("P,N", CASES, ids=IDS)
def test_pass_through_only_where_P_divides_N(staged, P, N):
    """Where P divides N the array ``shard_rows`` receives is the
    caller's own memory; otherwise ``shard_rows`` is not reached and the
    devices take row-slice views."""
    got = staged[f"{P}-{N}"]
    assert got["shared"] == ([True] if N % P == 0 else [])


@pytest.mark.parametrize("P,N", CASES, ids=IDS)
def test_caller_corpus_unchanged(staged, P, N):
    assert staged[f"{P}-{N}"]["unchanged"]


@pytest.mark.parametrize("metric,path", KNN_RUNS,
                         ids=[f"{m}-{p}" for m, p in KNN_RUNS])
@pytest.mark.parametrize("P,N", CASES, ids=IDS)
def test_knn_graph_on_staged_corpus_matches_oracle(staged, P, N, metric,
                                                   path):
    assert staged[f"{P}-{N}"]["knn"][f"{metric}-{path}"]


@pytest.mark.parametrize("P,N", CASES, ids=IDS)
def test_stage_spans_say_whether_they_padded(staged, P, N):
    got = staged[f"{P}-{N}"]
    assert got["padded"] == [int(N % P != 0)]
    assert got["join_padded"] == [int(N % P != 0)]


@pytest.mark.parametrize("P,N", CASES, ids=IDS)
def test_similarity_join_on_staged_corpus_matches_oracle(staged, P, N):
    assert staged[f"{P}-{N}"]["join"]


# ---------------------------------------------------------------------------
# P = 1 in-process, on the one real CPU device
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh1():
    import jax

    from repro.launch.mesh import make_mesh
    return make_mesh((1,), ("q",), devices=jax.devices()[:1])


@pytest.fixture
def recorded(monkeypatch):
    """Every array ``shard_rows`` receives, in order."""
    from repro.launch import mesh as mesh_mod
    seen = []
    real = mesh_mod.shard_rows

    def record(x, mesh, axis_name):
        seen.append(x)
        return real(x, mesh, axis_name)

    monkeypatch.setattr(mesh_mod, "shard_rows", record)
    return seen


def _corpus(n=300, d=64, seed=0):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


def test_single_device_stage_passes_the_rows_through(mesh1, recorded):
    from repro.launch.mesh import stage_rows
    corpus = _corpus()
    before = corpus.copy()
    xs = stage_rows(corpus, mesh1, "q", len(corpus))
    assert len(recorded) == 1 and recorded[0] is corpus
    np.testing.assert_array_equal(np.asarray(xs), corpus)
    assert [s.data.shape for s in xs.addressable_shards] == [corpus.shape]
    np.testing.assert_array_equal(corpus, before)


def test_single_device_stage_allocates_no_corpus_copy(mesh1):
    """No host allocation of the corpus's size while it is staged."""
    from repro.launch.mesh import stage_rows
    corpus = _corpus(n=2048, d=128)
    stage_rows(corpus, mesh1, "q", len(corpus)).block_until_ready()
    tracemalloc.start()
    try:
        xs = stage_rows(corpus, mesh1, "q", len(corpus))
        xs.block_until_ready()
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < corpus.nbytes // 4, (peak, corpus.nbytes)


@pytest.mark.parametrize("kind", ["float64", "strided"])
def test_single_device_stage_converts_once(mesh1, recorded, kind):
    """A float64 or strided corpus is converted once, to the float32
    C-contiguous rows ``shard_rows`` receives."""
    from repro.launch.mesh import stage_rows
    base = _corpus(n=300, d=128)
    corpus = (base.astype(np.float64) if kind == "float64"
              else base[:, ::2])
    before = corpus.copy()
    xs = stage_rows(corpus, mesh1, "q", len(corpus))
    (x,) = recorded
    assert x.dtype == np.float32 and x.flags.c_contiguous
    assert not np.shares_memory(x, corpus)
    np.testing.assert_array_equal(np.asarray(xs),
                                  np.asarray(corpus, np.float32))
    np.testing.assert_array_equal(corpus, before)


def test_knn_graph_stages_without_a_host_copy(mesh1, recorded):
    from repro.core.knn import brute_force_knn, knn_graph
    corpus = _corpus(n=40, d=8, seed=3)
    got = knn_graph(corpus, mesh1, topk=4, metric="l2", mode="batched",
                    quant="off")
    assert len(recorded) == 1 and np.shares_memory(recorded[0], corpus)
    want = brute_force_knn(corpus, 4, "l2")
    np.testing.assert_array_equal(got.indices, want.indices)


def test_similarity_join_stages_without_a_host_copy(mesh1, recorded):
    from repro.core.sparse import (brute_force_join, similarity_join,
                                   threshold_for_selectivity)
    corpus = _corpus(n=40, d=8, seed=4)
    thr = threshold_for_selectivity(corpus, 0.1, "dot")
    got = similarity_join(corpus, mesh1, threshold=thr, mode="batched",
                          quant="off")
    assert len(recorded) == 1 and np.shares_memory(recorded[0], corpus)
    wi, wj, _ = brute_force_join(corpus, thr, "dot")
    np.testing.assert_array_equal(got.i, wi)
    np.testing.assert_array_equal(got.j, wj)
