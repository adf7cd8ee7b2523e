"""Whole runs of the harness at a tiny size on the CPU: a sound run is
correct, the control is not, each fault planted under the timed path
makes ``correct`` false, and a run that finds no TPU prints no result."""

import importlib.util
import time

import numpy as np
import pytest

import harness

SECONDS = 0.2


def tiny(cell, *, control=False, **traffic_over):
    spec = harness.load_spec()
    c = harness.find_cell(spec, cell)
    cfg = harness.load_config(spec, c["config"])
    traffic = harness.load_traffic(c["traffic"])
    # the cell's widths and metric, at a size a test can hold; the jnp
    # path, since the Pallas interpreter is slow on the CPU
    cfg.update(rows=512 if cfg["metric"] == "l2" else 2048,
               use_kernel=False, chips=1)
    traffic.update(traffic_over)
    if "rate_per_s" in traffic:
        traffic.update(rate_per_s=200, check_requests=32)
    run = harness.Run(cell=cell, config=cfg, traffic=traffic, chips=1,
                      seed=2**33 + 5, seconds=SECONDS, trace=False,
                      devices=harness.check_devices(1, require_tpu=False))
    out = harness.run_cell(run, time.monotonic(), spec)
    return out, run


def control_reading(run):
    run.checks, run.control = [], True
    harness.load_module("drivers", run.traffic["driver"]).check(run)
    return dict((n, (v, lim)) for n, v, lim in run.checks)


@pytest.fixture
def fresh_knn():
    from repro.core import knn
    knn._knn_fn.cache_clear()
    yield knn
    knn._knn_fn.cache_clear()


@pytest.mark.parametrize("cell", ["knn-sift-1chip", "serve-msmarco-steady"])
def test_sound_run_is_correct_and_the_control_is_not(cell):
    out, run = tiny(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["run"]["compiles_in_window"] == 0
    assert list(out)[-1] == "checks"
    ctl = control_reading(run)
    value, limit = ctl["score_rel_err"]
    assert value > limit, ctl


def _swap_first_two(idx):
    return idx.at[..., 0].set(idx[..., 1]).at[..., 1].set(idx[..., 0])


def test_knn_answer_altered_where_produced(fresh_knn, monkeypatch):
    from repro.kernels import ref
    orig = ref.pairwise_topk

    def altered(*a, **k):
        vals, idx = orig(*a, **k)
        return vals, _swap_first_two(idx)
    monkeypatch.setattr(ref, "pairwise_topk", altered)
    out, _ = tiny("knn-sift-1chip")
    assert not out["correct"]


def test_knn_half_the_rows_left_out(fresh_knn, monkeypatch):
    from repro.kernels import ref
    orig = ref.pairwise_topk

    def half(*a, **k):
        vals, idx = orig(*a, **k)
        h = vals.shape[1] // 2           # [slots, block, topk]
        return (vals.at[:, h:].set(vals[:, :h]), idx.at[:, h:].set(idx[:, :h]))
    monkeypatch.setattr(ref, "pairwise_topk", half)
    out, _ = tiny("knn-sift-1chip")
    assert not out["correct"]


def test_knn_exchange_between_blocks_left_out(fresh_knn, monkeypatch):
    # what a lost gather or scatter leaves: each row's neighbours come
    # from its own half of the corpus only
    orig = fresh_knn.knn_graph

    def local_only(corpus, mesh, **k):
        h = len(corpus) // 2
        a, b = orig(corpus[:h], mesh, **k), orig(corpus[h:], mesh, **k)
        return fresh_knn.KnnResult(
            indices=np.concatenate([a.indices, b.indices + h]),
            scores=np.concatenate([a.scores, b.scores]), topk=a.topk)
    monkeypatch.setattr(fresh_knn, "knn_graph", local_only)
    out, _ = tiny("knn-sift-1chip")
    assert not out["correct"]


def test_knn_build_returning_a_stale_graph(fresh_knn, monkeypatch):
    orig, first = fresh_knn.knn_graph, []

    def stale(corpus, mesh, **k):
        if not first:
            first.append(orig(corpus, mesh, **k))
        time.sleep(0.05)                 # as long as a build would take
        return first[0]
    monkeypatch.setattr(fresh_knn, "knn_graph", stale)
    out, _ = tiny("knn-sift-1chip", corpora=2)
    assert out["attempted"] >= 2
    assert not out["correct"]


def test_serve_answer_altered_where_produced(monkeypatch):
    from repro.serving.engine import ServingCorpus
    orig = ServingCorpus.query

    def altered(self, *a, **k):
        vals, idx = orig(self, *a, **k)
        return vals, _swap_first_two(idx)
    monkeypatch.setattr(ServingCorpus, "query", altered)
    out, _ = tiny("serve-msmarco-steady")
    assert not out["correct"]


def test_serve_half_the_batch_left_out(monkeypatch):
    from repro.serving.batching import BatchScheduler
    orig = BatchScheduler._pack_queries

    def half(self, reqs):
        q = orig(self, reqs)
        q[(len(reqs) + 1) // 2:] = 0.0
        return q
    monkeypatch.setattr(BatchScheduler, "_pack_queries", half)
    # a burst makes launches carry several requests
    out, _ = tiny("serve-msmarco-steady")
    assert out["run"]["requests"] > 0
    assert not out["correct"]


def test_no_tpu_exits_nonzero_with_no_result(capsys):
    spec = importlib.util.spec_from_file_location(
        "bench_run_main", harness.BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(["--workload", "knn-sift-1chip", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "needs a TPU" in out.err
