"""The readers of the program's own spans and batcher counters
(``knn.host_ms``, ``batch.host_ms``, ``batch.queue_wait_ms``): hand-worked
values on a small synthetic trace and counters, nothing where the program
records nothing, and whole traced runs on the CPU."""

import time
from types import SimpleNamespace

import pytest

import harness
from yardstick.trace import TraceView

WINDOW = ("main", "bench.window", 1_000, 100_000_000)


def knn_run(host, builds=2):
    view = TraceView({"/device:TPU:0": [("%k = op()", 2_000, 10)]}, host)
    return SimpleNamespace(trace_view=view, work={"builds": builds})


def read(metric, run):
    return harness.load_module("layers", metric).read(run)


def test_knn_host_ms_sums_stage_upload_fetch_per_build():
    host = [WINDOW,
            ("main", "bench.knn_graph", 2_000, 50_000_000),
            ("main", "knn.stage", 2_000, 3_000_000),
            ("main", "knn.upload", 5_000_000, 4_000_000),
            ("main", "knn.dispatch", 9_000_000, 500_000),
            ("main", "knn.wait", 9_500_000, 30_000_000),
            ("main", "knn.fetch", 39_500_000, 1_000_000),
            ("main", "knn.stage", 60_000_000, 2_000_000),
            # starts before the window: a build of the warm-up, not read
            ("main", "knn.upload", 0, 900)]
    # (3 + 4 + 1 + 2) ms over 2 builds; dispatch and wait are not host time
    assert read("knn.host_ms", knn_run(host)) == pytest.approx(5.0)


@pytest.mark.parametrize("run", [
    knn_run([WINDOW, ("main", "bench.knn_graph", 2_000, 50_000_000)]),
    knn_run([WINDOW, ("main", "knn.stage", 2_000, 3_000_000)], builds=0),
    SimpleNamespace(trace_view=None, work={"builds": 3}),
], ids=["no-knn-spans", "no-builds", "untraced"])
def test_knn_host_ms_absent(run):
    assert read("knn.host_ms", run) is None


@pytest.mark.parametrize("metric,counters,want", [
    ("batch.host_ms", {"launches": 4, "launch_host_s": 0.012}, 3.0),
    ("batch.queue_wait_ms", {"packed_requests": 50, "queue_wait_s": 0.3},
     6.0),
])
def test_batch_readers_hand_worked(metric, counters, want):
    run = SimpleNamespace(counters=dict(counters, steps=1))
    assert read(metric, run) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["batch.host_ms", "batch.queue_wait_ms"])
@pytest.mark.parametrize("counters", [
    {},
    {"launches": 0, "packed_requests": 0, "launch_host_s": 0.0,
     "queue_wait_s": 0.0},
    # a program without the new counters (before they existed)
    {"launches": 7, "packed_requests": 9},
], ids=["none", "no-launch", "older-program"])
def test_batch_readers_absent(metric, counters):
    assert read(metric, SimpleNamespace(counters=counters)) is None


def traced_tiny(cell):
    """One traced run of ``cell`` on the CPU at a size a test can hold."""
    spec = harness.load_spec()
    c = harness.find_cell(spec, cell)
    cfg = harness.load_config(spec, c["config"])
    traffic = harness.load_traffic(c["traffic"])
    cfg.update(rows=512 if cfg["metric"] == "l2" else 2048,
               use_kernel=False, chips=1)
    if "rate_per_s" in traffic:
        traffic.update(rate_per_s=200, check_requests=32)
    run = harness.Run(cell=cell, config=cfg, traffic=traffic, chips=1,
                      seed=2**33 + 7, seconds=0.3, trace=True,
                      devices=harness.check_devices(1, require_tpu=False))
    return harness.run_cell(run, time.monotonic(), spec)


@pytest.mark.parametrize("cell,metrics", [
    ("knn-sift-1chip", ["knn.host_ms"]),
    ("serve-msmarco-light", ["batch.host_ms", "batch.queue_wait_ms"]),
])
def test_traced_run_reports_the_program_spans_and_counters(cell, metrics):
    out = traced_tiny(cell)
    assert out["correct"], out["checks"]
    for m in metrics:
        assert out["metrics"][m]["value"] > 0, (m, out["metrics"])
