"""Tracer core invariants (obs/trace.py, DESIGN.md section 14.1):
span nesting/ordering, counters, Chrome-trace export, the REPRO_TRACE /
REPRO_METRICS activation matrix, and the disabled path's zero-cost
contract (the falsy NOOP singleton adds no net allocations).
"""

import json
import os
import subprocess
import sys
import tracemalloc
from pathlib import Path

import numpy as np
import pytest

from repro.obs import report as report_mod
from repro.obs import trace as trace_mod

SRC = Path(__file__).resolve().parents[1] / "src"


@pytest.fixture(autouse=True)
def _fresh_tracer_state(monkeypatch):
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    monkeypatch.delenv("REPRO_METRICS", raising=False)
    trace_mod.reset()
    yield
    trace_mod.reset()


def test_nbytes_of():
    assert trace_mod.nbytes_of(np.zeros((3, 5), np.float32)) == 60
    assert trace_mod.nbytes_of(np.zeros((4,), np.int64)) == 32


def test_span_nesting_and_ordering():
    """Children close (and are appended) before their parents; depth and
    parent attributes record the nesting; child intervals are contained
    in the parent's."""
    tr = trace_mod.Tracer()
    with tr.span("outer", P=8):
        with tr.span("inner.a"):
            pass
        with tr.span("inner.b", round=1):
            pass
    names = [e["name"] for e in tr.events]
    assert names == ["inner.a", "inner.b", "outer"]
    by_name = {e["name"]: e for e in tr.events}
    assert by_name["outer"]["args"]["depth"] == 0
    assert by_name["outer"]["args"]["P"] == 8
    for child in ("inner.a", "inner.b"):
        ev = by_name[child]
        assert ev["args"]["depth"] == 1
        assert ev["args"]["parent"] == "outer"
        # containment: the child's interval sits inside the parent's
        parent = by_name["outer"]
        assert ev["ts"] >= parent["ts"]
        assert ev["ts"] + ev["dur"] <= parent["ts"] + parent["dur"] + 1e-6
    assert by_name["inner.b"]["args"]["round"] == 1
    # ts is monotone in append order for sequential siblings
    assert by_name["inner.a"]["ts"] <= by_name["inner.b"]["ts"]
    assert tr._stack == []


def test_span_stacks_are_per_thread():
    """A span opened on another thread (the batcher's loop) does not nest
    under the span open on this one."""
    import threading
    tr = trace_mod.Tracer()

    def loop():
        with tr.span("batch.idle"):
            pass

    with tr.span("knn.stage"):
        th = threading.Thread(target=loop)
        th.start()
        th.join(timeout=30)
    assert not th.is_alive()
    by_name = {e["name"]: e["args"] for e in tr.events}
    assert by_name["batch.idle"] == {"depth": 0}
    assert by_name["knn.stage"] == {"depth": 0}


def test_span_exception_still_recorded():
    tr = trace_mod.Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    assert [e["name"] for e in tr.events] == ["boom"]
    assert tr._stack == []


def test_record_and_counters():
    tr = trace_mod.Tracer()
    tr.record("phase", 0.25, device=3, what="restore")
    tr.count("bytes", 100, device=0)
    tr.count("bytes", 50, device=1)
    tr.count("bytes", 7, device=0)
    tr.count("events")
    (ev,) = tr.events
    assert ev["name"] == "phase" and ev["pid"] == 3
    assert abs(ev["dur"] - 0.25e6) < 1e-3
    assert tr.counter_total("bytes") == 157
    assert tr.counters_by_device("bytes") == {0: 107, 1: 50}
    assert tr.counter_names() == ["bytes", "events"]


def test_chrome_trace_export_roundtrip(tmp_path):
    """export() writes Chrome-trace JSON the report module validates and
    summarizes; the repro section carries exact counter totals."""
    tr = trace_mod.Tracer(path=tmp_path / "t.json")
    tr.meta["P"] = 8
    with tr.span("sweep.gather"):
        pass
    tr.count("comm.bytes", 4096, device=2)
    out = tr.export()
    assert out == tmp_path / "t.json"
    obj = report_mod.load_trace(out)   # raises on an invalid trace
    assert report_mod.validate_chrome_trace(obj) == []
    phs = {e["ph"] for e in obj["traceEvents"]}
    assert phs == {"X", "C"}
    assert obj["repro"]["version"] == trace_mod.TRACE_FORMAT_VERSION
    assert obj["repro"]["counters"]["comm.bytes"]["2"] == 4096
    assert obj["repro"]["meta"] == {"P": 8}
    summary = report_mod.span_summary(obj)
    assert summary["sweep.gather"]["count"] == 1


def test_export_without_path_raises():
    with pytest.raises(ValueError, match="no export path"):
        trace_mod.Tracer().export()


def test_metrics_only_drops_spans():
    tr = trace_mod.Tracer(metrics_only=True)
    with tr.span("x"):
        tr.count("c", 2)
    tr.record("y", 0.1)
    assert tr.events == []
    assert tr.counter_total("c") == 2


def test_env_activation_matrix(monkeypatch):
    """Unset/0 -> falsy NOOP; 1 -> tracer at the default path; any other
    value -> tracer at that path; REPRO_METRICS=1 -> counters only;
    invalid REPRO_METRICS raises (the registry contract)."""
    assert trace_mod.get_tracer() is trace_mod.NOOP
    assert not trace_mod.get_tracer()

    monkeypatch.setenv("REPRO_TRACE", "0")
    trace_mod.reset()
    assert trace_mod.get_tracer() is trace_mod.NOOP

    monkeypatch.setenv("REPRO_TRACE", "1")
    trace_mod.reset()
    tr = trace_mod.get_tracer()
    assert tr and str(tr.path) == trace_mod.DEFAULT_TRACE_PATH
    assert trace_mod.get_tracer() is tr        # cached on the env values

    monkeypatch.setenv("REPRO_TRACE", "/tmp/custom_trace.json")
    tr2 = trace_mod.get_tracer()               # key change rebuilds
    assert tr2 is not tr and str(tr2.path) == "/tmp/custom_trace.json"

    monkeypatch.delenv("REPRO_TRACE")
    monkeypatch.setenv("REPRO_METRICS", "1")
    trace_mod.reset()
    tr3 = trace_mod.get_tracer()
    assert tr3 and tr3.metrics_only and tr3.path is None

    monkeypatch.setenv("REPRO_METRICS", "-1")
    trace_mod.reset()
    with pytest.raises(ValueError, match="REPRO_METRICS must be >= 0"):
        trace_mod.get_tracer()


def test_configure_overrides_env_and_reset_restores(monkeypatch):
    monkeypatch.setenv("REPRO_TRACE", "1")
    trace_mod.reset()
    forced = trace_mod.configure(metrics_only=True)
    assert trace_mod.get_tracer() is forced
    trace_mod.reset()
    got = trace_mod.get_tracer()
    assert got is not forced and isinstance(got, trace_mod.Tracer)


def test_env_tracer_flushes_at_exit(tmp_path):
    """The REPRO_TRACE=<path> tracer exports at process exit (what the
    CI trace-smoke job relies on)."""
    out = tmp_path / "exit_trace.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = str(SRC)
    env["REPRO_TRACE"] = str(out)
    code = ("from repro.obs import trace as t\n"
            "tr = t.get_tracer()\n"
            "assert tr\n"
            "tr.count('smoke', 3)\n"
            "with tr.span('s'):\n"
            "    pass\n")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    obj = report_mod.load_trace(out)
    assert obj["repro"]["counters"]["smoke"]["-1"] == 3


def test_export_stamps_epoch_microseconds():
    """Spans are stamped on the profiler's host clock (epoch ns as
    ``time.time_ns``), in microseconds, so an export lines up with an
    ``.xplane.pb`` of the same run (DESIGN.md section 14.1)."""
    import time
    tr = trace_mod.Tracer()
    before = time.time_ns() / 1e3
    with tr.span("knn.stage", rows=8, P=1):
        pass
    tr.record("faults.round", 0.001)
    after = time.time_ns() / 1e3
    span, rec = tr.events
    assert before <= span["ts"] <= span["ts"] + span["dur"] <= after
    assert before - 1e3 <= rec["ts"] <= after
    assert tr.chrome_trace()["repro"]["clock"] == trace_mod.TRACE_CLOCK \
        == "epoch-us"


def test_disabled_path_zero_net_allocations():
    """The no-op overhead contract (DESIGN.md section 14.1): with tracing
    and the profiler off, the instrumented call-site patterns — a span
    site with attributes, a counter site behind the falsy guard — leave
    exactly zero net bytes behind in the tracing code and on the call
    sites' own lines, over every 2000-round block."""
    import inspect

    def call_sites():
        tr = trace_mod.get_tracer()
        with tr.span("knn.stage", rows=131072, P=1):
            pass
        if tr:  # pragma: no cover - tracing is off in this test
            tr.count("sweep.pair_tiles", 15)
        return tr

    assert call_sites() is trace_mod.NOOP     # the shared singleton
    assert trace_mod.NOOP.span("knn.stage", rows=1) is trace_mod._NOOP_SPAN
    lines, first = inspect.getsourcelines(call_sites)
    only = [tracemalloc.Filter(True, trace_mod.__file__)] + [
        tracemalloc.Filter(True, __file__, lineno=n)
        for n in range(first, first + len(lines))]

    def traced_bytes():
        snap = tracemalloc.take_snapshot().filter_traces(only)
        return sum(t.size for t in snap.traces)

    tracemalloc.start()
    try:
        for _ in range(2000):        # one-time warm-up allocations
            call_sites()
        grown = []
        for _ in range(4):
            before = traced_bytes()
            for _ in range(2000):
                call_sites()
            grown.append(traced_bytes() - before)
        assert grown == [0, 0, 0, 0], (
            f"disabled tracing allocates per round: 2000-round blocks "
            f"grew {grown} bytes")
    finally:
        tracemalloc.stop()
