"""Program spans in a JAX profile (obs/trace.py, DESIGN.md section 14.1):
one ``knn_graph`` build at P = 1 and at P = 4 and one ``BatchScheduler``
step, run under ``jax.profiler.start_trace`` on fake CPU devices, leave
every ``knn.*`` and ``batch.*`` span in the ``.xplane.pb`` with its
attributes, nested as the program opens them; with ``REPRO_TRACE`` on as
well, the Chrome-trace export stamps the same spans on the profiler's
clock.  The batcher's ``queue_wait_s`` / ``launch_host_s`` counters are
checked under an injected clock.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

SRC = Path(__file__).resolve().parents[1] / "src"
sys.path.insert(0, str(SRC))

from repro.serving.batching import BatchScheduler              # noqa: E402

KNN_SPANS = ("knn.stage", "knn.upload", "knn.dispatch", "knn.wait",
             "knn.fetch")
LAUNCH_SPANS = ("batch.pack", "batch.dispatch", "batch.wait", "batch.fetch",
                "batch.resolve")

PROFILED = r"""
import glob, json, sys, time
import numpy as np, jax
from jax.profiler import ProfileData
from repro.core.knn import knn_graph
from repro.launch.mesh import make_mesh
from repro.obs import trace as trace_mod
from repro.serving import ServingCorpus
from repro.serving.batching import BatchScheduler

prof_dir = sys.argv[1]
rng = np.random.default_rng(5)
devs = jax.devices()
meshes = {P: make_mesh((P,), ("q",), devices=devs[:P]) for P in (1, 4)}
corpus = rng.normal(size=(60, 16)).astype(np.float32)
sc = ServingCorpus.build(rng.normal(size=(64, 16)).astype(np.float32),
                         meshes[4], block=16)
sched = BatchScheduler(sc, max_batch=8)

def build(P):
    return knn_graph(corpus, meshes[P], topk=3, metric="l2",
                     mode="batched", placement="cyclic", quant="off")

def submit(n):
    return [sched.submit(rng.normal(size=(16,)), kind="topk", topk=3)
            for _ in range(n)]

for P in (1, 4):                     # compile outside the profile
    build(P)
submit(3)
sched.step()
submit(3)

jax.profiler.start_trace(prof_dir)
sched.step()
for P in (1, 4):
    build(P)
sched.start()                        # the loop idles, then serves one
time.sleep(0.2)
submit(1)[0].result(timeout=120)
sched.stop()
jax.profiler.stop_trace()
trace_mod.get_tracer().export()

pd = ProfileData.from_file(glob.glob(prof_dir + "/**/*.xplane.pb",
                                     recursive=True)[0])
t0 = None
spans = []
for plane in pd.planes:
    if plane.name == "Task Environment":
        t0 = dict(plane.stats)["profile_start_time"]
    if not plane.name.startswith("/host:"):
        continue
    for line in plane.lines:
        for e in line.events:
            if e.name.split(".")[0] in ("knn", "batch"):
                spans.append([line.name, e.name, e.start_ns, e.duration_ns,
                              {k: v for k, v in e.stats}])
print("SPANS " + json.dumps({"profile_start_ns": t0, "spans": spans}))
"""


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("profiled")
    env = dict(os.environ)
    env.update(XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu",
               REPRO_TRACE=str(tmp / "repro_trace.json"))
    env.pop("REPRO_METRICS", None)
    r = subprocess.run([sys.executable, "-c", PROFILED, str(tmp / "prof")],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    line = next(x for x in r.stdout.splitlines() if x.startswith("SPANS "))
    got = json.loads(line[len("SPANS "):])
    export = json.loads((tmp / "repro_trace.json").read_text())
    return got, export


def _by_name(spans, name):
    return sorted((s for s in spans if s[1] == name), key=lambda s: s[2])


def _inside(child, parent):
    return (parent[2] <= child[2]
            and child[2] + child[3] <= parent[2] + parent[3])


def test_knn_spans_in_the_profile_in_order(profiled):
    spans = profiled[0]["spans"]
    per = {n: _by_name(spans, n) for n in KNN_SPANS}
    assert all(len(v) == 2 for v in per.values()), \
        {n: len(v) for n, v in per.items()}
    for build in range(2):            # P = 1, then P = 4
        seq = [per[n][build] for n in KNN_SPANS]
        assert {s[4]["P"] for s in seq} == {(1, 4)[build]}
        assert {s[4]["rows"] for s in seq} == {60}
        for a, b in zip(seq, seq[1:]):     # siblings, one after another
            assert a[2] + a[3] <= b[2], (a, b)


def test_knn_stage_says_in_the_profile_that_it_did_not_pad(profiled):
    """P divides the 60 rows at P = 1 and 4: each ``knn.stage`` reaches
    the profile with ``padded`` 0, as a deployment's builds must."""
    stages = _by_name(profiled[0]["spans"], "knn.stage")
    assert [(s[4]["P"], s[4]["padded"]) for s in stages] == [(1, 0), (4, 0)]


def test_batch_spans_in_the_profile_nested(profiled):
    spans = profiled[0]["spans"]
    steps = _by_name(spans, "batch.step")
    assert len(steps) == 2, steps    # the synchronous step and the loop's
    assert steps[0][4] == {"n": 3, "groups": 1, "queue_depth": 0}
    for step in steps:
        seq = [next(s for s in _by_name(spans, n) if _inside(s, step))
               for n in LAUNCH_SPANS]
        for a, b in zip(seq, seq[1:]):
            assert a[2] + a[3] <= b[2], (a, b)
    idle = _by_name(spans, "batch.idle")
    assert idle and not any(_inside(s, i) or _inside(i, s)
                            for i in idle for s in steps)
    # the loop idled before its step
    assert idle[0][2] + idle[0][3] <= steps[1][2]


def test_export_and_profile_share_a_clock(profiled):
    got, export = profiled
    assert export["repro"]["clock"] == "epoch-us"
    (x,) = [s for s in _by_name(got["spans"], "knn.upload")
            if s[4]["P"] == 4]
    # the export holds the warm-up build before it too
    e = max((e for e in export["traceEvents"]
             if e["name"] == "knn.upload" and e["args"]["P"] == 4),
            key=lambda e: e["ts"])
    xplane_ns = got["profile_start_ns"] + x[2]
    assert abs(e["ts"] * 1e3 - xplane_ns) < 1e6, (e["ts"], xplane_ns)
    assert abs(e["dur"] * 1e3 - x[3]) < 1e6


class _StubCorpus:
    """A corpus whose launches return at once (top-k of zeros)."""
    P, block, d = 1, 16, 4

    def query(self, q, *, topk, mode, metric, use_kernel):
        return (np.zeros((q.shape[0], topk), np.float32),
                np.zeros((q.shape[0], topk), np.int32))


def test_queue_wait_and_launch_host_under_an_injected_clock():
    """Every clock read advances one second: two requests submitted at
    t = 1 and 2, the step reads t = 3, its one launch starts at 4, waits
    from 5 to 6, has fetched at 7 and resolved at 8."""
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    sched = BatchScheduler(_StubCorpus(), max_batch=8, clock=clock)
    a = sched.submit(np.zeros(4), kind="topk", topk=2)
    b = sched.submit(np.zeros(4), kind="topk", topk=2)
    assert sched.step() == 2
    assert sched.counters["launches"] == 1
    assert sched.counters["queue_wait_s"] == (4 - 1) + (4 - 2)
    # (wait start 5 - launch start 4) + (end 8 - fetched 6)
    assert sched.counters["launch_host_s"] == 1 + 2
    assert [a.result(0).latency_s, b.result(0).latency_s] == [6.0, 5.0]
    # a second launch adds to both
    sched.submit(np.zeros(4), kind="topk", topk=2)       # t = 9
    sched.step()                                         # starts at 11
    assert sched.counters["queue_wait_s"] == 5 + 2
    assert sched.counters["launch_host_s"] == 3 + 3
