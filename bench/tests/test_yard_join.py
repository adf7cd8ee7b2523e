"""Whole runs of the join cell at a tiny size on the CPU: a sound run is
correct, the control is not, and each fault planted under the timed path
makes ``correct`` false."""

import time
from types import SimpleNamespace

import numpy as np
import pytest

import harness
from yardstick.trace import TraceView

CELL = "join-semdedup-1chip"
SECONDS = 0.2


def tiny(**traffic_over):
    spec = harness.load_spec()
    c = harness.find_cell(spec, CELL)
    cfg = harness.load_config(spec, c["config"])
    traffic = harness.load_traffic(c["traffic"])
    # the cell's metric, threshold, data and limits at a size a test can
    # hold, on the jnp path (the Pallas interpreter is slow on the CPU).
    # 128 wide, not 512: the CPU's float32 dot sums 512 products less
    # exactly than the chip's MXU, and over three seeds its
    # score_rel_err read 1.09e-6 to 1.28e-6 against the control's 1.32e-6
    # to 1.55e-6, the closest of each within 2% of the 1.3e-6 limit.  At
    # 128 wide the program reads at most 7.3e-7 and the control at least
    # 2.7e-6.
    cfg.update(rows=2048, dim=128, use_kernel=False, chips=1)
    traffic.update(traffic_over)
    run = harness.Run(cell=CELL, config=cfg, traffic=traffic, chips=1,
                      seed=2**33 + 5, seconds=SECONDS, trace=False,
                      devices=harness.check_devices(1, require_tpu=False))
    out = harness.run_cell(run, time.monotonic(), spec)
    return out, run


@pytest.fixture
def sparse():
    from repro.core import sparse
    sparse._join_fn.cache_clear()
    yield sparse
    sparse._join_fn.cache_clear()


def wrap(sparse, monkeypatch, change):
    """Put ``change(result, call)`` between the timed path and its
    result."""
    orig, calls = sparse.similarity_join, []

    def changed(corpus, mesh, **k):
        calls.append(None)
        return change(orig(corpus, mesh, **k), len(calls))
    monkeypatch.setattr(sparse, "similarity_join", changed)


def test_sound_run_is_correct_and_the_control_is_not():
    out, run = tiny()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["run"]["compiles_in_window"] == 0
    assert out["run"]["hits_per_join"] > 0
    assert out["run"]["near_threshold_share"] >= 0.1
    assert list(out)[-1] == "checks"
    run.checks, run.control = [], True
    harness.load_module("drivers", run.traffic["driver"]).check(run)
    ctl = {n: (v, lim) for n, v, lim in run.checks}
    value, limit = ctl["score_rel_err"]
    assert value > limit, ctl


def test_one_hit_dropped(sparse, monkeypatch):
    def drop(res, n):
        return sparse.JoinResult(
            i=res.i[1:], j=res.j[1:], scores=res.scores[1:],
            counts=res.counts, capacity=res.capacity,
            escalations=res.escalations, overflow=res.overflow)
    wrap(sparse, monkeypatch, drop)
    out, _ = tiny()
    assert out["checks"]["missing_pairs"]["value"] > 0
    assert not out["correct"]


def test_a_pair_below_the_threshold_added(sparse, monkeypatch):
    def add(res, n):
        # (0, N - 1) is two i.i.d. rows or a far pair: its cosine is ~0
        i = np.append(res.i, 0)
        j = np.append(res.j, 2047)
        s = np.append(res.scores, np.float32(0.95))
        order = np.lexsort((j, i))
        return sparse.JoinResult(
            i=i[order], j=j[order], scores=s[order], counts=res.counts,
            capacity=res.capacity, escalations=res.escalations,
            overflow=res.overflow)
    wrap(sparse, monkeypatch, add)
    out, _ = tiny()
    assert out["checks"]["extra_pairs"]["value"] > 0
    assert not out["correct"]


def test_a_join_returning_an_earlier_corpus_answer(sparse, monkeypatch):
    first = []

    def stale(res, n):
        if not first:
            first.append(res)
        return first[0]
    wrap(sparse, monkeypatch, stale)
    out, _ = tiny(corpora=2)
    assert out["attempted"] >= 2
    assert out["checks"]["missing_pairs"]["value"] > 0
    assert not out["correct"]


def test_one_score_altered_where_produced(sparse, monkeypatch):
    from repro.kernels import ref
    orig = ref.pairwise_threshold

    def altered(*a, **k):
        vals, i, j, count = orig(*a, **k)
        return vals.at[0].multiply(1 + 1e-5), i, j, count
    monkeypatch.setattr(ref, "pairwise_threshold", altered)
    out, _ = tiny()
    assert out["checks"]["missing_pairs"]["value"] == 0
    assert out["checks"]["score_rel_err"]["value"] > 5e-6
    assert not out["correct"]


def test_pairwise_threshold_roofline_counts():
    mod = harness.load_module("rooflines", "pairwise_threshold")
    # N = 4 rows of width 2: 6 distinct pairs, 2 d = 4 operations each;
    # the block read once (4 x 2 x 4 B) and 3 hits written (12 B each)
    flops, nbytes = mod.per_join(N=4, d=2, P=1, k=1, hits=3,
                                 dtype="float32")
    assert flops == 24 and nbytes == 32 + 36
    # at P = 4 with 3 of 4 blocks a chip the operations do not grow
    flops4, nbytes4 = mod.per_join(N=8, d=2, P=4, k=3, hits=0,
                                   dtype="float32")
    assert flops4 == 2 * 2 * 8 * 7 / 2 and nbytes4 == 4 * 3 * 2 * 2 * 4


# device event names as a TPU v5 lite trace gives them (shortened)
JOIN = ('%pairwise_threshold.1 = (f32[66048,128]{1,0:T(8,128)}, s32[66048,128]'
        '{1,0:T(8,128)}, s32[66048,128]{1,0:T(8,128)}, s32[520,128]{1,0:T(8,128)'
        '}) custom-call(s32[3]{0} %constant.43, f32[1,262144,512]{2,1,0} %x.1), '
        'custom_call_target="tpu_custom_call"')
TOPK = ('%pairwise_topk.1 = (f32[1,131072,10]{2,1,0:T(8,128)}, s32[1,131072,10]'
        '{2,1,0:T(8,128)}) custom-call(f32[1,131072,128]{2,1,0:T(8,128)S(1)} '
        '%bitcast.10), custom_call_target="tpu_custom_call"')
GATHER = ('%gather.3 = f32[1398016]{0} gather(f32[66048,128]{1,0} '
          '%pairwise_threshold_out, s32[1398016,2]{1,0} %concatenate.2)')


def test_join_kernel_event_name():
    join = harness.load_module("rooflines", "pairwise_threshold")
    topk = harness.load_module("rooflines", "pairwise_topk")
    assert join.matches(JOIN) and not topk.matches(JOIN)
    assert topk.matches(TOPK) and not join.matches(TOPK)
    assert not join.matches(GATHER)


WINDOW = ("main", "bench.window", 1_000, 100_000_000)


def read(metric, run):
    return harness.load_module("layers", metric).read(run)


def join_run(host, device, joins=2):
    view = TraceView({"/device:TPU:0": device}, host)
    return SimpleNamespace(trace_view=view, work={"joins": joins})


def test_join_readers_hand_worked():
    host = [WINDOW,
            ("main", "join.stage", 2_000, 1_000_000),
            ("main", "join.upload", 3_000_000, 4_000_000),
            ("main", "join.dispatch", 7_000_000, 500_000),
            ("main", "join.wait", 7_500_000, 30_000_000),
            ("main", "join.fetch", 40_000_000, 2_000_000),
            ("main", "join.sort", 42_000_000, 3_000_000),
            # starts before the window: the warm join's, not read
            ("main", "join.sort", 0, 900)]
    device = [(JOIN, 8_000_000, 20_000_000),
              (GATHER, 28_000_000, 3_000_000),
              ("%while.12 = s32[] while(s32[] %c)", 31_000_000, 5_000_000),
              # the loop's body, inside the loop's own event: not twice
              ("%fusion.17 = s32[] fusion(s32[] %c)", 32_000_000, 3_000_000),
              # before the window: not read
              (GATHER, 500, 400)]
    run = join_run(host, device)
    # (1 + 4 + 2 + 3) ms of host spans over 2 joins
    assert read("join.host_ms", run) == pytest.approx(5.0)
    # the gather and the while loop, 8 ms, over 2 joins; not the kernel
    assert read("join.xla_ms", run) == pytest.approx(4.0)


@pytest.mark.parametrize("metric", ["join.host_ms", "join.xla_ms"])
@pytest.mark.parametrize("run", [
    join_run([WINDOW], [(GATHER, 2_000, 10)]),
    join_run([WINDOW, ("main", "join.sort", 2_000, 10)], [(JOIN, 2_000, 10)],
             joins=0),
    SimpleNamespace(trace_view=None, work={"joins": 3}),
], ids=["no-join-spans-or-kernel", "no-joins", "untraced"])
def test_join_readers_absent(metric, run):
    assert read(metric, run) is None


def test_idle_join_is_the_idle_share():
    run = SimpleNamespace(idle_share=lambda: 7.5)
    assert read("idle.join", run) == 7.5


@pytest.mark.parametrize("scores,want", [
    ([0.9, 0.905, 0.909, 0.95, 1.0], 0.6), ([0.95, 0.99], 0.0), ([], 0.0)])
def test_near_threshold_share(scores, want):
    drv = harness.load_module("drivers", "join_builds")
    assert drv.near_threshold_share(scores, 0.9) == pytest.approx(want)
