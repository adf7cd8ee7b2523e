"""Reduction of a small recorded device trace to busy time, idle share,
kernel time and collective time."""

from pathlib import Path

import pytest

import harness
from yardstick import trace

DATA = Path(__file__).resolve().parent / "data"


def test_union_of_overlapping_intervals():
    evs = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 31, 1)]
    assert trace.union_ns(evs) == 20
    assert trace.union_ns(evs, lo=8, hi=32) == 7 + 2
    assert trace.busy_intervals(evs) == [(0, 15), (30, 35)]


# two chips, 10 us window: chip 0 runs a kernel and a collective that
# overlaps it; chip 1 the same kernel later and one all-reduce
TWO_CHIPS = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%body.1 = (f32[3,8,10]{2,1,0}, s32[3,8,10]{2,1,0}) custom-call(f32[3,8,128]{2,1,0} %x), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2 name: "%collective-permute-done.1 = f32[8,129]{1,0} collective-permute-done(f32[8,129]{1,0} %p)" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 9000000 duration_ps: 500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%body.1 = (f32[3,8,10]{2,1,0}, s32[3,8,10]{2,1,0}) custom-call(f32[3,8,128]{2,1,0} %x), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2 name: "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %y), to_apply=%add" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "main/1" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 4500000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "np.asarray(jax.Array)" } }
}
"""


def test_two_chip_reduction_hand_worked():
    v = trace.load_text(TWO_CHIPS)
    assert list(v.devices) == ["/device:TPU:0", "/device:TPU:1"]
    assert v.window_ns() == (1000, 11000)
    # chip 0 busy [0, 5) us, chip 1 [5, 9.5) us: mean 4.75 us
    assert v.busy_s() == pytest.approx(4.75e-6)
    kernel = harness.load_module("rooflines", "pairwise_topk")
    secs, n = v.op_seconds(kernel.matches)
    assert n == 2 and secs == pytest.approx(8e-6)
    csecs, cn = v.collective_seconds()
    assert cn == 2 and csecs == pytest.approx(2.5e-6)
    assert v.top_ops(2) == [["body.1 custom-call", pytest.approx(4e-6)],
                            ["collective-permute-done.1 collective-permute-done",
                             pytest.approx(1e-6)]]
    # chip 0 idles [5, 10) us while the host converts an array at 5.0 us
    # is covered by np.asarray (4.5..5.5) only at its start; the gap's
    # middle (7.5 us) has no span but the window
    assert v.idle_gaps(1) == [["no host span", pytest.approx(5e-6)]]


def test_recorded_one_chip_knn_trace():
    # recorded on a TPU v5 lite: 12 graph builds of knn-sift-1chip in a
    # 10 s window, trimmed to the XLA Ops line and a few host spans
    v = trace.load_text((DATA / "knn_1chip_trace.textproto").read_text())
    assert list(v.devices) == ["/device:TPU:0"]
    lo, hi = v.window_ns()
    assert (hi - lo) / 1e9 == pytest.approx(10.649, abs=1e-3)
    kernel = harness.load_module("rooflines", "pairwise_topk")
    secs, n = v.op_seconds(kernel.matches)
    assert n == 12                               # one launch a build
    assert secs == pytest.approx(9.552076932, rel=1e-9)
    assert v.busy_s() == pytest.approx(9.558477846, rel=1e-9)
    assert secs <= v.busy_s() <= (hi - lo) / 1e9
    assert v.collective_seconds() == (0.0, 0)    # one chip: none
    assert v.top_ops(1)[0][0] == "body.1 custom-call"
    gaps = v.idle_gaps(10)
    # the gaps between builds are host work inside knn_graph
    assert len(gaps) == 10 and all(g[0].startswith("bench.knn_graph")
                                   for g in gaps)
    assert 0.08 < gaps[0][1] < 0.1


def test_recorded_trace_matches_a_plain_reading_of_the_file():
    # the same sums by a plain parse of the text, without ProfileData
    import re
    text = (DATA / "knn_1chip_trace.textproto").read_text()
    start = text.index('name: "/device:TPU:0"')
    dev = text[start:text.index("planes {", start)]
    names = dict(re.findall(r'key: (\d+) value \{ id: \d+ name: "((?:[^"\\]|\\.)*)"', dev))
    evs = [(names[m], int(o) / 1e3, int(d) / 1e3) for m, o, d in re.findall(
        r"metadata_id: (\d+) offset_ps: (\d+) duration_ps: (\d+)", dev)]
    kernel = sum(d for n, _, d in evs if n.startswith("%body.1 = "))
    assert kernel / 1e9 == pytest.approx(9.552076932, rel=1e-6)
    spans, end, busy = sorted((s, s + d) for _, s, d in evs), -1.0, 0.0
    for s, e in spans:
        busy += max(0.0, e - max(s, end))
        end = max(end, e)
    assert busy / 1e9 == pytest.approx(9.558477846, rel=1e-6)
