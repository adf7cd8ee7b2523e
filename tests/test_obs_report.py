"""Report CLI tests (obs/report.py, DESIGN.md section 14.4): validation
catches malformed traces, summaries aggregate spans/counters correctly,
and the CLI gates (exit 0 valid / 1 invalid) as the CI trace-smoke job
relies on.  Host-only — the report module is stdlib-only by design.
"""

import json

import pytest

from repro.obs import report as report_mod
from repro.obs import trace as trace_mod


def _sample_trace():
    tr = trace_mod.Tracer()
    with tr.span("sweep.gather", P=8):
        pass
    with tr.span("sweep.gather"):
        pass
    tr.record("faults.round", 0.002, round=0)
    tr.count("comm.ppermute.gather_bytes", 864)
    tr.count("serving.queries", 5, device=0)
    tr.count("serving.queries", 7, device=1)
    return tr.chrome_trace()


def test_validate_accepts_tracer_output():
    assert report_mod.validate_chrome_trace(_sample_trace()) == []


@pytest.mark.parametrize("mutate,needle", [
    (lambda o: o.pop("traceEvents"), "traceEvents"),
    (lambda o: o["traceEvents"][0].pop("name"), "missing 'name'"),
    (lambda o: o["traceEvents"][0].pop("dur"), "ph=X needs dur"),
    (lambda o: o["traceEvents"][0].update(dur=-1.0), "ph=X needs dur"),
    (lambda o: o["repro"].update(version="x"), "repro.version"),
    (lambda o: o["repro"].update(counters=[1]), "repro.counters"),
    (lambda o: o["repro"].update(clock="ticks"), "repro.clock"),
])
def test_validate_flags_malformed(mutate, needle):
    obj = _sample_trace()
    mutate(obj)
    errors = report_mod.validate_chrome_trace(obj)
    assert errors and any(needle in e for e in errors), errors


@pytest.mark.parametrize("version,clock", [(2, "epoch-us"),
                                           (1, "relative-us"), (1, None)])
def test_validate_accepts_both_clocks(version, clock):
    """Format 2 stamps epoch microseconds; a version-1 file (relative
    microseconds, with or without the clock field) still validates."""
    obj = _sample_trace()
    obj["repro"]["version"] = version
    if clock is None:
        del obj["repro"]["clock"]
    else:
        obj["repro"]["clock"] = clock
    assert report_mod.validate_chrome_trace(obj) == []


def test_validate_counter_sample_needs_value():
    obj = _sample_trace()
    c = next(e for e in obj["traceEvents"] if e["ph"] == "C")
    del c["args"]["value"]
    errors = report_mod.validate_chrome_trace(obj)
    assert any("ph=C needs args.value" in e for e in errors), errors


def test_validate_non_dict_top_level():
    assert report_mod.validate_chrome_trace([1, 2]) == [
        "top level is not an object"]


def test_span_summary_aggregates_per_name():
    s = report_mod.span_summary(_sample_trace())
    assert s["sweep.gather"]["count"] == 2
    assert s["faults.round"]["count"] == 1
    assert abs(s["faults.round"]["total_ms"] - 2.0) < 0.5
    for row in s.values():
        assert row["max_ms"] >= row["mean_ms"] >= 0
    # sorted by total descending
    totals = [row["total_ms"] for row in s.values()]
    assert totals == sorted(totals, reverse=True)


def test_counter_summary_prefers_repro_section():
    c = report_mod.counter_summary(_sample_trace())
    assert c["comm.ppermute.gather_bytes"] == {"-1": 864.0, "total": 864.0}
    assert c["serving.queries"] == {"0": 5.0, "1": 7.0, "total": 12.0}


def test_counter_summary_falls_back_to_samples():
    obj = _sample_trace()
    del obj["repro"]["counters"]
    c = report_mod.counter_summary(obj)
    assert c["serving.queries"]["total"] == 12.0


def test_render_tables():
    out = report_mod.render(_sample_trace())
    assert "sweep.gather" in out and "faults.round" in out
    assert "comm.ppermute.gather_bytes" in out
    assert "(program-wide)" in out            # device -1 counters
    assert "0:5 1:7" in out                   # per-device counters


def test_load_trace_raises_on_invalid(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"traceEvents": [{"ph": "X"}]}))
    with pytest.raises(ValueError, match="invalid Chrome trace"):
        report_mod.load_trace(p)


def test_cli_valid_and_invalid(tmp_path, capsys):
    good = tmp_path / "good.json"
    good.write_text(json.dumps(_sample_trace()))
    assert report_mod.main([str(good)]) == 0
    out = capsys.readouterr().out
    assert "sweep.gather" in out and "trace:" in out

    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert report_mod.main([str(bad)]) == 1
    assert "INVALID" in capsys.readouterr().out

    missing = tmp_path / "nope.json"
    assert report_mod.main([str(missing)]) == 1
