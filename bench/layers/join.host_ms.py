"""Per-layer metric: host milliseconds per join in which the join's entry
point stages the padded corpus, uploads it, fetches the hits and sorts
them, the chips idle meanwhile: the program's ``join.stage``,
``join.upload``, ``join.fetch`` and ``join.sort`` spans in the profiler
trace, those starting inside the window, over the joins of the window."""

SPANS = ("join.stage", "join.upload", "join.fetch", "join.sort")


def read(run):
    view, joins = run.trace_view, run.work.get("joins")
    if view is None or not joins:
        return None
    lo, hi = view.window_ns()
    durs = [d for _, name, s, d in view.host if name in SPANS and lo <= s < hi]
    if not durs:
        return None
    return sum(durs) / 1e6 / joins
