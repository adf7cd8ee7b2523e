"""Per-layer metric: host milliseconds per graph build in which the k-NN
entry point stages the padded corpus, uploads it and fetches the graph,
the chips idle meanwhile: the program's ``knn.stage``, ``knn.upload`` and
``knn.fetch`` spans in the profiler trace, those starting inside the
window, over the builds of the window."""

SPANS = ("knn.stage", "knn.upload", "knn.fetch")


def read(run):
    view, builds = run.trace_view, run.work.get("builds")
    if view is None or not builds:
        return None
    lo, hi = view.window_ns()
    durs = [d for _, name, s, d in view.host if name in SPANS and lo <= s < hi]
    if not durs:
        return None
    return sum(durs) / 1e6 / builds
