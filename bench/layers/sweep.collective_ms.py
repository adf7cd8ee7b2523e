"""Per-layer metric: device milliseconds of collective operations
(collective-permute, all-gather, all-reduce, ...) per graph build, per
chip, from the device trace."""


def read(run):
    if run.trace_view is None or not run.work.get("builds"):
        return None
    secs, n = run.trace_view.collective_seconds()
    if n == 0:
        return None
    return 1e3 * secs / len(run.trace_view.devices) / run.work["builds"]
