"""Per-layer metric: requests packed into each launch of the batcher over
the window (its ``packed_requests`` and ``launches`` counters)."""


def read(run):
    launches = run.counters.get("launches", 0)
    if not launches:
        return None
    return run.counters.get("packed_requests", 0) / launches
