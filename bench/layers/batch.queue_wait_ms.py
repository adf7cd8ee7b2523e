"""Per-layer metric: milliseconds a request waits in the batcher's queue,
from its submission to the start of the launch that carries it (its
``queue_wait_s`` and ``packed_requests`` counters over the window)."""


def read(run):
    packed = run.counters.get("packed_requests", 0)
    if not packed or "queue_wait_s" not in run.counters:
        return None
    return 1e3 * run.counters["queue_wait_s"] / packed
