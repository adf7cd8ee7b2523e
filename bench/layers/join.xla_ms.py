"""Per-layer metric: device milliseconds per join, per chip, in which
operations other than the join kernel (``%pairwise_threshold``) run:
chiefly the XLA compaction of the kernel's slot rows into the hit
buffers, from the device trace.  The union of their intervals, since a
while loop's event holds its body's.  Nothing where the trace holds no
kernel event."""

from harness import load_module
from yardstick.trace import union_ns


def read(run):
    view, joins = run.trace_view, run.work.get("joins")
    if view is None or not view.devices or not joins:
        return None
    kernel = load_module("rooflines", "pairwise_threshold").matches
    if view.op_seconds(kernel)[1] == 0:
        return None
    other = view.op_events(lambda name: not kernel(name))
    ns = sum(union_ns(evs) for evs in other.values())
    return ns / 1e6 / len(view.devices) / joins
