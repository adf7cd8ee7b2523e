"""Per-layer metric: host milliseconds per launch of the batcher outside
its wait for the device (packing, dispatch, fetch and resolve: its
``launch_host_s`` and ``launches`` counters over the window)."""


def read(run):
    launches = run.counters.get("launches", 0)
    if not launches or "launch_host_s" not in run.counters:
        return None
    return 1e3 * run.counters["launch_host_s"] / launches
