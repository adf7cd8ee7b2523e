"""Per-layer metric: share (%) of the traced window in which the devices
ran no operation."""


def read(run):
    return run.idle_share()
