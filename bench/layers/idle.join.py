"""Per-layer metric: share (%) of the traced window in which the chip ran
no operation, on the join cell: the host's part of a join (upload, fetch,
sort) that the chip waits for."""


def read(run):
    return run.idle_share()
