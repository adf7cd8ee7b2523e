"""Per-layer metric: the pairwise_threshold kernel's share of its roofline
(%)."""


def read(run):
    return run.roofline("pairwise_threshold")
