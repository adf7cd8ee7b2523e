"""Per-layer metric: the pairwise_topk kernel's share of its roofline (%)."""


def read(run):
    return run.roofline("pairwise_topk")
