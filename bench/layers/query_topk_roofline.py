"""Per-layer metric: the query_topk kernel's share of its roofline (%)."""


def read(run):
    return run.roofline("query_topk")
