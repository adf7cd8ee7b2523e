"""Operations and bytes of the join kernel (``kernels/pairwise_threshold.py``)
as the algorithm needs them, for the joins whose kernel events the trace
holds.

Per join of N rows over P chips with k resident blocks of B = ceil(N / P)
rows each:

* operations: 2 d per distinct unordered pair, N (N - 1) / 2 pairs;
* bytes: every chip reads its k resident blocks once (P k B d at the
  resident dtype) and the hits are written once (a float32 score and two
  int32 row ids each).
"""

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}
HIT_BYTES = 12
# the kernel's device event is its HLO custom call, which the program
# names after the kernel: ``%pairwise_threshold.N``
NAME = "%pairwise_threshold."
MOSAIC = 'custom_call_target="tpu_custom_call"'


def matches(name: str) -> bool:
    return name.startswith(NAME) and MOSAIC in name


def per_join(N: int, d: int, P: int, k: int, hits: float, dtype: str):
    """(operations, bytes) of one join with ``hits`` passing pairs."""
    B = -(-N // P)
    flops = 2.0 * d * N * (N - 1) / 2
    nbytes = P * k * B * d * DTYPE_BYTES[dtype] + hits * HIT_BYTES
    return flops, float(nbytes)


def work(run, n_events: int):
    """(operations, bytes, operand dtype) of the traced kernel events: one
    event per chip per join."""
    cfg = run.config
    P = run.chips
    flops, nbytes = per_join(int(cfg["rows"]), int(cfg["dim"]), P,
                             int(cfg["quorum_blocks"]),
                             float(run.work.get("hits_per_join", 0.0)),
                             cfg["dtype"])
    joins = n_events / P
    return flops * joins, nbytes * joins, cfg["dtype"]
