"""Operations and bytes of the serving kernel (``kernels/query_score.py``)
as the algorithm needs them, for the launches whose kernel events the
trace holds.

Per launch over N valid corpus rows of width d, with q real (unpadded)
queries:

* operations: 2 d per real query and valid row;
* bytes: the resident rows the launch streams once (N d at the resident
  dtype), the real queries read (q d float32) and their top-k lists
  written (q topk, a float32 score and an int32 index each).

The real queries come from the batcher's ``packed_requests`` counter over
the same window.
"""

import re

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}
# the kernel's device event is its HLO custom call (today
# ``%query_topk.N``): a Mosaic call whose outputs are the [Q, topk]
# float32 scores and int32 indices
EVENT = re.compile(r"^%\S+ = \(f32\[\d+,\d+\]\{[^}]*\}, "
                   r"s32\[\d+,\d+\]\{[^}]*\}\) custom-call\(")
MOSAIC = 'custom_call_target="tpu_custom_call"'


def matches(name: str) -> bool:
    return MOSAIC in name and bool(EVENT.match(name))


def totals(N: int, d: int, topk: int, launches: float, queries: float,
           dtype: str):
    """(operations, bytes) of ``launches`` launches carrying ``queries``
    real queries in all."""
    flops = 2.0 * d * N * queries
    nbytes = launches * N * d * DTYPE_BYTES[dtype] \
        + queries * (d * 4 + topk * 8)
    return flops, float(nbytes)


def work(run, n_events: int):
    cfg = run.config
    launches = n_events / run.chips
    queries = float(run.counters.get("packed_requests", 0))
    flops, nbytes = totals(int(cfg["rows"]), int(cfg["dim"]),
                           int(run.traffic["topk"]), launches, queries,
                           cfg["dtype"])
    return flops, nbytes, cfg["dtype"]
