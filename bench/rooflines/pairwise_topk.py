"""Operations and bytes of the k-NN pair kernel (``kernels/pairwise_topk.py``)
as the algorithm needs them, for the builds whose kernel events the trace
holds.

Per build of an N-row graph over P chips with k resident blocks of
B = ceil(N / P) rows each:

* operations: 2 d per distinct unordered pair, N (N - 1) / 2 pairs (a
  pair scored once per orientation still counts once);
* bytes: every chip reads its k resident blocks once (P k B d at the
  resident dtype) and writes its k per-block neighbour lists once
  (P k B topk, a float32 score and an int32 index each).
"""

import re

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}
# the kernel's device event is its HLO custom call, named by the program
# (today ``%body.N``): a Mosaic call whose outputs are the per-slot
# [k, block, topk] float32 scores and int32 indices, over float32 blocks
EVENT = re.compile(r"^%\S+ = \(f32\[\d+,\d+,\d+\]\{[^}]*\}, "
                   r"s32\[\d+,\d+,\d+\]\{[^}]*\}\) custom-call\(")
MOSAIC = 'custom_call_target="tpu_custom_call"'


def matches(name: str) -> bool:
    return (MOSAIC in name and bool(EVENT.match(name))
            and "s8[" not in name and "bf16[" not in name)


def per_build(N: int, d: int, P: int, k: int, topk: int, dtype: str):
    """(operations, bytes) of one graph build."""
    B = -(-N // P)
    flops = 2.0 * d * N * (N - 1) / 2
    nbytes = P * k * B * (d * DTYPE_BYTES[dtype] + topk * 8)
    return flops, float(nbytes)


def work(run, n_events: int):
    """(operations, bytes, operand dtype) of the traced kernel events: one
    event per chip per build."""
    cfg = run.config
    P = run.chips
    flops, nbytes = per_build(int(cfg["rows"]), int(cfg["dim"]), P,
                              int(cfg["quorum_blocks"]), int(cfg["topk"]),
                              cfg["dtype"])
    builds = n_events / P
    return flops * builds, nbytes * builds, cfg["dtype"]
