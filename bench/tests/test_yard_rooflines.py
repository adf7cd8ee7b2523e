"""Each kernel's operation and byte count at hand-worked shapes."""

import harness


def test_pairwise_topk_one_chip():
    mod = harness.load_module("rooflines", "pairwise_topk")
    # N = 4 rows of width 2: 6 distinct pairs, 2 d = 4 operations each
    flops, nbytes = mod.per_build(N=4, d=2, P=1, k=1, topk=3,
                                  dtype="float32")
    assert flops == 24
    # the block read once (4 x 2 x 4 B) and 4 lists of 3 (8 B an entry)
    assert nbytes == 32 + 96


def test_pairwise_topk_four_chip_quorum_ownership():
    mod = harness.load_module("rooflines", "pairwise_topk")
    # 262,144 rows at P = 4 with 3 of 4 blocks on each chip: every
    # distinct pair is owned by one chip, so the operations do not grow
    # with P, and each chip reads its 3 resident blocks once
    N, d, topk = 262144, 128, 10
    flops, nbytes = mod.per_build(N, d, P=4, k=3, topk=topk,
                                  dtype="float32")
    assert flops == 2 * d * N * (N - 1) / 2
    B = N // 4
    assert nbytes == 4 * 3 * B * (d * 4 + topk * 8)
    # an int8 corpus reads a quarter of the bytes, the same operations
    f8, b8 = mod.per_build(N, d, P=4, k=3, topk=topk, dtype="int8")
    assert f8 == flops and b8 == 4 * 3 * B * (d + topk * 8)


def test_query_topk_counts_real_queries():
    mod = harness.load_module("rooflines", "query_topk")
    # two launches over 1,000 rows of width 4, carrying 3 real queries
    flops, nbytes = mod.totals(N=1000, d=4, topk=10, launches=2,
                               queries=3, dtype="float32")
    assert flops == 2 * 4 * 1000 * 3
    assert nbytes == 2 * 1000 * 4 * 4 + 3 * (4 * 4 + 10 * 8)


# device event names as a TPU v5 lite trace gives them (shortened)
PAIRWISE = ('%body.1 = (f32[1,131072,10]{2,1,0:T(8,128)}, s32[1,131072,10]'
            '{2,1,0:T(8,128)}) custom-call(s32[3]{0:T(128)S(1)} %copy-done.1, '
            'f32[1,131072,128]{2,1,0:T(8,128)S(1)} %bitcast.10), '
            'custom_call_target="tpu_custom_call"')
QUERY = ('%query_topk.1 = (f32[32,16]{1,0:T(8,128)S(1)}, s32[32,16]{1,0:T(8,128)'
         'S(1)}) custom-call(f32[1,2097152,384]{2,1,0:T(8,128)} %stack.1), '
         'custom_call_target="tpu_custom_call"')
OTHER = ('%copy.5 = f32[131072,10]{0,1:T(8,128)} copy(f32[131072,10]'
         '{1,0:T(8,128)} %bitcast.13)')


def test_kernel_event_names():
    pw = harness.load_module("rooflines", "pairwise_topk")
    qt = harness.load_module("rooflines", "query_topk")
    assert pw.matches(PAIRWISE) and not qt.matches(PAIRWISE)
    assert qt.matches(QUERY) and not pw.matches(QUERY)
    assert not pw.matches(OTHER) and not qt.matches(OTHER)
    # the quantized pair kernel reads int8 blocks: not this roofline
    assert not pw.matches(PAIRWISE.replace("f32[1,131072,128]", "s8[1,131072,128]"))
