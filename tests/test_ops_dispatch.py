"""Direct coverage for the kernels/ops.py dispatch layer.

The engine ``batch_fn`` hooks route every Pallas launch through
``_interpret()``: interpret mode on the CPU backend, compiled everywhere
else.  There is no fallback to the ref.py oracles — a kernel
that cannot lower must raise, so a run that asked for the kernel never
silently measures the jnp path instead.

Shapes here are deliberately distinct from tests/test_kernels.py so every
call traces fresh — the jitted entry points would otherwise replay a
cached trace and bypass the monkeypatched kernels.
"""

import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels import pairwise_batch_q as pbq_mod
from repro.kernels import pairwise_threshold as pt_mod
from repro.kernels import pairwise_topk as ptk_mod
from repro.kernels import query_score as qs_mod

RNG = np.random.default_rng(7)


def _forces_args(k=5, block=9, n_pairs=7):
    quorum = jnp.asarray(np.concatenate(
        [RNG.normal(size=(k, block, 3)),
         RNG.uniform(0.5, 2, (k, block, 1))], -1), jnp.float32)
    lo = RNG.integers(0, k, size=n_pairs).astype(np.int32)
    hi = RNG.integers(0, k, size=n_pairs).astype(np.int32)
    wi = np.ones(n_pairs, np.float32)
    wj = (lo != hi).astype(np.float32)
    return quorum, lo, hi, wi, wj


def test_interpret_dispatch_tracks_backend(monkeypatch):
    """_interpret() is the single source of the interpret/compiled
    decision: True on CPU, False (compiled) on TPU and on any other
    backend."""
    assert jax.default_backend() == "cpu"       # the CI/test environment
    assert ops._interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert ops._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops._interpret() is True


def test_interpret_flag_reaches_pallas_launch(monkeypatch):
    """The hook wrappers pass _interpret()'s verdict into the Pallas
    call (recorded by a shim that then runs the real kernel)."""
    seen = {}
    real = ops.pairwise_batch_pallas

    def shim(*args, **kwargs):
        seen["interpret"] = kwargs.get("interpret")
        return real(*args, **kwargs)

    monkeypatch.setattr(ops, "pairwise_batch_pallas", shim)
    quorum, lo, hi, wi, wj = _forces_args(block=11)
    out = ops.pairwise_batch_forces(quorum, lo, hi, wi, wj)
    assert seen["interpret"] is True            # CPU backend -> interpret
    want = ref.pairwise_batch_forces(quorum, lo, hi, wi, wj)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def _lowering_error(*args, **kwargs):
    raise NotImplementedError("no Mosaic lowering for this kernel")


def _pair_args(block, k=3, n_pairs=4, d=5):
    quorum = jnp.asarray(RNG.normal(size=(k, block, d)), jnp.float32)
    lo = RNG.integers(0, k, n_pairs).astype(np.int32)
    hi = RNG.integers(0, k, n_pairs).astype(np.int32)
    meta = np.stack([np.ones(n_pairs), (lo == hi), np.arange(n_pairs),
                     n_pairs + np.arange(n_pairs), np.full(n_pairs, block),
                     np.full(n_pairs, block)], 1).astype(np.int32)
    return quorum, lo, hi, jnp.asarray(meta)


def _quant_args(block):
    quorum, lo, hi, meta = _pair_args(block)
    q = jnp.round(quorum * 20).astype(jnp.int8)
    sd = jnp.tile(jnp.asarray([[0.05, 0.025]], jnp.float32), (q.shape[0], 1))
    sq = jnp.sum(quorum * quorum, axis=-1)
    return q, sd, sq, lo, hi, meta


def _call_forces():
    ops.pairwise_batch_forces(*_forces_args(block=19))


def _call_query():
    k, block, d = 3, 14, 6
    stack = jnp.asarray(RNG.normal(size=(k, block, d)), jnp.float32)
    queries = jnp.asarray(RNG.normal(size=(5, d)), jnp.float32)
    ops.query_topk(stack, queries, jnp.ones((k, block), jnp.float32),
                   jnp.arange(k * block, dtype=jnp.int32).reshape(k, block),
                   topk=3)


def _call_threshold():
    ops.pairwise_threshold(*_pair_args(21), threshold=0.4, capacity=100,
                           block_rows=21)


def _call_topk():
    ops.pairwise_topk(*_pair_args(22), topk=3, block_rows=22)


def _call_threshold_q():
    q, sd, sq, lo, hi, meta = _quant_args(23)
    l1 = jnp.sum(jnp.abs(q.astype(jnp.float32)), axis=-1) * 0.05
    ops.pairwise_threshold_q(q, sd, l1, sq, lo, hi, meta, threshold=0.4,
                             capacity=64, block_rows=23)


def _call_topk_q():
    ops.pairwise_topk_q(*_quant_args(25), topk=3, block_rows=25)


@pytest.mark.parametrize("target,attr,call", [
    (ops, "pairwise_batch_pallas", _call_forces),
    (qs_mod, "query_topk_pallas", _call_query),
    (pt_mod, "pairwise_threshold_pallas", _call_threshold),
    (ptk_mod, "pairwise_topk_pallas", _call_topk),
    (pbq_mod, "pairwise_threshold_q_pallas", _call_threshold_q),
    (pbq_mod, "pairwise_topk_q_pallas", _call_topk_q),
], ids=["pairwise_batch_forces", "query_topk", "pairwise_threshold",
        "pairwise_topk", "pairwise_threshold_q", "pairwise_topk_q"])
def test_lowering_error_propagates(monkeypatch, target, attr, call):
    """A kernel that cannot lower raises out of its engine hook: no hook
    swaps in the jnp oracle (which would hide the device path)."""
    monkeypatch.setattr(target, attr, _lowering_error)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with pytest.raises(NotImplementedError, match="no Mosaic lowering"):
            call()


def test_join_kernels_refuse_to_compile_for_tpu(monkeypatch):
    """The quantized join kernel has no TPU lowering (in-tile cumsum
    compaction): on a compiling backend its hook raises with that reason
    before any launch.  The f32 join kernel lowers: its hook no longer
    raises there and launches the kernel compiled (recorded by a shim
    that stands in for the launch, which this CPU cannot run compiled)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seen = {}

    def launch(quorum, lo, hi, meta, *, capacity, interpret, **kwargs):
        seen["interpret"] = interpret
        z = jnp.zeros((capacity,), jnp.int32)
        return (z.astype(jnp.float32), z, z, jnp.int32(0),
                jnp.zeros((2,), jnp.int32))

    monkeypatch.setattr(pt_mod, "pairwise_threshold_pallas", launch)
    ops.pairwise_threshold(*_pair_args(26), threshold=0.4, capacity=100,
                           block_rows=26)
    assert seen == {"interpret": False}
    with pytest.raises(NotImplementedError, match="ROADMAP S4"):
        _call_threshold_q()


def test_real_kernel_bugs_still_propagate(monkeypatch):
    """Only ImportError/NotImplementedError trigger the ref fallback;
    anything else (shape bugs, assertion failures) must surface."""
    def broken(*a, **k):
        raise ValueError("genuine kernel bug")

    monkeypatch.setattr(ops, "pairwise_batch_pallas", broken)
    quorum, lo, hi, wi, wj = _forces_args(block=15)
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # no fallback warning either
        with pytest.raises(ValueError, match="genuine kernel bug"):
            ops.pairwise_batch_forces(quorum, lo, hi, wi, wj)
