"""Compile the main-path Pallas kernels for a TPU v5e that is described,
not attached, at the shapes chip_smoke.py dispatches.

Interpret mode on the CPU cannot see what the TPU compiler refuses
(unaligned block shapes, unsupported vector ops, fast-memory overruns).
These tests lower and compile each kernel with ``interpret=False`` against
a ``v5e:2x2`` topology description and check that the compiled program
holds the Mosaic kernel (``tpu_custom_call``).  The topology is described
only inside the module fixture below: one process at a time may load the
TPU library, and only the worker that runs this file does.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as PS
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.pairwise_batch import pairwise_batch_pallas
from repro.kernels.pairwise_batch_q import pairwise_topk_q_pallas
from repro.kernels.pairwise_topk import pairwise_topk_pallas
from repro.kernels.query_score import query_topk_pallas

KNN_BLOCK = 65_536          # chip_smoke k-NN: N = 65,536 at P = 1
SERVE_ROWS = 1 << 20        # chip_smoke serving: N = 1,048,576 at P = 1
D = 128


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    # the TPU library otherwise logs to /tmp/tpu_logs whatever TMPDIR says
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_for_tpu(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_pairwise_topk_compiles(one_chip):
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    # the slot ids are the schedule's host constants (one self tile)
    lo = hi = np.zeros(1, np.int32)
    fn = lambda x, meta: pairwise_topk_pallas(
        x, lo, hi, meta, topk=10, block_rows=KNN_BLOCK, metric="l2")
    _compile_for_tpu(fn, spec((1, KNN_BLOCK, D), jnp.float32),
                     spec((1, 6), jnp.int32))


@pytest.mark.parametrize("count_rounds", [False, True],
                         ids=["count-off", "count-on"])
@pytest.mark.parametrize("P", [1, 4])
def test_gated_pairwise_topk_compiles_at_the_cell_shapes(one_chip, P,
                                                         count_rounds):
    """The gated merge, with the round count off and on, at the k-NN
    cells' per-chip shapes: one 131,072-row block at P = 1, a 3-slot
    quorum of 65,536-row blocks (a self tile, two cross pairs) at P = 4."""
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    k, block = (1, 131_072) if P == 1 else (3, 65_536)
    lo = np.zeros(k, np.int32)
    hi = np.arange(k, dtype=np.int32)
    fn = lambda x, meta: pairwise_topk_pallas(
        x, lo, hi, meta, topk=10, block_rows=block, metric="l2",
        count_rounds=count_rounds)
    _compile_for_tpu(fn, spec((k, block, D), jnp.float32),
                     spec((k, 6), jnp.int32))


def test_query_topk_compiles(one_chip):
    # ServingCorpus runs top-10 requests on the pow2 bucket topk = 16
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    fn = functools.partial(query_topk_pallas, topk=16, metric="dot")
    _compile_for_tpu(fn, spec((1, SERVE_ROWS, D), jnp.float32),
                     spec((32, D), jnp.float32),
                     spec((1, SERVE_ROWS), jnp.float32),
                     spec((1, SERVE_ROWS), jnp.int32))


def test_pairwise_topk_q_compiles(one_chip):
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    # cyclic P = 4 (k = 3): a self tile and two cross pairs
    k = 3
    lo = np.array([0, 0, 0], np.int32)
    hi = np.array([0, 1, 2], np.int32)
    fn = lambda q, sd, sq, meta: pairwise_topk_q_pallas(
        q, sd, sq, lo, hi, meta, topk=10, block_rows=KNN_BLOCK, metric="l2")
    _compile_for_tpu(fn, spec((k, KNN_BLOCK, D), jnp.int8),
                     spec((k, 2), jnp.float32),
                     spec((k, KNN_BLOCK), jnp.float32),
                     spec((len(lo), 6), jnp.int32))


def test_pairwise_batch_compiles(one_chip):
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    k, block, n_pairs = 3, 512, 4
    _compile_for_tpu(pairwise_batch_pallas,
                     spec((k, block, 4), jnp.float32),
                     spec((n_pairs,), jnp.int32), spec((n_pairs,), jnp.int32),
                     spec((n_pairs, 2), jnp.float32))


def test_knn_sweep_compiles_inside_shard_map(topo, monkeypatch):
    """The k-NN program as knn_graph builds it: the kernel inside
    shard_map, where its index maps are traced under varying-axis
    typing."""
    from repro.core.knn import quorum_allpairs_knn
    from repro.core.placement import get_placement
    from repro.launch.mesh import make_mesh
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = make_mesh((1,), ("q",), devices=topo.devices[:1])
    sched = get_placement("cyclic", 1).schedule()
    batch_fn = functools.partial(ops.pairwise_topk, topk=10,
                                 block_rows=KNN_BLOCK, metric="l2")

    def body(xb, mb):
        return quorum_allpairs_knn(xb, topk=10, axis_name="q",
                                   schedule=sched, mask=mb, metric="l2",
                                   mode="batched", n_valid=KNN_BLOCK,
                                   batch_fn=batch_fn)

    rows = NamedSharding(mesh, PS("q"))
    fn = jax.shard_map(body, mesh=mesh, in_specs=(PS("q"), PS("q")),
                       out_specs=(PS("q"), PS("q")))
    _compile_for_tpu(
        fn, jax.ShapeDtypeStruct((KNN_BLOCK, D), jnp.float32, sharding=rows),
        jax.ShapeDtypeStruct((1, sched.n_pairs), jnp.float32, sharding=rows))


@pytest.mark.parametrize("P", [1, 4])
def test_knn_program_names_its_kernel_and_phases(topo, monkeypatch, P):
    """The whole ``knn_graph`` program at P = 1 and at P = 4 cyclic: its
    kernel's custom call reads ``%pairwise_topk.N`` whatever program
    holds it, and the sweep's device ops carry the ``sweep.*`` named
    scopes in their ``op_name``."""
    import re
    from repro.core import knn
    from repro.core.placement import resolve_placement
    from repro.launch.mesh import make_mesh
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = make_mesh((P,), ("q",), devices=topo.devices[:P])
    block = 4096
    run = knn._knn_fn(mesh, "q", P * block, block, 10, "l2", "batched", True,
                      resolve_placement("cyclic", P))
    cells = [c.cell_contents for c in run.__closure__]
    fn = next(c for c in cells if hasattr(c, "lower"))
    mask_table = next(c for c in cells if hasattr(c, "shape"))
    rows = NamedSharding(mesh, PS("q"))
    text = _compile_for_tpu(
        fn, jax.ShapeDtypeStruct((P * block, D), jnp.float32, sharding=rows),
        jax.ShapeDtypeStruct(mask_table.shape, mask_table.dtype,
                             sharding=rows)).as_text()
    calls = re.findall(r"^\s*%(\S+) = .*custom-call\(.*tpu_custom_call", text,
                       re.M)
    assert calls and all(c.startswith("pairwise_topk.") for c in calls), calls
    scopes = {"sweep.pair_compute"} | ({"sweep.gather", "sweep.scatter"}
                                       if P > 1 else set())
    for scope in scopes:
        assert f"/{scope}/" in text, scope


JOIN_ROWS = 262_144         # the join cell: one 262,144 x 512 block at P = 1
JOIN_D = 512


def test_pairwise_threshold_compiles_at_the_cell_shape(one_chip):
    """The tiled join kernel and its compaction for one 262,144 x 512 block
    (a self tile): its VMEM use follows the tile, not the block."""
    from repro.kernels.pairwise_threshold import pairwise_threshold_pallas
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    lo = hi = np.zeros(1, np.int32)
    fn = lambda x, meta: pairwise_threshold_pallas(
        x, lo, hi, meta, threshold=0.9, capacity=1 << 20,
        block_rows=JOIN_ROWS, metric="dot")
    _compile_for_tpu(fn, spec((1, JOIN_ROWS, JOIN_D), jnp.float32),
                     spec((1, 6), jnp.int32))


@pytest.mark.parametrize("P", [1, 4])
def test_join_program_compiles_inside_shard_map(topo, monkeypatch, P):
    """The whole ``similarity_join`` program with the kernel, at P = 1 and
    at P = 4 cyclic on ``v5e:2x2``: the kernel compiles inside shard_map
    under varying-axis typing and its custom call reads
    ``%pairwise_threshold.N``."""
    import re
    from repro.core import sparse
    from repro.core.placement import resolve_placement
    from repro.launch.mesh import make_mesh
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = make_mesh((P,), ("q",), devices=topo.devices[:P])
    block = 8192
    run = sparse._join_fn(mesh, "q", P * block, block, 0.9, "dot",
                          "batched", 1 << 16, True, True,
                          resolve_placement("cyclic", P), 128)
    cells = [c.cell_contents for c in run.__closure__]
    fn = next(c for c in cells if hasattr(c, "lower"))
    mask_table = next(c for c in cells if hasattr(c, "shape"))
    rows = NamedSharding(mesh, PS("q"))
    text = _compile_for_tpu(
        fn, jax.ShapeDtypeStruct((P * block, JOIN_D), jnp.float32,
                                 sharding=rows),
        jax.ShapeDtypeStruct(mask_table.shape, mask_table.dtype,
                             sharding=rows)).as_text()
    calls = re.findall(r"^\s*%(\S+) = .*custom-call\(.*tpu_custom_call", text,
                       re.M)
    assert calls and all(c.startswith("pairwise_threshold.")
                         for c in calls), calls
