"""All-pairs k-NN graph tests (DESIGN.md section 12.3).

The acceptance sweep: ``repro.core.knn`` selfcheck — exact neighbor
index equality against the dense brute-force oracle for every execution
mode (batched / overlap / scan / fused kernel), both metrics, ragged
corpora, and underfull neighbor lists — for **every registered
placement** at P in {4, 5, 7, 8, 12, 13} where the placement is defined
(the same grid as the sparse-join sweep in tests/test_sparse.py).  Runs
in fake-device subprocesses (dry-run isolation rule, see
tests/test_distributed.py).

Host-level pieces (the brute-force oracle, argument validation, the env
mode override, the scatter's non-additive merge monoid) are covered
in-process.
"""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.knn import brute_force_knn
from repro.core.placement import registered_placements
from repro.kernels.ref import IDX_SENTINEL, NEG_INF

SRC = Path(__file__).resolve().parents[1] / "src"

P_SWEEP = (4, 5, 7, 8, 12, 13)

KNN_CASES = [
    (P, name)
    for P in P_SWEEP
    for name, cls in sorted(registered_placements().items())
    if cls.supports(P)
]


def run_sub(code: str, devices: int, env_extra: dict | None = None) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(SRC)
    env.update(env_extra or {})
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


@pytest.mark.parametrize("P,name", KNN_CASES,
                         ids=[f"{n}-P{P}" for P, n in KNN_CASES])
def test_knn_graph_matches_oracle(P, name):
    """Every mode + fused kernel under the placement returns the exact
    neighbor index lists of the dense oracle; the ragged tail and the
    underfull sentinel padding are asserted inside the selfcheck."""
    out = run_sub(
        f"from repro.core.knn import selfcheck_main; "
        f"selfcheck_main({P}, placement={name!r})", P)
    assert "knn selfcheck OK" in out
    assert f"placement={name}(" in out
    assert "batched,overlap,scan,kernel" in out


def test_knn_env_mode_override():
    """REPRO_ALLPAIRS_MODE steers the k-NN engine's auto mode (the
    shared override surface, DESIGN.md section 4): a forced mode still
    matches the oracle, and a conflict with the fused kernel raises."""
    code = """
import numpy as np, jax
from repro.core.knn import brute_force_knn, knn_graph
rng = np.random.default_rng(3)
corpus = rng.normal(size=(40, 8)).astype(np.float32)
mesh = jax.make_mesh((4,), ("q",), axis_types=(jax.sharding.AxisType.Auto,))
res = knn_graph(corpus, mesh, topk=5)        # auto -> forced scan
want = brute_force_knn(corpus, 5)
assert (res.indices == want.indices).all()
try:
    knn_graph(corpus, mesh, topk=5, use_kernel=True)
except ValueError as e:
    assert "conflicts with a fused batch_fn" in str(e), e
else:
    raise AssertionError("kernel + forced non-batched mode must raise")
print("KNN-ENV-OK")
"""
    out = run_sub(code, 4, env_extra={"REPRO_ALLPAIRS_MODE": "scan"})
    assert "KNN-ENV-OK" in out


def test_brute_force_knn_properties():
    rng = np.random.default_rng(0)
    corpus = rng.normal(size=(30, 6)).astype(np.float32)
    for metric in ("dot", "l2"):
        res = brute_force_knn(corpus, 7, metric)
        assert res.indices.shape == (30, 7)
        for r in range(30):
            row = res.indices[r]
            assert r not in row                       # self excluded
            assert len(set(row.tolist())) == 7        # distinct neighbors
            # scores descend under the (-score, index) order
            assert (np.diff(res.scores[r]) <= 1e-6).all()


def test_brute_force_knn_underfull_pads_sentinels():
    rng = np.random.default_rng(1)
    corpus = rng.normal(size=(4, 3)).astype(np.float32)
    res = brute_force_knn(corpus, 6)
    assert (res.indices[:, 3:] == IDX_SENTINEL).all()
    assert (res.scores[:, 3:] == NEG_INF).all()
    assert (res.indices[:, :3] != IDX_SENTINEL).all()


def test_knn_graph_single_device():
    """P = 1 degenerates to the self tile only — the whole graph from
    one block, still oracle-exact (in-process, one real CPU device)."""
    import jax

    from repro.core.knn import knn_graph

    rng = np.random.default_rng(2)
    corpus = rng.normal(size=(17, 5)).astype(np.float32)
    mesh = jax.make_mesh((1,), ("q",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    for metric in ("dot", "l2"):
        want = brute_force_knn(corpus, 4, metric)
        for mode in ("batched", "scan"):
            got = knn_graph(corpus, mesh, topk=4, metric=metric, mode=mode)
            np.testing.assert_array_equal(got.indices, want.indices)
            np.testing.assert_allclose(got.scores, want.scores,
                                       rtol=1e-5, atol=1e-5)


def test_knn_argument_validation():
    import jax.numpy as jnp

    from repro.core.knn import quorum_allpairs_knn

    with pytest.raises(ValueError, match="metric"):
        quorum_allpairs_knn(jnp.zeros((4, 3)), topk=2, axis_name="q",
                            axis_size=2, metric="cosine")
    with pytest.raises(ValueError, match="topk"):
        quorum_allpairs_knn(jnp.zeros((4, 3)), topk=0, axis_name="q",
                            axis_size=2)
    with pytest.raises(ValueError, match="batch_fn"):
        quorum_allpairs_knn(jnp.zeros((4, 3)), topk=2, axis_name="q",
                            axis_size=2, mode="scan", batch_fn=lambda *a: a)


def test_knn_graph_counts_merge_rounds_under_the_tracer():
    """With the Tracer on, ``knn_graph`` runs the kernel with its round
    count and records ``pairwise_topk.merge_rounds`` and ``.merge_steps``
    per device: at P = 4 cyclic every device's active directed items sum
    to P^2 one-tile steps (a cross pair is scored in both orientations),
    each running 1 to topk rounds; the graph is the untraced one."""
    out = run_sub("""
import numpy as np, jax
from repro.core.knn import brute_force_knn, knn_graph
from repro.launch.mesh import make_mesh
from repro.obs import trace
rng = np.random.default_rng(4)
for P in (1, 4):
    mesh = make_mesh((P,), ("q",), devices=jax.devices()[:P])
    corpus = rng.normal(size=(P * 64 - 5, 8)).astype(np.float32)
    run = lambda: knn_graph(corpus, mesh, topk=10, metric="l2",
                            mode="batched", placement="cyclic",
                            use_kernel=True, quant="off")
    plain = run()
    tr = trace.configure(metrics_only=True)
    try:
        got = run()
    finally:
        trace.reset()
    np.testing.assert_array_equal(got.indices, plain.indices)
    np.testing.assert_array_equal(
        got.indices, brute_force_knn(corpus, 10, "l2").indices)
    steps = tr.counters_by_device("pairwise_topk.merge_steps")
    rounds = tr.counters_by_device("pairwise_topk.merge_rounds")
    assert sorted(steps) == sorted(rounds) == list(range(P)), (steps, rounds)
    s, r = sum(steps.values()), sum(rounds.values())
    assert s == P * P and s <= r <= 10 * s, (P, steps, rounds)
    print("COUNTS", P, s, r)
""", 4)
    assert out.count("COUNTS") == 2, out


def test_knn_program_has_no_round_count_with_the_tracer_off():
    """The program the untraced ``knn_graph`` runs has no count output:
    its kernel returns the two lists alone, and the traced program's
    kernel the counts as well."""
    import jax
    import jax.numpy as jnp

    from repro.core import knn
    from repro.core.placement import resolve_placement

    mesh = jax.make_mesh((1,), ("q",),
                         axis_types=(jax.sharding.AxisType.Auto,))

    def kernel_outputs(count_rounds):
        run = knn._knn_fn(mesh, "q", 600, 600, 10, "l2", "batched", True,
                          resolve_placement("cyclic", 1), count_rounds)
        cells = [c.cell_contents for c in run.__closure__]
        fn = next(c for c in cells if hasattr(c, "lower"))
        mask = next(c for c in cells if hasattr(c, "shape"))
        jaxpr = jax.make_jaxpr(fn)(jnp.zeros((600, 8), jnp.float32), mask)
        return len(jaxpr.out_avals), [len(e.outvars)
                                      for e in _eqns(jaxpr.jaxpr)
                                      if e.primitive.name == "pallas_call"]

    assert kernel_outputs(False) == (2, [2])
    assert kernel_outputs(True) == (3, [3])


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    from jax.extend import core as jcore
    for e in jaxpr.eqns:
        yield e
        for p in e.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(sub, jcore.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    yield from _eqns(sub)
