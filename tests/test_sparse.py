"""Thresholded sparse similarity-join tests (DESIGN.md section 11).

The acceptance sweep: ``repro.core.sparse`` selfcheck — bit-exact
pair-set equality (index-level) against the dense brute-force oracle for
every execution mode (batched / overlap / scan / fused kernel), both
metrics, prefilter on and off, plus the overflow/escalation contract and
the ppermute ring gather — for **every registered placement** at
P in {4, 5, 7, 8, 12, 13} where the placement is defined (the
test_placement_engine.py sweep, extended to the sparse engine).  Runs in
fake-device subprocesses (dry-run isolation rule, see
tests/test_distributed.py).  The serving-side thresholded range query is
swept by the serving selfcheck in test_serving.py / test_placement_engine
sweeps, which now include ``check_threshold``.

Host-level helpers (threshold selection, the brute-force oracle, the
capacity heuristic + env override) are covered in-process.
"""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import sparse
from repro.core.placement import registered_placements
from repro.core.sparse import (brute_force_join, default_capacity,
                               threshold_for_selectivity)

SRC = Path(__file__).resolve().parents[1] / "src"

P_SWEEP = (4, 5, 7, 8, 12, 13)

SPARSE_CASES = [
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


@pytest.mark.parametrize("P,name", SPARSE_CASES,
                         ids=[f"{n}-P{P}" for P, n in SPARSE_CASES])
def test_sparse_join_matches_oracle(P, name):
    """Every mode + fused kernel under the placement returns the exact
    passing-pair index set of the dense oracle; overflow flags, capacity
    escalation, and the ring gather are asserted inside the selfcheck."""
    out = run_sub(
        f"from repro.core.sparse import selfcheck_main; "
        f"selfcheck_main({P}, placement={name!r})", P)
    assert "sparse selfcheck OK" in out
    assert f"placement={name}(" in out
    assert "batched,overlap,scan,kernel" in out


def test_sparse_env_mode_override():
    """REPRO_ALLPAIRS_MODE steers the sparse engine's auto mode (shared
    override surface, DESIGN.md section 4): a forced mode still matches
    the oracle, and a conflict with the fused kernel raises."""
    code = """
import numpy as np, jax
from repro.core.sparse import (brute_force_join, similarity_join,
                               threshold_for_selectivity)
rng = np.random.default_rng(3)
corpus = rng.normal(size=(40, 8)).astype(np.float32)
thr = threshold_for_selectivity(corpus, 0.1)
mesh = jax.make_mesh((4,), ("q",), axis_types=(jax.sharding.AxisType.Auto,))
res = similarity_join(corpus, mesh, threshold=thr)   # auto -> forced scan
wi, wj, _ = brute_force_join(corpus, thr)
assert (res.i == wi).all() and (res.j == wj).all()
try:
    similarity_join(corpus, mesh, threshold=thr, use_kernel=True)
except ValueError as e:
    assert "conflicts with a fused batch_fn" in str(e), e
else:
    raise AssertionError("kernel + forced non-batched mode must raise")
print("SPARSE-ENV-OK")
"""
    out = run_sub(code, 4, env_extra={"REPRO_ALLPAIRS_MODE": "scan"})
    assert "SPARSE-ENV-OK" in out


def test_serving_threshold_placement():
    """The serving range query under a plane placement (the
    check_threshold step of the serving selfcheck at projective P=7)."""
    out = run_sub(
        "from repro.serving.selfcheck import main; "
        "main(7, placement='projective')", 7)
    assert "serving selfcheck OK" in out
    assert "placement=projective(" in out


def test_brute_force_join_properties():
    rng = np.random.default_rng(0)
    corpus = rng.normal(size=(30, 6)).astype(np.float32)
    for metric in ("dot", "l2"):
        thr = threshold_for_selectivity(corpus, 0.2, metric)
        i, j, s = brute_force_join(corpus, thr, metric)
        assert (i < j).all()
        assert (s >= thr).all()
        # sorted by (i, j), no duplicates
        order = np.lexsort((j, i))
        assert (order == np.arange(len(i))).all()
        assert len({(a, b) for a, b in zip(i.tolist(), j.tolist())}) == len(i)
        # selectivity lands near the target
        total = corpus.shape[0] * (corpus.shape[0] - 1) // 2
        assert 0.1 <= len(i) / total <= 0.3, len(i) / total


def test_threshold_for_selectivity_gap():
    """The picked threshold sits strictly inside a score gap, so no score
    lies within min_gap/2 of it — float-rounding-proof membership."""
    rng = np.random.default_rng(1)
    corpus = rng.normal(size=(24, 5)).astype(np.float32)
    thr = threshold_for_selectivity(corpus, 0.15, "dot", min_gap=1e-3)
    _, _, s = brute_force_join(corpus, -np.inf, "dot")
    assert (np.abs(s - thr) > 5e-4).all()


def test_default_capacity_env(monkeypatch):
    monkeypatch.delenv("REPRO_SPARSE_CAPACITY", raising=False)
    assert default_capacity(1) == 128                  # floor
    assert default_capacity(1 << 20) == (1 << 20) // 8  # 1/8, already x128
    assert default_capacity(1000) == 128               # ceil(125) -> 128
    # a start 1/8 of the candidates cannot allocate: it is bounded by a
    # small share of the device's memory (a TPU v5e's on the CPU)
    most = default_capacity(1 << 36)
    assert most % 128 == 0
    assert 12 * most <= (16 << 30) * sparse.START_MEMORY_SHARE
    monkeypatch.setenv("REPRO_SPARSE_CAPACITY", "512")
    assert default_capacity(1 << 30) == 512            # override wins
    monkeypatch.setenv("REPRO_SPARSE_CAPACITY", "0")
    with pytest.raises(ValueError, match="REPRO_SPARSE_CAPACITY"):
        default_capacity(1)


JOIN_KERNEL_CASES = [(1, "cyclic"), (4, "cyclic"), (7, "cyclic"),
                     (7, "projective")]


@pytest.mark.parametrize("P,name", JOIN_KERNEL_CASES,
                         ids=[f"{n}-P{P}" for P, n in JOIN_KERNEL_CASES])
def test_similarity_join_kernel_matches_brute_force(P, name):
    """``similarity_join`` through the fused kernel equals the dense
    oracle for both metrics, with a ragged corpus tail, at one block (a
    self tile only) and under cyclic and plane quorums."""
    code = f"""
import numpy as np
from repro.core.sparse import (brute_force_join, similarity_join,
                               threshold_for_selectivity)
from repro.launch.mesh import make_mesh
import jax
mesh = make_mesh(({P},), ("q",), devices=jax.devices()[:{P}])
rng = np.random.default_rng({P})
corpus = rng.normal(size=({P} * 24 - 5, 16)).astype(np.float32)
for metric in ("dot", "l2"):
    thr = threshold_for_selectivity(corpus, 0.05, metric)
    wi, wj, wv = brute_force_join(corpus, thr, metric)
    res = similarity_join(corpus, mesh, threshold=thr, metric=metric,
                          mode="batched", placement={name!r},
                          use_kernel=True, quant="off")
    assert not res.overflow and len(wi) > 0
    np.testing.assert_array_equal(res.i, wi)
    np.testing.assert_array_equal(res.j, wj)
    np.testing.assert_allclose(res.scores, wv, rtol=1e-6, atol=1e-5)
print("JOIN-KERNEL-OK")
"""
    out = run_sub(code, P)
    assert "JOIN-KERNEL-OK" in out


def test_capacity_remembered_across_joins(monkeypatch):
    """A join that had to escalate leaves its capacity and slot to the
    next join of the same program, which escalates no more; both equal
    the oracle."""
    import jax
    from repro.core.sparse import similarity_join
    from repro.launch.mesh import make_mesh
    monkeypatch.setenv("REPRO_SPARSE_CAPACITY", "128")
    mesh = make_mesh((1,), ("q",), devices=jax.devices()[:1])
    rng = np.random.default_rng(11)
    first, second = (rng.normal(size=(48, 8)).astype(np.float32)
                     for _ in range(2))
    thr = threshold_for_selectivity(first, 0.25)
    runs = []
    for corpus in (first, second):
        res = similarity_join(corpus, mesh, threshold=thr, mode="batched",
                              use_kernel=True, quant="off")
        wi, wj, _ = brute_force_join(corpus, thr)
        np.testing.assert_array_equal(res.i, wi)
        np.testing.assert_array_equal(res.j, wj)
        runs.append(res)
    assert runs[0].escalations >= 1 and runs[0].n_pairs > 128
    assert runs[1].escalations == 0
    assert runs[1].capacity == runs[0].capacity
