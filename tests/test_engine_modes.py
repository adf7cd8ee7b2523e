"""Equivalence tests for the engine's execution modes (DESIGN.md section 4).

Each subprocess pins its own fake-device count (dry-run isolation rule, see
tests/test_distributed.py).  repro.core.selfcheck compares every mode in
(batched, overlap, scan) against allgather_allpairs and the numpy oracle;
P values here complement test_distributed's 4/5/8 with the P = 2 edge
(k = P, single shift) and P = 6 (even, d = P/2 orbit with k = 3 so the
overlap schedule has a non-trivial ready order).
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src"


def run_sub(code: str, devices: int) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(SRC)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


@pytest.mark.parametrize("P", [2, 6])
def test_engine_modes_agree(P):
    out = run_sub(f"from repro.core.selfcheck import main; main({P})", P)
    assert "selfcheck OK" in out
    assert "batched,overlap,scan" in out


def test_nbody_modes_and_fused_kernel():
    """distributed_forces across every mode — including the batched mode
    routed through the fused Pallas pairwise_batch kernel — against the
    numpy O(N^2) reference."""
    code = """
import numpy as np, jax, jax.numpy as jnp
from repro.apps.nbody import distributed_forces, forces_reference
rng = np.random.default_rng(1)
N = 32
bodies = np.concatenate([rng.normal(size=(N,3)),
                         rng.uniform(0.5, 2, (N,1))], -1).astype(np.float32)
mesh = jax.make_mesh((4,), ("q",), axis_types=(jax.sharding.AxisType.Auto,))
ref = forces_reference(bodies)
for mode, uk in [("batched", True), ("batched", False), ("overlap", False),
                 ("scan", False), ("auto", False)]:
    out = np.asarray(distributed_forces(jnp.asarray(bodies), mesh,
                                        mode=mode, use_kernel=uk))
    err = np.abs(out - ref).max() / np.abs(ref).max()
    assert err < 1e-4, (mode, uk, err)
print("NBODY-MODES-OK")
"""
    assert "NBODY-MODES-OK" in run_sub(code, 4)


@pytest.mark.parametrize("mode", ["batched", "overlap"])
def test_pcit_modes_match_reference(mode):
    """The PCIT tile phases in the unrolled modes (scan is covered by
    test_distributed) against the O(N^3) numpy reference, odd P."""
    code = f"""
import numpy as np, jax
from repro.apps.pcit import run_quorum_pcit, pcit_reference, correlation_reference
rng = np.random.default_rng(0)
N, G = 30, 18
Z = rng.normal(size=(4, G)); W = rng.normal(size=(N, 4))
X = W @ Z + 0.5 * rng.normal(size=(N, G))
mesh = jax.make_mesh((5,), ("q",), axis_types=(jax.sharding.AxisType.Auto,))
corr, keep = run_quorum_pcit(X, mesh, mode="{mode}")
np.testing.assert_allclose(corr, correlation_reference(X), rtol=1e-4, atol=1e-5)
assert (keep == pcit_reference(X)).all()
print("PCIT-MODE-OK")
"""
    assert "PCIT-MODE-OK" in run_sub(code, 5)


def test_env_var_mode_override():
    """REPRO_ALLPAIRS_MODE forces auto-mode selection (the benchmark/CI
    A/B hook) without changing results."""
    code = """
import os
os.environ["REPRO_ALLPAIRS_MODE"] = "overlap"
from repro.core.selfcheck import main
main(4, modes=("auto",))
"""
    out = run_sub(code, 4)
    assert "selfcheck OK" in out


def test_default_mask_dedups_half_orbit():
    """mask=None must dedup the doubly-generated d = P/2 orbit on even P
    (the engine derives the device's pair_mask_table row via axis_index) —
    without it those pair contributions come out exactly 2x."""
    code = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as PS
from repro.core.allpairs import quorum_allpairs
from repro.core.scheduler import build_schedule
from repro.core.selfcheck import pairwise_force, oracle
P, block = 6, 8
sched = build_schedule(P)
rng = np.random.default_rng(0)
x = rng.normal(size=(P * block, 3)).astype(np.float32)
from repro.launch.mesh import make_mesh
mesh = make_mesh((P,), ("q",))
for mode in ["scan", "batched", "overlap"]:
    def f(xb):
        return quorum_allpairs(pairwise_force, xb, axis_name="q",
                               schedule=sched, mode=mode)  # mask=None
    got = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=PS("q"),
                                out_specs=PS("q")))(x)
    np.testing.assert_allclose(np.asarray(got), oracle(x),
                               rtol=2e-4, atol=2e-5, err_msg=mode)
print("DEFAULT-MASK-OK")
"""
    assert "DEFAULT-MASK-OK" in run_sub(code, 6)


def test_select_mode_heuristic(monkeypatch):
    """The auto heuristic itself: env override wins (and typos raise, not
    silently fall through to the heuristic), a fused batch_fn forces
    batched, the byte budget pushes big problems to overlap/scan."""
    import jax
    import jax.numpy as jnp

    from repro.core.allpairs import _select_mode
    from repro.core.scheduler import build_schedule

    sched = build_schedule(8)  # k = 4
    x = jnp.zeros((16, 4), jnp.float32)
    probe = jax.ShapeDtypeStruct((16, 3), jnp.float32)

    monkeypatch.delenv("REPRO_ALLPAIRS_MODE", raising=False)
    assert _select_mode(sched, x, probe, None) == "batched"  # small: fits
    assert _select_mode(sched, x, probe, object()) == "batched"  # fused kernel

    monkeypatch.setenv("REPRO_ALLPAIRS_MODE", "scan")
    assert _select_mode(sched, x, probe, None) == "scan"
    monkeypatch.setenv("REPRO_ALLPAIRS_MODE", "batch")  # typo
    with pytest.raises(ValueError, match="REPRO_ALLPAIRS_MODE"):
        _select_mode(sched, x, probe, None)
    monkeypatch.delenv("REPRO_ALLPAIRS_MODE")

    monkeypatch.setenv("REPRO_BATCH_BYTES_LIMIT", "1")
    assert _select_mode(sched, x, probe, None) == "overlap"  # k >= 3
    sched2 = build_schedule(2)  # k = 2: nothing to hide behind
    assert _select_mode(sched2, x, probe, None) == "scan"


def test_batch_bytes_limit_read_at_select_time(monkeypatch):
    """Regression: REPRO_BATCH_BYTES_LIMIT set *after* import must still be
    honored — the budget is read inside _select_mode, not at module load."""
    import jax
    import jax.numpy as jnp

    import repro.core.allpairs as ap
    from repro.core.scheduler import build_schedule

    sched = build_schedule(8)  # k = 4
    x = jnp.zeros((16, 4), jnp.float32)
    probe = jax.ShapeDtypeStruct((16, 3), jnp.float32)

    monkeypatch.delenv("REPRO_ALLPAIRS_MODE", raising=False)
    monkeypatch.delenv("REPRO_BATCH_BYTES_LIMIT", raising=False)
    assert ap.auto_batch_bytes() == ap._DEFAULT_BATCH_BYTES
    assert ap._select_mode(sched, x, probe, None) == "batched"
    # the module is long imported; shrinking the budget now must take effect
    monkeypatch.setenv("REPRO_BATCH_BYTES_LIMIT", "1")
    assert ap.auto_batch_bytes() == 1
    assert ap._select_mode(sched, x, probe, None) == "overlap"


def test_use_kernel_requires_batched_mode():
    """The fused kernel only replaces the batched inner step; asking for it
    with another mode (or the atom strategy) must error, not silently run
    the jnp path."""
    code = """
import numpy as np, jax, jax.numpy as jnp
from repro.apps.nbody import distributed_forces
bodies = jnp.zeros((8, 4), jnp.float32)
mesh = jax.make_mesh((2,), ("q",), axis_types=(jax.sharding.AxisType.Auto,))
for kwargs in [dict(mode="overlap", use_kernel=True),
               dict(strategy="atom", use_kernel=True)]:
    try:
        distributed_forces(bodies, mesh, **kwargs)
    except ValueError as e:
        assert "use_kernel" in str(e), e
    else:
        raise AssertionError(f"no error for {kwargs}")

# the engine-level guard: batch_fn with a non-batched explicit mode
from repro.core.allpairs import quorum_allpairs
try:
    quorum_allpairs(lambda a, b: (a, b), bodies, axis_name="q",
                    axis_size=2, mode="scan", batch_fn=lambda *a: None)
except ValueError as e:
    assert "batch_fn" in str(e), e
else:
    raise AssertionError("no error for engine-level batch_fn conflict")
print("KERNEL-GUARD-OK")
"""
    assert "KERNEL-GUARD-OK" in run_sub(code, 2)
