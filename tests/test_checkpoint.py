"""Tests for ckpt/checkpoint.py (previously untested).

In-process: save/restore roundtrips, the atomic-commit manifest rule,
retention GC, and the async manager's error surfacing.  In fake-device
subprocesses (dry-run isolation rule): a sharded roundtrip across
placements, and a P-rescale restore driven by ``elastic.rescale`` — the
checkpoint stores the *global* arrays, so a resize is a restore under
the new mesh plus the plan's residency fetches.
"""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.ckpt import (CheckpointManager, load_checkpoint, save_checkpoint)
from repro.ckpt.checkpoint import latest_step

SRC = Path(__file__).resolve().parents[1] / "src"


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "params": {"w": rng.normal(size=(12, 4)).astype(np.float32),
                   "b": rng.normal(size=(4,)).astype(np.float32)},
        "step": np.int32(seed),
        "scales": [rng.uniform(size=(3,)).astype(np.float32),
                   rng.uniform(size=(5,)).astype(np.float32)],
    }


def _assert_tree_equal(a, b):
    import jax
    fa, _ = jax.tree.flatten(a)
    fb, _ = jax.tree.flatten(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_roundtrip(tmp_path):
    tree = _tree(3)
    path = save_checkpoint(tmp_path, 7, tree)
    assert path == tmp_path / "step_7"
    assert (path / "MANIFEST.json").exists()
    like = _tree(0)                       # same structure, different values
    restored, step = load_checkpoint(tmp_path, like)
    assert step == 7
    _assert_tree_equal(restored, tree)


def test_latest_step_requires_manifest(tmp_path):
    assert latest_step(tmp_path) is None
    save_checkpoint(tmp_path, 1, _tree(1))
    save_checkpoint(tmp_path, 5, _tree(5))
    # a crash mid-write leaves no MANIFEST: the step must be ignored
    torn = tmp_path / "step_9"
    torn.mkdir()
    (torn / "arrays.npz").write_bytes(b"torn write")
    assert latest_step(tmp_path) == 5
    restored, step = load_checkpoint(tmp_path, _tree(0))
    assert step == 5
    _assert_tree_equal(restored, _tree(5))


def test_dtype_restored_from_target_structure(tmp_path):
    import jax.numpy as jnp
    tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    save_checkpoint(tmp_path, 0, {"w": jnp.asarray(tree["w"], jnp.bfloat16)})
    like = {"w": jnp.zeros((2, 3), jnp.bfloat16)}
    restored, _ = load_checkpoint(tmp_path, like)
    assert restored["w"].dtype == jnp.bfloat16


def test_manager_async_gc_and_resume(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save_async(s, _tree(s))
        mgr.wait()
    kept = sorted(d.name for d in tmp_path.iterdir())
    assert kept == ["step_3", "step_4"]
    restored, step = mgr.restore_latest(_tree(0))
    assert step == 4
    _assert_tree_equal(restored, _tree(4))


def test_manager_surfaces_async_errors(tmp_path, monkeypatch):
    mgr = CheckpointManager(tmp_path / "sub", keep=2)
    import repro.ckpt.checkpoint as ck

    def boom(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr(ck, "save_checkpoint", boom)
    mgr.save_async(1, _tree(1))
    with pytest.raises(OSError, match="disk full"):
        mgr.wait()
    # the error is consumed: the manager is reusable afterwards
    monkeypatch.undo()
    mgr.save_async(2, _tree(2))
    mgr.wait()
    assert latest_step(tmp_path / "sub") == 2


def run_sub(code: str, devices: int) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(SRC)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_sharded_roundtrip_across_placements(tmp_path):
    """Save a corpus sharded under one placement's mesh, restore it under
    another placement (and its residency): the checkpoint stores global
    arrays, so a placement migration is a restore + the rescale plan's
    residency delta."""
    code = f"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as PS
from repro.ckpt import save_checkpoint, load_checkpoint
from repro.core.placement import get_placement
from repro.launch.elastic import rescale

tmp = {str(tmp_path)!r}
P = 12
mesh = jax.make_mesh((P,), ("q",), axis_types=(jax.sharding.AxisType.Auto,))
sh = NamedSharding(mesh, PS("q"))
rng = np.random.default_rng(0)
corpus = rng.normal(size=(P * 8, 16)).astype(np.float32)
tree = {{"corpus": jax.device_put(jnp.asarray(corpus), sh),
         "step": jnp.int32(11)}}
save_checkpoint(tmp, 11, tree)

# same-P placement migrations: affine (P = 12 is a plane P) fetches at
# most its residency delta; full replication must fetch the complement
plan_affine = rescale(P, P, "cyclic", "affine")
assert plan_affine.is_migration
plan = rescale(P, P, "cyclic", "full")
assert plan.is_migration and plan.total_fetch_blocks > 0
like = {{"corpus": jnp.zeros_like(tree["corpus"]), "step": jnp.int32(0)}}
restored, step = load_checkpoint(tmp, like,
                                 shardings={{"corpus": sh, "step": None}})
assert step == 11
np.testing.assert_array_equal(np.asarray(restored["corpus"]), corpus)
assert restored["corpus"].sharding == sh
# every device can materialize its new residency from the restored global
block = corpus.shape[0] // P
for dev, res in enumerate(plan.new_quorums):
    for b in res:
        np.testing.assert_array_equal(
            np.asarray(restored["corpus"][b * block:(b + 1) * block]),
            corpus[b * block:(b + 1) * block])
print("CKPT-PLACEMENT-OK")
"""
    assert "CKPT-PLACEMENT-OK" in run_sub(code, 12)


def test_rescale_restore(tmp_path):
    """P-rescale restore: a checkpoint written under P_old restores under
    a P_new mesh (re-chunked residency from elastic.rescale); values are
    bit-identical and the fetch plan covers every new residency set."""
    code = f"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as PS
from repro.ckpt import save_checkpoint, load_checkpoint
from repro.launch.elastic import rescale

tmp = {str(tmp_path)!r}
P_old, P_new = 4, 6
N, d = 48, 8                      # divisible by both P values
devs = jax.devices()
from repro.launch.mesh import make_mesh
mesh_old = make_mesh((P_old,), ("q",), devices=devs[:P_old])
rng = np.random.default_rng(1)
corpus = rng.normal(size=(N, d)).astype(np.float32)
x = jax.device_put(jnp.asarray(corpus), NamedSharding(mesh_old, PS("q")))
save_checkpoint(tmp, 3, {{"corpus": x}})

plan = rescale(P_old, P_new)
assert plan.P_new == P_new and plan.schedule.P == P_new
# a resize reuses nothing: every device fetches its whole new residency
assert plan.fetches == {{i: list(q) for i, q in enumerate(plan.new_quorums)}}

from repro.launch.mesh import make_mesh
mesh_new = make_mesh((P_new,), ("q",), devices=devs[:P_new])
sh_new = NamedSharding(mesh_new, PS("q"))
restored, step = load_checkpoint(tmp, {{"corpus": jnp.zeros((N, d))}},
                                 shardings={{"corpus": sh_new}})
assert step == 3
np.testing.assert_array_equal(np.asarray(restored["corpus"]), corpus)
assert restored["corpus"].sharding == sh_new
block = N // P_new
for dev, res in enumerate(plan.new_quorums):   # new residency materializes
    for b in res:
        np.testing.assert_array_equal(
            np.asarray(restored["corpus"][b * block:(b + 1) * block]),
            corpus[b * block:(b + 1) * block])
print("CKPT-RESCALE-OK")
"""
    assert "CKPT-RESCALE-OK" in run_sub(code, 6)
