"""Streaming-update layer tests (serving/stream.py): direct coverage of
the block-update path — append-then-replace ordering on one block,
propagation to overlapping holder quorums, ragged (short) appends and
the validity column, and the dirty-block listener hooks that feed
standing delta indexes (DESIGN.md sections 12 and 16.5).

Device-touching tests run in fake-device subprocesses (the dry-run
isolation rule, see tests/test_distributed.py); the listener registry is
pure host code and is exercised in-process as well."""

import os
import subprocess
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"


def run_sub(code: str, devices: int) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(SRC)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_append_then_replace_ordering_same_block():
    """An append (a replace into empty capacity) followed by a replace of
    the same block must leave only the second write visible: rows beyond
    the new data are zeroed and invalid — no stale append rows linger."""
    code = """
import jax, numpy as np
from repro.serving.stream import build_state, replace_block
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("q",))
rng = np.random.default_rng(0)
corpus = rng.normal(size=(8, 3)).astype(np.float32)
st = build_state(corpus, mesh, block=4)        # capacity 16; block 3 hosts
a = rng.normal(size=(3, 3)).astype(np.float32)  # rows 12..14
st = replace_block(st, mesh, "q", 3, a)         # the 'append'
assert np.asarray(st.valid)[12:16].tolist() == [True, True, True, False]
b = rng.normal(size=(1, 3)).astype(np.float32)
st = replace_block(st, mesh, "q", 3, b)         # then replace, shorter
shard = np.asarray(st.shard); valid = np.asarray(st.valid)
np.testing.assert_array_equal(shard[12], b[0])
assert valid[12:16].tolist() == [True, False, False, False]
np.testing.assert_array_equal(shard[13:16], np.zeros((3, 3), np.float32))
print("ORDERING-OK")
"""
    assert "ORDERING-OK" in run_sub(code, 4)


def test_replace_propagates_to_overlapping_quorums():
    """A replaced block must land at every holder's matching stack slot
    (the block lives in k overlapping quorums) and leave every other
    slot — and its validity row — bit-untouched."""
    code = """
import jax, numpy as np
from repro.core.placement import get_placement
from repro.serving.stream import build_state, replace_block
P = 5
plc = get_placement("cyclic", P)
from repro.launch.mesh import make_mesh
mesh = make_mesh((P,), ("q",))
rng = np.random.default_rng(1)
corpus = rng.normal(size=(P * 2, 3)).astype(np.float32)
st = build_state(corpus, mesh, placement=plc)
A = plc.schedule().A
k = len(A)
assert len(plc.block_holders(2)) == k >= 2
new = rng.normal(size=(2, 3)).astype(np.float32)
st2 = replace_block(st, mesh, "q", 2, new, placement=plc)
s0, s1 = np.asarray(st.stack), np.asarray(st2.stack)
v0, v1 = np.asarray(st.stack_valid), np.asarray(st2.stack_valid)
touched = 0
for i in range(P):
    for s, a in enumerate(A):
        r = i * k + s
        if (i + a) % P == 2:       # device i's slot s holds block 2
            touched += 1
            np.testing.assert_array_equal(s1[r], new)
            assert v1[r].all()
        else:                      # every other slot arrives unchanged
            np.testing.assert_array_equal(s1[r], s0[r])
            np.testing.assert_array_equal(v1[r], v0[r])
assert touched == k
print("QUORUM-OK")
"""
    assert "QUORUM-OK" in run_sub(code, 5)


def test_ragged_append_validity_column():
    """A short (ragged) append: nvalid < data rows marks the tail
    invalid in both the owner shard and every holder's stack-validity
    row (the validity column rides the same permute as the data), and
    out-of-range nvalid is rejected."""
    code = """
import jax, numpy as np
from repro.core.placement import placement_from_env
from repro.serving.stream import build_state, replace_block
P = 4
from repro.launch.mesh import make_mesh
mesh = make_mesh((P,), ("q",))
rng = np.random.default_rng(2)
corpus = rng.normal(size=(6, 3)).astype(np.float32)
st = build_state(corpus, mesh, block=2)         # block 3 (rows 6,7) empty
assert np.asarray(st.valid).sum() == 6
data = rng.normal(size=(2, 3)).astype(np.float32)
st2 = replace_block(st, mesh, "q", 3, data, nvalid=1)
valid = np.asarray(st2.valid); shard = np.asarray(st2.shard)
assert valid[6] and not valid[7]
np.testing.assert_array_equal(shard[6:8], data)  # data lands, row 7 invalid
plc = placement_from_env(P)
A = plc.schedule().A
k = len(A)
sv = np.asarray(st2.stack_valid); stk = np.asarray(st2.stack)
seen = 0
for i in range(P):
    for s, a in enumerate(A):
        if (i + a) % P == 3:
            r = i * k + s
            seen += 1
            assert sv[r, 0] and not sv[r, 1]
            np.testing.assert_array_equal(stk[r], data)
assert seen == k
try:
    replace_block(st, mesh, "q", 3, data, nvalid=3)
    raise SystemExit("nvalid=3 > rows must raise")
except ValueError:
    pass
try:
    replace_block(st, mesh, "q", 3, rng.normal(size=(3, 3)).astype(np.float32))
    raise SystemExit("rows > block capacity must raise")
except ValueError:
    pass
print("RAGGED-OK")
"""
    assert "RAGGED-OK" in run_sub(code, 4)


def test_dirty_listener_fires_per_update():
    """Every streamed update (replace, and append via the serving corpus)
    notifies registered dirty listeners with the block id — the hook
    that marks core.delta.DeltaIndex standing outputs dirty."""
    code = """
import jax, numpy as np
from repro.serving.stream import (build_state, register_dirty_listener,
                                  replace_block, unregister_dirty_listener)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("q",))
rng = np.random.default_rng(3)
corpus = rng.normal(size=(8, 3)).astype(np.float32)
st = build_state(corpus, mesh)
seen = []
hook = register_dirty_listener(seen.append)   # returns fn (decorator form)
assert hook is seen.append or hook == seen.append
st = replace_block(st, mesh, "q", 1, rng.normal(size=(2, 3)).astype(np.float32))
st = replace_block(st, mesh, "q", 3, rng.normal(size=(2, 3)).astype(np.float32))
assert seen == [1, 3], seen
unregister_dirty_listener(seen.append)
st = replace_block(st, mesh, "q", 0, rng.normal(size=(2, 3)).astype(np.float32))
assert seen == [1, 3], seen                   # unregistered: no more events
unregister_dirty_listener(seen.append)        # double-remove is a no-op
print("LISTENER-OK")
"""
    assert "LISTENER-OK" in run_sub(code, 4)


def test_listener_registry_is_host_only():
    """The registry itself needs no devices: register/unregister and the
    decorator form work without touching jax."""
    from repro.serving import stream

    seen = []

    @stream.register_dirty_listener
    def hook(b):
        seen.append(b)

    try:
        stream._notify_dirty(7)
        assert seen == [7]
    finally:
        stream.unregister_dirty_listener(hook)
    stream._notify_dirty(9)
    assert seen == [7]
    stream.unregister_dirty_listener(hook)  # no-op after removal
