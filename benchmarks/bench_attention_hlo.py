"""Measured collective schedule: quorum vs ring sequence-parallel attention.

Lowers both strategies on a 16-device mesh (subprocess) and parses the
optimized HLO: per-device wire bytes and collective-op counts.  This is the
measured counterpart of bench_attention_comm's analytic model, and the
evidence for the beyond-paper claim (sqrt(P) collective phases vs P-1).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"
ROOT = Path(__file__).resolve().parents[1]

_CHILD = r"""
import json, sys
sys.path.insert(0, %r)
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as PS
from repro.apps.attention import quorum_attention_local, ring_attention_local
from repro.core.scheduler import build_causal_schedule
from repro.launch.dryrun import collective_bytes

P = 16
B, T, H, KV, hd = 1, 16*512, 8, 8, 64
mesh = jax.make_mesh((P,), ("q",), axis_types=(jax.sharding.AxisType.Auto,))
sched = build_causal_schedule(P)
valid = sched.valid.astype(np.float32)
q = jax.ShapeDtypeStruct((B, T, H, hd), jnp.bfloat16)
kv = jax.ShapeDtypeStruct((B, T, KV, hd), jnp.bfloat16)
vr = jax.ShapeDtypeStruct(valid.shape, jnp.float32)

out = {}
with mesh:
    f_q = jax.jit(jax.shard_map(
        lambda qb, kb, vb, v: quorum_attention_local(qb, kb, vb, v,
                                                     schedule=sched, axis_name="q"),
        mesh=mesh,
        in_specs=(PS(None, "q"), PS(None, "q"), PS(None, "q"), PS("q")),
        out_specs=PS(None, "q")))
    txt = f_q.lower(q, kv, kv, vr).compile().as_text()
    out["quorum"] = collective_bytes(txt)
    f_r = jax.jit(jax.shard_map(
        lambda qb, kb, vb: ring_attention_local(qb, kb, vb, axis_name="q",
                                                axis_size=P),
        mesh=mesh,
        in_specs=(PS(None, "q"),) * 3, out_specs=PS(None, "q")))
    txt = f_r.lower(q, kv, kv).compile().as_text()
    # ring permutes live inside a scan body: multiply by trip count P
    c = collective_bytes(txt)
    c = {k: (v * P if k != "count" else v) for k, v in c.items()}
    out["ring"] = c
print(json.dumps(out))
""" % (str(SRC),)


def run(csv_rows):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["JAX_PLATFORMS"] = "cpu"     # virtual devices, never the chip
    env["PYTHONPATH"] = f"{SRC}:{ROOT}"
    r = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    qw = res["quorum"]["wire_total"]
    rw = res["ring"]["wire_total"]
    csv_rows.append(("attn_hlo_quorum_P16", f"{qw/1e6:.1f}",
                     f"MB_wire;ops={res['quorum']['count']};"
                     f"ring_MB={rw/1e6:.1f};ring_ops_x_trip={res['ring']['count']}x16;"
                     f"bytes_ratio={qw/max(rw,1):.2f}"))
