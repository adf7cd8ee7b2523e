"""Distributed self-check for the quorum all-pairs engine.

Run as ``XLA_FLAGS=--xla_force_host_platform_device_count=<P> python -m
repro.core.selfcheck [P] [modes] [placement]`` — the test suite invokes
this in a subprocess so the main pytest process keeps a single CPU device
(see launch/dryrun.py note).  ``modes`` is an optional comma-separated
subset of the engine modes (default: all of batched, overlap, scan).
``placement`` is an optional placement spec (a registered name, ``auto``,
or ``plane``); unset it defers to the ``REPRO_PLACEMENT`` env var — the
CI placement matrix sets only the env var.

Checks, for a toy n-body-style interaction: every engine execution mode
under the selected placement == allgather_allpairs == pure-numpy O(N^2)
oracle.  A full-replication placement delegates to allgather inside the
engine, so the check degenerates to oracle equality (still asserted per
requested mode).
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .allpairs import (ENGINE_MODES, allgather_allpairs, pair_mask_table,
                       quorum_allpairs)
from .placement import placement_from_env, resolve_placement


def pairwise_force(bi, bj):
    """Toy 1/r^2-ish interaction between two blocks of 3D points."""
    d = bi[:, None, :] - bj[None, :, :]                  # [m, n, 3]
    r2 = jnp.sum(d * d, axis=-1) + 1e-3
    f = d / (r2 ** 1.5)[..., None]
    out_i = jnp.sum(f, axis=1)                           # force on bi points
    out_j = -jnp.sum(f, axis=0)                          # force on bj points
    return out_i, out_j


def oracle(x: np.ndarray) -> np.ndarray:
    """Numpy O(N^2) oracle for the toy interaction."""
    n = x.shape[0]
    d = x[:, None, :] - x[None, :, :]
    r2 = (d * d).sum(-1) + 1e-3
    f = d / (r2 ** 1.5)[..., None]
    # exclude self-interaction of identical points? the toy kernel includes
    # i==j terms (d=0 -> f=0 anyway), so the plain sum matches.
    return f.sum(axis=1)


def main(nblocks: int | None = None,
         modes: tuple[str, ...] = ENGINE_MODES,
         placement: str | None = None) -> None:
    """Run the engine selfcheck (see module docstring for the CLI)."""
    devs = jax.devices()
    Pn = nblocks or len(devs)
    assert len(devs) >= Pn, f"need {Pn} devices, have {len(devs)}"
    plc = (placement_from_env(Pn) if placement is None
           else resolve_placement(placement, Pn))
    from ..launch.mesh import make_mesh
    mesh = make_mesh((Pn,), ("q",), devices=devs[:Pn])
    sched = None if plc.full else plc.schedule()
    block = 8
    rng = np.random.default_rng(0)
    x = rng.normal(size=(Pn * block, 3)).astype(np.float32)
    masks = (np.ones((Pn, 1), np.float32) if sched is None
             else pair_mask_table(sched))  # [P, n_pairs]

    def run_quorum(xs, ms, mode):
        def f(xb, mb):
            if plc.full:  # engine routes to allgather; mask does not apply
                return quorum_allpairs(pairwise_force, xb, axis_name="q",
                                       mode=mode, placement=plc)
            return quorum_allpairs(pairwise_force, xb, axis_name="q",
                                   schedule=sched, mask=mb, mode=mode,
                                   placement=plc)
        return jax.jit(jax.shard_map(f, mesh=mesh,
                                     in_specs=(P("q"), P("q")),
                                     out_specs=P("q")))(xs, ms)

    @jax.jit
    def run_allgather(xs):
        def f(xb):
            return allgather_allpairs(pairwise_force, xb, axis_name="q",
                                      axis_size=Pn)
        return jax.shard_map(f, mesh=mesh, in_specs=P("q"), out_specs=P("q"))(xs)

    want = oracle(x)
    got_a = np.asarray(run_allgather(x))
    np.testing.assert_allclose(got_a, want, rtol=2e-4, atol=2e-5)
    max_err = 0.0
    for mode in modes:
        got_q = np.asarray(run_quorum(x, masks, mode))
        np.testing.assert_allclose(got_q, want, rtol=2e-4, atol=2e-5,
                                   err_msg=f"mode={mode} vs oracle")
        np.testing.assert_allclose(got_q, got_a, rtol=2e-4, atol=2e-5,
                                   err_msg=f"mode={mode} vs allgather")
        max_err = max(max_err, float(np.abs(got_q - want).max()))
    pairs = "P" if plc.full else str(sched.n_pairs)
    print(f"selfcheck OK: P={Pn} placement={plc.describe()} "
          f"k={plc.replication} pairs/dev={pairs} "
          f"modes={','.join(modes)} max|err|={max_err:.2e}")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else None,
         tuple(sys.argv[2].split(",")) if len(sys.argv) > 2 else ENGINE_MODES,
         sys.argv[3] if len(sys.argv) > 3 else None)
