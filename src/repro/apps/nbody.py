"""Direct-interaction n-body forces — the paper's motivating algorithm family
(atom-decomposition [7] vs force-decomposition vs quorums, paper section 1.2).

``quorum`` strategy uses the engine (one array of k*N/P bodies per device);
``atom`` is the all-gather atom-decomposition baseline (N bodies per device).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.allpairs import (allgather_allpairs, pair_mask_table,
                             quorum_allpairs)
from ..core.scheduler import build_schedule

SOFTENING = 1e-2


def pair_forces(bi: jax.Array, bj: jax.Array):
    """Gravitational interaction between body blocks [m, 4] (x, y, z, mass).

    Returns (force on bi bodies [m, 3], force on bj bodies [n, 3]).
    Newton's third law: computed once per pair — the paper's Fig. 1 saving.
    """
    pi, mi = bi[:, :3], bi[:, 3]
    pj, mj = bj[:, :3], bj[:, 3]
    d = pj[None, :, :] - pi[:, None, :]                 # [m, n, 3]
    r2 = jnp.sum(d * d, axis=-1) + SOFTENING
    inv_r3 = jax.lax.rsqrt(r2) / r2
    w = (mi[:, None] * mj[None, :] * inv_r3)[..., None]  # [m, n, 1]
    f_ij = w * d                                        # force ON i FROM j
    return jnp.sum(f_ij, axis=1), -jnp.sum(f_ij, axis=0)


def forces_reference(bodies: np.ndarray) -> np.ndarray:
    """Numpy O(N^2) force oracle (tests/benchmarks compare against it)."""
    p, m = bodies[:, :3], bodies[:, 3]
    d = p[None, :, :] - p[:, None, :]
    r2 = (d * d).sum(-1) + SOFTENING
    w = (m[:, None] * m[None, :]) / (np.sqrt(r2) * r2)
    return (w[..., None] * d).sum(axis=1)


@functools.lru_cache(maxsize=64)
def forces_fn(mesh, axis_name: str = "q", strategy: str = "quorum",
              mode: str = "auto", use_kernel: bool = False):
    """Build (and cache) the jitted distributed-forces callable.

    Cached per (mesh, axis_name, strategy, mode, use_kernel) so repeated
    calls — simulation steps, benchmark reps — reuse one traced/compiled
    executable instead of re-jitting a fresh closure every call.
    Returns ``f(bodies [N, 4]) -> forces [N, 3]``.
    """
    from jax.sharding import PartitionSpec as PS
    P = mesh.shape[axis_name]
    if strategy == "quorum":
        sched = build_schedule(P)
        masks = jnp.asarray(pair_mask_table(sched))
        from ..kernels import ops as kops
        batch_fn = None
        if use_kernel:
            if mode not in ("batched", "auto"):
                raise ValueError(
                    f"use_kernel needs the batched mode (got mode={mode!r}); "
                    "the fused kernel only replaces the batched inner step")
            batch_fn = functools.partial(kops.pairwise_batch_forces,
                                         softening=SOFTENING)

        def body(xb, mb):
            return quorum_allpairs(pair_forces, xb, axis_name=axis_name,
                                   schedule=sched, mask=mb, mode=mode,
                                   batch_fn=batch_fn)

        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(PS(axis_name), PS(axis_name)),
            out_specs=PS(axis_name),
            check_vma=kops.shard_map_check_vma(use_kernel)))
        return lambda bodies: fn(bodies, masks)
    if strategy == "atom":
        if use_kernel:
            raise ValueError("use_kernel applies only to strategy='quorum'")

        def body(xb):
            return allgather_allpairs(pair_forces, xb, axis_name=axis_name,
                                      axis_size=P)
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=PS(axis_name),
            out_specs=PS(axis_name)))
    raise ValueError(strategy)


def distributed_forces(bodies, mesh, *, axis_name: str = "q",
                       strategy: str = "quorum", mode: str = "auto",
                       use_kernel: bool = False):
    """bodies: [N, 4] sharded over axis_name.  Returns forces [N, 3].

    ``mode`` selects the engine execution mode (batched / overlap / scan /
    auto — see core.allpairs and DESIGN.md section 4).  ``use_kernel`` routes
    the batched mode through the fused Pallas pairwise_batch kernel.
    """
    return forces_fn(mesh, axis_name, strategy, mode, use_kernel)(bodies)


def leapfrog_step(bodies, vel, dt, forces):
    """Symplectic integrator step (example driver uses this)."""
    m = bodies[:, 3:4]
    vel = vel + dt * forces / m
    pos = bodies[:, :3] + dt * vel
    return jnp.concatenate([pos, bodies[:, 3:4]], axis=-1), vel
