"""Analytical comm-volume predictor for the sweep data plane
(DESIGN.md section 14.3).

Every collective the runtime issues has a statically-known payload —
the schedule's shift structure fixes the hop count and block shapes fix
the bytes — so per-device communication is a pure function of
(placement, block bytes):

  * quorum gather:  one ppermute hop per **nonzero** shift, each moving
    one block — ``(k - 1) * block_bytes`` per device for a difference
    set containing 0.
  * quorum scatter: the inverse shifts move per-slot partials —
    ``(k - 1) * partial_bytes`` per device.
  * full placement: the engine routes through ``lax.all_gather`` —
    ``(P - 1) * block_bytes`` per device and **zero** ppermute hops.
  * serving tree merge: ``ceil(log2 P)`` doubling hops; ring gather:
    ``P - 1`` hops.

Resident bytes per device are ``replication * block_bytes`` — the
paper's O(N/sqrt(P)) replication claim, versus N for all-gather; the
cluster-wide ppermute ratio ``(k-1)/(P-1)`` is the same sqrt saving on
the wire.  The traced actuals (``obs.trace`` counters recorded at jit
trace time, exact because collective shapes are static) must match
these predictions bit-for-bit; :func:`verify_dense_comm` asserts it for
every registered placement and is wired into CI as ``python -m
repro.obs.comm`` (run under fake devices).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..core.placement import (Placement, resolve_placement,
                              supported_placements)
from . import trace as trace_mod

__all__ = [
    "SweepComm",
    "block_bytes_of",
    "quant_block_bytes",
    "predict_sweep_comm",
    "predict_tree_merge_comm",
    "predict_ring_gather_comm",
    "traced_sweep_comm",
    "verify_dense_comm",
    "verify_quant_comm",
]


def block_bytes_of(block: int, dim: int, dtype: str = "float32") -> int:
    """One [block, dim] quorum block's payload bytes under ``dtype``
    (DESIGN.md section 14.3) — the predictor's dtype-itemsize
    parametrization; ``int8``/``bfloat16`` stacks shrink every gather
    hop by the same 4x/2x their residency shrinks."""
    return block * dim * np.dtype(dtype).itemsize


def quant_block_bytes(block: int, dim: int, mode: str) -> int:
    """One quantized block's per-hop gather payload (DESIGN.md section
    17.1): the [block, dim] codes at the mode's itemsize plus the side
    arrays that ride the same shifts — scale + delta (two f32 scalars)
    and the l1 + sq f32 rows.  Mirrors core.quant's QuantBlocks pytree
    leaf-for-leaf, so the traced gather bytes of a quantized sweep
    equal ``nonzero_shifts * quant_block_bytes`` exactly."""
    from ..core.quant import quant_itemsize
    return block * dim * quant_itemsize(mode) + 8 + 8 * block


@dataclasses.dataclass(frozen=True)
class SweepComm:
    """Predicted per-device communication of one sweep under a placement
    (DESIGN.md section 14.3).  All byte fields are **per device**; the
    SPMD programs are symmetric, so the cluster total is ``P x`` each."""

    P: int
    placement: str
    block_bytes: int
    partial_bytes: int
    gather_hops: int
    scatter_hops: int
    gather_bytes: int
    scatter_bytes: int
    allgather_bytes: int
    resident_bytes: int

    @property
    def ppermute_bytes(self) -> int:
        """Total per-device ppermute bytes (gather + scatter)."""
        return self.gather_bytes + self.scatter_bytes

    def as_dict(self) -> Dict[str, int]:
        """The prediction as a plain dict (benchmark JSON output)."""
        return dataclasses.asdict(self)


def predict_sweep_comm(placement, block_bytes: int, *,
                       partial_bytes: Optional[int] = None,
                       P: Optional[int] = None) -> SweepComm:
    """Predict one sweep's per-device comm volume under ``placement``
    (a Placement or spec name; ``P`` required for a name) — the
    analytical side of the DESIGN.md section 14.3 cross-check.

    ``block_bytes`` is one block's payload; ``partial_bytes`` the
    per-slot scatter payload (defaults to ``block_bytes`` — exact for
    emitters whose partials have the block's shape).  A full placement
    predicts zero ppermute hops and the all-gather baseline instead.
    """
    if not isinstance(placement, Placement):
        if P is None:
            raise ValueError("P is required when placement is a spec name")
        placement = resolve_placement(placement, P)
    pb = int(block_bytes) if partial_bytes is None else int(partial_bytes)
    bb = int(block_bytes)
    resident = placement.replication * bb
    if placement.full:
        return SweepComm(
            P=placement.P, placement=placement.name, block_bytes=bb,
            partial_bytes=pb, gather_hops=0, scatter_hops=0,
            gather_bytes=0, scatter_bytes=0,
            allgather_bytes=(placement.P - 1) * bb,
            resident_bytes=resident)
    sched = placement.schedule()
    nz = sum(1 for a in sched.shifts if int(a) % placement.P != 0)
    return SweepComm(
        P=placement.P, placement=placement.name, block_bytes=bb,
        partial_bytes=pb, gather_hops=nz, scatter_hops=nz,
        gather_bytes=nz * bb, scatter_bytes=nz * pb, allgather_bytes=0,
        resident_bytes=resident)


def predict_tree_merge_comm(P: int, payload_bytes: int) -> Dict[str, int]:
    """Per-device comm of the serving recursive-doubling top-k merge:
    one ppermute hop per shift doubling (``ceil(log2 P)`` hops), each
    moving the running candidate payload (DESIGN.md sections 9, 14.3)."""
    hops = 0
    shift = 1
    while shift < P:
        hops += 1
        shift *= 2
    return {"hops": hops, "bytes": hops * int(payload_bytes)}


def predict_ring_gather_comm(P: int, payload_bytes: int) -> Dict[str, int]:
    """Per-device comm of the thresholded-query ppermute ring gather:
    ``P - 1`` single-step hops, each moving the full buffer payload
    (DESIGN.md sections 11.4, 14.3)."""
    return {"hops": max(0, P - 1),
            "bytes": max(0, P - 1) * int(payload_bytes)}


def traced_sweep_comm(tracer) -> Dict[str, int]:
    """The traced per-device comm actuals out of a tracer's counters —
    the empirical side of the DESIGN.md section 14.3 cross-check."""
    return {
        "gather_bytes": int(tracer.counter_total(
            "comm.ppermute.gather_bytes")),
        "scatter_bytes": int(tracer.counter_total(
            "comm.ppermute.scatter_bytes")),
        "gather_hops": int(tracer.counter_total(
            "comm.ppermute.gather_hops")),
        "scatter_hops": int(tracer.counter_total(
            "comm.ppermute.scatter_hops")),
        "allgather_bytes": int(tracer.counter_total("comm.allgather.bytes")),
    }


def verify_dense_comm(P: Optional[int] = None,
                      placements: Optional[Sequence[str]] = None,
                      *, block: int = 4, dim: int = 3,
                      mode: str = "batched", dtype: str = "float32",
                      verbose: bool = True) -> List[Dict[str, int]]:
    """Run one dense sweep per registered placement under a fresh tracer
    and assert the traced ppermute / all-gather bytes equal the
    analytical prediction **exactly** (DESIGN.md section 14.3; the CI
    trace-smoke cross-check, ``python -m repro.obs.comm``).

    Needs ``P`` jax devices (fake-device subprocesses in tests).  The
    toy pair function emits block-shaped partials, so
    ``partial_bytes == block_bytes`` and the default prediction is
    exact.  ``dtype`` parametrizes the block itemsize
    (:func:`block_bytes_of`) — ``bfloat16``/``int8`` stacks must trace
    to proportionally smaller hops.  Returns one traced-actuals dict
    per placement checked.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as PS

    from ..core.allpairs import quorum_allpairs

    devs = jax.devices()
    Pn = P or len(devs)
    if len(devs) < Pn:
        raise RuntimeError(f"need {Pn} devices, have {len(devs)}")
    from ..launch.mesh import make_mesh
    mesh = make_mesh((Pn,), ("q",), devices=devs[:Pn])
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(Pn * block, dim)) * 10).astype(dtype)
    block_bytes = block_bytes_of(block, dim, dtype)

    def pair_fn(bi, bj):
        # out_j(bi, bj) == out_i(bj, bi): the engine's symmetry contract;
        # cast back to the stack dtype (jnp.sum promotes int8 -> int32)
        # so partial_bytes == block_bytes holds at every swept dtype
        return ((bi * jnp.sum(bj * bj)).astype(bi.dtype),
                (bj * jnp.sum(bi * bi)).astype(bj.dtype))

    out: List[Dict[str, int]] = []
    try:
        for plc in supported_placements(Pn):
            if placements is not None and plc.name not in placements:
                continue
            tracer = trace_mod.configure()

            def f(xb):
                return quorum_allpairs(pair_fn, xb, axis_name="q",
                                       mode=mode, placement=plc)

            run = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=PS("q"),
                                        out_specs=PS("q")))
            np.asarray(run(x))  # trace + run once: counters fire per trace
            pred = predict_sweep_comm(plc, block_bytes)
            got = traced_sweep_comm(tracer)
            for field in ("gather_bytes", "scatter_bytes", "gather_hops",
                          "scatter_hops", "allgather_bytes"):
                want = getattr(pred, field) if field != "allgather_bytes" \
                    else pred.allgather_bytes
                assert got[field] == want, (
                    f"{plc.name} P={Pn}: traced {field}={got[field]} != "
                    f"predicted {want}")
            out.append({"placement": plc.name, **got})
            if verbose:
                print(f"  comm {plc.name:10s} P={Pn:<3d} mode={mode}: "
                      f"gather={got['gather_bytes']}B x{got['gather_hops']} "
                      f"scatter={got['scatter_bytes']}B "
                      f"allgather={got['allgather_bytes']}B == predicted")
    finally:
        trace_mod.reset()
    if verbose:
        print(f"comm predictor OK: {len(out)} placement(s) at P={Pn} "
              f"dtype={dtype}, traced == predicted exactly")
    return out


def verify_quant_comm(P: Optional[int] = None,
                      placements: Optional[Sequence[str]] = None,
                      *, block: int = 4, dim: int = 3,
                      qmode: str = "int8",
                      verbose: bool = True) -> List[Dict[str, int]]:
    """Gather one quantized QuantBlocks stack per registered placement
    under a fresh tracer and assert the traced ppermute gather bytes
    equal ``nonzero_shifts * quant_block_bytes`` exactly (DESIGN.md
    sections 14.3, 17.1) — the quantized twin of
    :func:`verify_dense_comm`, pinning the side arrays' payload
    accounting to the predictor formula.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as PS

    from ..core import sweep as sweep_mod
    from ..core.quant import QuantBlocks, quantize_corpus

    devs = jax.devices()
    Pn = P or len(devs)
    if len(devs) < Pn:
        raise RuntimeError(f"need {Pn} devices, have {len(devs)}")
    from ..launch.mesh import make_mesh
    mesh = make_mesh((Pn,), ("q",), devices=devs[:Pn])
    rng = np.random.default_rng(0)
    x = rng.normal(size=(Pn * block, dim)).astype(np.float32)
    qc = quantize_corpus(x, Pn, block, qmode)
    leaves = qc.device_arrays()
    payload = quant_block_bytes(block, dim, qmode)

    out: List[Dict[str, int]] = []
    try:
        for plc in supported_placements(Pn):
            if placements is not None and plc.name not in placements:
                continue
            sched = plc.schedule()
            tracer = trace_mod.configure()

            def f(q, s, d_, l1, sq):
                qb = QuantBlocks(q=q, scale=s, delta=d_, l1=l1, sq=sq)
                g = sweep_mod.quorum_gather(qb, sched, "q")
                return g.q

            spec = PS("q")
            run = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(spec,) * 5,
                                        out_specs=spec))
            np.asarray(run(*leaves))
            got = traced_sweep_comm(tracer)
            nz = sum(1 for a in sched.shifts if int(a) % plc.P != 0)
            want = nz * payload
            assert got["gather_bytes"] == want, (
                f"{plc.name} P={Pn} quant={qmode}: traced gather_bytes="
                f"{got['gather_bytes']} != predicted {want}")
            assert got["gather_hops"] == nz, (
                f"{plc.name} P={Pn} quant={qmode}: traced gather_hops="
                f"{got['gather_hops']} != {nz}")
            out.append({"placement": plc.name, "qmode": qmode, **got})
            if verbose:
                print(f"  quant comm {plc.name:10s} P={Pn:<3d} "
                      f"quant={qmode}: gather={got['gather_bytes']}B "
                      f"x{got['gather_hops']} == predicted")
    finally:
        trace_mod.reset()
    if verbose:
        print(f"quant comm predictor OK: {len(out)} placement(s) at "
              f"P={Pn} quant={qmode}, traced == predicted exactly")
    return out


def _main(argv=None) -> int:
    """CLI: ``python -m repro.obs.comm [--P N] [--placements ...]
    [--mode batched] [--dtype float32] [--quant int8]`` — the
    predictor-vs-traced equality check (DESIGN.md section 14.3); with
    ``--quant`` it also pins the quantized-stack gather payload
    (DESIGN.md section 17.1)."""
    import argparse
    ap = argparse.ArgumentParser(
        description="assert traced ppermute bytes == analytical "
                    "prediction for every registered placement")
    ap.add_argument("--P", type=int, default=None)
    ap.add_argument("--placements", nargs="*", default=None)
    ap.add_argument("--mode", default="batched",
                    choices=["batched", "overlap", "scan"])
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16", "int8"])
    ap.add_argument("--quant", default=None, choices=["int8", "bf16"])
    args = ap.parse_args(argv)
    verify_dense_comm(args.P, args.placements, mode=args.mode,
                      dtype=args.dtype)
    if args.quant is not None:
        verify_quant_comm(args.P, args.placements, qmode=args.quant)
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(_main())
