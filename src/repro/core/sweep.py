"""The unified pair-sweep runtime: one engine core under every workload.

The paper's contribution is a *single* distribution scheme — cyclic
quorums with O(N/sqrt(P)) residency — and all four workloads the repo
ships (dense all-pairs reduction, thresholded similarity join, online
top-k / range-query serving, all-pairs k-NN graphs) run the *same*
schedule → gather → pair-compute → emit loop over it.  This module owns
that loop once (DESIGN.md section 12):

  * **data plane** — :func:`quorum_gather` pulls the k resident blocks
    with k-1 ``lax.ppermute`` cyclic shifts; :func:`quorum_scatter`
    routes per-slot partials back to block owners with the inverse
    shifts and folds them under a caller-chosen monoid (sum for dense
    reductions, a top-k merge for k-NN — partials may be arbitrary
    pytrees).
  * **execution modes** — ``batched`` (one vectorized step over every
    work item), ``overlap`` (each item computes as soon as its later
    block lands, so XLA's latency-hiding scheduler overlaps the
    remaining shifts), ``scan`` (serial ``lax.scan``, the low-memory
    oracle); :func:`select_mode` is the single ``mode="auto"``
    heuristic, :func:`validate_mode` the single argument contract.
  * **work items** — by default the schedule's per-difference slot
    pairs; an emitter may substitute a per-slot sweep (``lo == hi ==
    arange(k)``), which is how the serving engines ride the same driver
    over a *resident* stack instead of a gathered one.
  * **emitter protocol** — :class:`SweepEmitter` is the plug-in seam: a
    workload supplies the per-item compute and the carry it folds into
    (a monoid accumulator, a fixed-capacity compaction buffer, a
    per-row top-k list), and :func:`pair_sweep` runs it under any mode.
    Adding a workload is one emitter + one thin adapter (core/knn.py is
    the worked example), not a fork of the loop.

The shared top-k selection helpers (:func:`topk_by_score`,
:func:`merge_topk`) live here because two emitter families (serving
query, k-NN graph) select by the same (-score, index) total order.
``core.allpairs`` re-exports the long-standing public names so existing
imports keep working; outputs of the ported engines are bit-exact with
the pre-runtime implementations (the tier-1 suite is the oracle).
"""

from __future__ import annotations

import abc
from typing import Any, Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..kernels.ref import IDX_SENTINEL, NEG_INF
from ..obs import trace as obs_trace
from . import env as env_mod
from .scheduler import PairSchedule

__all__ = [
    "ENGINE_MODES",
    "SweepEmitter",
    "pair_sweep",
    "slot_items",
    "ready_order",
    "pair_ready_order",
    "sweep_rounds",
    "quorum_gather",
    "quorum_scatter",
    "pair_mask_table",
    "mark_varying",
    "auto_batch_bytes",
    "env_mode_override",
    "validate_mode",
    "select_mode",
    "resolve_sweep_placement",
    "topk_by_score",
    "merge_topk",
]

ENGINE_MODES = ("batched", "overlap", "scan")

# auto-mode switches away from `batched` when the workload's working set
# would exceed this budget (bytes; overridable for small-VMEM or huge-HBM
# parts via REPRO_BATCH_BYTES_LIMIT)
_DEFAULT_BATCH_BYTES = 1 << 28


def auto_batch_bytes() -> int:
    """The auto-mode byte budget (DESIGN.md section 4), read from
    ``REPRO_BATCH_BYTES_LIMIT`` at *selection* time (every ``mode="auto"``
    trace), not at import — setting the env var after ``import repro``
    works.  Shared by every engine heuristic through
    :func:`select_mode`."""
    val = env_mod.read_knob("REPRO_BATCH_BYTES_LIMIT")
    return _DEFAULT_BATCH_BYTES if val is None else int(val)


def env_mode_override() -> str | None:
    """The validated ``REPRO_ALLPAIRS_MODE`` forced mode, or None if unset
    (DESIGN.md section 4).

    The benchmark / CI A/B hook, consulted by every ``mode="auto"``
    selection (engine, PCIT tile phases, serving scoring, sparse join,
    k-NN).  Read at trace time — set it before the first jitted call;
    already-compiled auto-mode programs keep their baked-in choice.
    Unknown values raise rather than silently falling through to the
    heuristic (core/env.py is the registry).
    """
    return env_mod.read_knob("REPRO_ALLPAIRS_MODE")


def validate_mode(mode: str, batch_fn) -> None:
    """The shared mode/kernel argument contract (DESIGN.md section 12.1):
    ``mode`` must be an engine mode or ``auto``, and a fused ``batch_fn``
    only replaces the batched inner step."""
    if mode not in ENGINE_MODES + ("auto",):
        raise ValueError(f"mode must be one of {ENGINE_MODES + ('auto',)}, "
                         f"got {mode!r}")
    if batch_fn is not None and mode not in ("batched", "auto"):
        raise ValueError(
            f"batch_fn only replaces the batched inner step (got "
            f"mode={mode!r}); drop it or use mode='batched'")


def select_mode(schedule: PairSchedule, working_set_bytes: int,
                batch_fn) -> str:
    """The single ``mode="auto"`` heuristic (DESIGN.md sections 4, 12.1).

    Environment override first (:func:`env_mode_override`; conflicts with
    a fused ``batch_fn`` — which only exists for the batched step — raise
    instead of silently dropping the kernel), then: a fused batch kernel
    always means ``batched``; otherwise ``batched`` while the workload's
    ``working_set_bytes`` fits the :func:`auto_batch_bytes` budget,
    ``overlap`` when there are enough shifts to hide (k >= 3), ``scan``
    as the low-memory last resort.  Each engine supplies its own
    working-set formula; the policy lives only here.
    """
    env = env_mode_override()
    if env is not None:
        if batch_fn is not None and env != "batched":
            raise ValueError(
                f"REPRO_ALLPAIRS_MODE={env} conflicts with a fused batch_fn "
                "(the kernel only replaces the batched inner step)")
        return env
    if batch_fn is not None:
        return "batched"
    if working_set_bytes <= auto_batch_bytes():
        return "batched"
    if schedule.k >= 3:
        return "overlap"
    return "scan"


def resolve_sweep_placement(schedule, axis_size, placement):
    """The shared placement-threading step of every engine entry point
    (DESIGN.md sections 10, 12.1).

    Validates P-consistency between ``schedule`` / ``axis_size`` /
    ``placement``; when both schedule and placement are None, consults
    ``REPRO_PLACEMENT`` at ``axis_size``.  Returns ``(schedule,
    placement)`` — schedule may still be None (callers that special-case
    e.g. full replication derive it afterwards via
    ``placement.schedule()``).
    """
    if placement is not None:
        if axis_size is not None and placement.P != axis_size:
            raise ValueError(
                f"placement is for P={placement.P} but axis_size={axis_size}")
        if schedule is not None and schedule.P != placement.P:
            raise ValueError(
                f"placement is for P={placement.P} but schedule.P="
                f"{schedule.P}")
    if placement is None and schedule is None:
        assert axis_size is not None, "need schedule, placement, or axis_size"
        from .placement import placement_from_env
        placement = placement_from_env(axis_size)
    return schedule, placement


# ---------------------------------------------------------------------------
# Data plane: cyclic-shift gather / scatter, masks (DESIGN.md section 2)
# ---------------------------------------------------------------------------

def _shift_perm(P: int, shift: int) -> list[tuple[int, int]]:
    """ppermute permutation delivering block (i + shift) % P to device i."""
    return [(j, (j - shift) % P) for j in range(P)]


def _tree_nbytes(tree) -> int:
    """Static payload bytes of a pytree (every leaf's size x itemsize —
    exact during a jit trace, where shapes are static)."""
    return sum(obs_trace.nbytes_of(leaf) for leaf in jax.tree.leaves(tree))


def quorum_gather(x, schedule: PairSchedule, axis_name: str,
                  *, overlap_fn: Callable[[int, Any], Any] | None = None):
    """Gather this device's quorum blocks (DESIGN.md section 2, phase 1).

    Args:
      x: the local block, shape [block, ...] (inside shard_map), or an
        arbitrary pytree of per-block arrays — every leaf rides the same
        cyclic shifts, which is how the quantized corpus threads its
        per-block scale/norm side arrays through the data plane
        (core/quant.py, DESIGN.md section 17).
      schedule: PairSchedule for the quorum axis size P.
      axis_name: mesh axis the blocks are sharded over.
      overlap_fn: optional ``f(slot, block)`` called as each block lands —
        lets callers overlap compute with the next in-flight permute (the
        double-buffered mode; XLA's latency-hiding scheduler interleaves the
        independent ppermutes and per-slot compute).

    Returns:
      stacked quorum blocks [k, block, ...] (pytree x: each leaf gains the
      leading slot axis); slot s holds global block (i + shifts[s]) % P.
      If overlap_fn is given, returns the list of its results instead.
    """
    P = schedule.P
    shifts = [int(s) for s in schedule.shifts]
    # comm accounting fires at jit-trace time: shapes are static, so the
    # counted bytes are exact, once per compiled program (DESIGN.md 14.2);
    # _tree_nbytes degenerates to nbytes_of for a plain array
    tr = obs_trace.get_tracer()
    if tr:
        nz = sum(1 for a in shifts if a % P != 0)
        tr.count("comm.ppermute.gather_hops", nz)
        tr.count("comm.ppermute.gather_bytes", nz * _tree_nbytes(x))
    with jax.named_scope("sweep.gather"):
        blocks = []
        results = []
        for slot, a in enumerate(shifts):
            blk = x if a == 0 else jax.tree.map(
                lambda leaf, a=a: lax.ppermute(leaf, axis_name,
                                               _shift_perm(P, a)), x)
            if overlap_fn is not None:
                results.append(overlap_fn(slot, blk))
            else:
                blocks.append(blk)
        if overlap_fn is not None:
            return results
        return jax.tree.map(lambda *leaves: jnp.stack(leaves, axis=0),
                            *blocks)


def quorum_scatter(partials, schedule: PairSchedule, axis_name: str,
                   *, reduce_fn: Callable[[Any, Any], Any] = jnp.add):
    """Route per-slot partial results back to block owners and reduce
    (DESIGN.md section 2, phase 3).

    partials: [k, block, ...] stacked, or a length-k sequence of per-slot
    partials; slot s is a partial result for global block
    (i + shifts[s]) % P.  Each per-slot partial may be an arbitrary
    pytree (every leaf is ppermuted with the inverse shift) — the k-NN
    emitter scatters (values, indices) pairs this way.  Arrivals fold
    with ``reduce_fn`` (default elementwise sum; pass a top-k merge or
    any other monoid for non-additive reductions, DESIGN.md section
    12.2).  The per-slot sequence form is what the overlap engine mode
    produces: each slot's inverse shift depends only on that slot's pair
    results, so the scheduler can start early slots' sends while later
    pairs are still computing (the pipelined scatter).
    Returns the reduced per-block result for the local block.
    """
    P = schedule.P
    shifts = [int(s) for s in schedule.shifts]
    tr = obs_trace.get_tracer()
    with jax.named_scope("sweep.scatter"):
        acc = None
        for slot, a in enumerate(shifts):
            part = partials[slot]
            if a == 0:
                arrived = part
            else:
                if tr:  # exact: per-slot pytree leaf bytes, counted at
                    # jit-trace time (DESIGN.md 14.2)
                    tr.count("comm.ppermute.scatter_hops")
                    tr.count("comm.ppermute.scatter_bytes",
                             _tree_nbytes(part))
                arrived = jax.tree.map(
                    lambda leaf: lax.ppermute(leaf, axis_name,
                                              _shift_perm(P, -a)), part)
            acc = arrived if acc is None else reduce_fn(acc, arrived)
        return acc


def pair_mask_table(schedule: PairSchedule) -> np.ndarray:
    """[P, n_pairs] float mask deduplicating the d = P/2 orbit for even P
    (DESIGN.md section 3.2).

    Each unordered pair with difference P/2 is generated by exactly two
    devices (i and i + P/2); the device with the smaller canonical lower
    endpoint keeps it.  All other entries are 1.  The mask rides into
    shard_map as a sharded operand, so control flow stays uniform.
    """
    P, n = schedule.P, schedule.n_pairs
    mask = np.ones((P, n), dtype=np.float32)
    if P % 2 == 0 and P > 1:
        d_half = P // 2
        idx = np.nonzero(schedule.pair_diff == d_half)[0]
        if idx.size:
            s = int(idx[0])
            a_lo = int(schedule.shifts[schedule.pair_slots[s, 0]])
            for i in range(P):
                lo = (i + a_lo) % P
                hi = (lo + d_half) % P
                # keeper: the generating device whose lower endpoint is the
                # canonical (smaller) block id of the orbit
                mask[i, s] = 1.0 if lo == min(lo, hi) else 0.0
    return mask


def mark_varying(x: jax.Array, axis_name: str) -> jax.Array:
    """Mark x as varying over the quorum axis (shard_map's varying-axis
    typing; the plumbing every engine-internal constant goes through —
    DESIGN.md section 2)."""
    return lax.pcast(x, axis_name, to="varying")


# ---------------------------------------------------------------------------
# Work items (DESIGN.md section 12.1)
# ---------------------------------------------------------------------------

def ready_order(lo: Sequence[int], hi: Sequence[int],
                k: int) -> List[List[int]]:
    """Work items grouped by *ready slot* for the overlap mode
    (DESIGN.md sections 4, 12.1).

    An item referencing slots (lo, hi) can compute once its later block
    lands in the gather shift sequence, i.e. at slot max(lo, hi);
    ready[s] lists the items that become computable when slot s arrives.
    """
    out: List[List[int]] = [[] for _ in range(k)]
    for idx in range(len(lo)):
        out[max(int(lo[idx]), int(hi[idx]))].append(idx)
    return out


def pair_ready_order(schedule: PairSchedule) -> list[list[int]]:
    """Pair indices grouped by ready slot for the schedule's slot pairs
    (:func:`ready_order` over ``schedule.pair_slots``; DESIGN.md
    section 4)."""
    return ready_order(schedule.pair_slots[:, 0], schedule.pair_slots[:, 1],
                       schedule.k)


def sweep_rounds(schedule: PairSchedule, mode: str) -> List[List[int]]:
    """Pair indices grouped into the mode's synchronization rounds — the
    boundaries where a fault-tolerant driver may observe failures and
    checkpoint partials (DESIGN.md section 13).

    Mirrors each engine mode's real synchronization structure: ``batched``
    materializes every pair in one fused step (a single round), ``overlap``
    synchronizes once per gather shift as blocks land (the non-empty
    :func:`pair_ready_order` groups), and ``scan`` carries state through
    one pair per step (one round per pair).  Round lists concatenate to
    ``range(schedule.n_pairs)`` reordered — every pair appears exactly
    once, so replaying rounds in order folds partials in a
    mode-independent canonical pair order.
    """
    if mode not in ENGINE_MODES:
        raise ValueError(f"mode must be one of {ENGINE_MODES}, got {mode!r}")
    n = schedule.n_pairs
    if mode == "batched":
        return [list(range(n))] if n else []
    if mode == "scan":
        return [[i] for i in range(n)]
    return [grp for grp in pair_ready_order(schedule) if grp]


def slot_items(k: int) -> Tuple[np.ndarray, np.ndarray]:
    """The per-slot work-item list (``lo == hi == arange(k)``) used by
    emitters that sweep a resident stack slot-by-slot instead of the
    schedule's slot pairs — the serving query engines (DESIGN.md
    section 12.2)."""
    slots = np.arange(k, dtype=np.int32)
    return slots, slots


# ---------------------------------------------------------------------------
# Emitter protocol + driver (DESIGN.md section 12.1)
# ---------------------------------------------------------------------------

class SweepEmitter(abc.ABC):
    """The workload plug-in seam of the pair-sweep runtime (DESIGN.md
    section 12.1).

    An emitter owns the *per-item compute* and the *carry* it folds item
    results into; :func:`pair_sweep` owns mode dispatch and the data
    plane.  One emitter instance is built per trace (its fields may hold
    traced arrays).  Contract, per mode:

      * ``batched``  — :meth:`prepare` (optional, sees the gathered
        stack), then :meth:`batch` computes every item in one vectorized
        step (routing through ``self.batch_fn`` when a fused kernel is
        attached).
      * ``scan``     — :meth:`prepare`, then ``lax.scan`` of
        :meth:`scan_emit` over :meth:`scan_items` starting from
        :meth:`scan_init`, then :meth:`scan_finalize`.
      * ``overlap``  — :meth:`overlap_begin` builds a host-side state
        object; :meth:`overlap_slot` observes each block as it lands;
        :meth:`overlap_emit` runs each item at its ready slot (items and
        slot indices are *static* here — the loop is unrolled);
        :meth:`overlap_finalize` folds the state into the output.

    All three modes must produce index-identical results (scores to
    float tolerance) — the workload selfchecks assert it.
    """

    #: optional fused-kernel hook replacing the batched inner step
    #: (forces ``batched`` under ``mode="auto"``; see :func:`select_mode`)
    batch_fn = None

    def items(self) -> Tuple[np.ndarray, np.ndarray]:
        """(lo, hi) slot indices of each work item — default: the
        schedule's per-difference slot pairs (DESIGN.md section 3.2);
        slot-sweep emitters override with :func:`slot_items`."""
        return (self.schedule.pair_slots[:, 0],
                self.schedule.pair_slots[:, 1])

    def prepare(self, quorum: jax.Array) -> None:
        """Optional hook run after the gather in batched/scan modes —
        e.g. the sparse engine computes its norm-bound prefilter over the
        full stack here (DESIGN.md section 11.1)."""

    @abc.abstractmethod
    def batch(self, quorum: jax.Array):
        """Compute every work item in one vectorized step over the
        gathered [k, block, ...] stack; returns the sweep output."""

    @abc.abstractmethod
    def scan_init(self):
        """The (varying-marked) carry the serial scan starts from."""

    @abc.abstractmethod
    def scan_items(self):
        """Per-item traced arrays ``lax.scan`` iterates over."""

    @abc.abstractmethod
    def scan_emit(self, carry, quorum: jax.Array, item):
        """Fold one work item into the scan carry."""

    def scan_finalize(self, carry):
        """Turn the final scan carry into the sweep output (default:
        the carry itself)."""
        return carry

    @abc.abstractmethod
    def overlap_begin(self):
        """Build the host-side state object the unrolled overlap sweep
        mutates (lists of per-slot contributions, a boxed carry, ...)."""

    def overlap_slot(self, state, slot: int, blk: jax.Array) -> None:
        """Optional hook observing each block as it lands (e.g. per-slot
        norm extrema for the incremental prefilter)."""

    @abc.abstractmethod
    def overlap_emit(self, state, idx: int, bi: jax.Array,
                     bj: jax.Array) -> None:
        """Run work item ``idx`` (static int) on its two landed blocks,
        folding the result into ``state``."""

    @abc.abstractmethod
    def overlap_finalize(self, state):
        """Fold the overlap state into the sweep output."""

    # -- delta maintenance (DESIGN.md section 16) -------------------------
    # Host-side monoid patch rules over *standing* outputs, consumed by
    # core/delta.py's DeltaIndex: retract a dirty tile's stale
    # contribution, fold its fresh one.  Static numpy functions — they
    # act on folded host results, not traced arrays — so any driver can
    # call them without constructing a traced emitter.

    @staticmethod
    def delta_retract(standing, stale, ctx=None):
        """Remove a stale contribution from a standing output (the
        delta-sweep retract hook, DESIGN.md section 16).  Emitters with
        an invertible (or patchable) output monoid override this; the
        base protocol does not support delta maintenance."""
        raise NotImplementedError(
            "this emitter does not support delta maintenance "
            "(no delta_retract rule; see DESIGN.md section 16)")

    @staticmethod
    def delta_fold(standing, fresh, ctx=None):
        """Fold a fresh contribution into a standing output (the
        delta-sweep fold hook, DESIGN.md section 16).  Emitters with a
        delta-maintainable output monoid override this; the base
        protocol does not support delta maintenance."""
        raise NotImplementedError(
            "this emitter does not support delta maintenance "
            "(no delta_fold rule; see DESIGN.md section 16)")


def pair_sweep(emitter: SweepEmitter, *, schedule: PairSchedule,
               axis_name: str, mode: str, x: jax.Array | None = None,
               stack: jax.Array | None = None):
    """Run one emitter over the schedule under a resolved execution mode
    (DESIGN.md section 12.1) — the single home of the schedule → gather
    → pair-compute → emit loop.

    Exactly one of ``x`` (the local block: the stack is gathered with
    the schedule's ppermute shifts) or ``stack`` (an already-resident
    [k, block, ...] stack, the serving path) must be given.  ``mode``
    must be a concrete engine mode — resolve ``auto`` first with
    :func:`select_mode` (each adapter supplies its working-set bytes).
    Returns whatever the emitter's finalize step produces.
    """
    assert (x is None) != (stack is None), "need exactly one of x / stack"
    assert mode in ENGINE_MODES, mode
    tr = obs_trace.get_tracer()
    if tr:  # exact at jit-trace time: the schedule is static
        tr.count("sweep.pair_tiles", int(len(emitter.items()[0])))
    # device ops of the pair math carry the sweep.pair_compute scope (in
    # the overlap mode it nests under sweep.gather, which interleaves it)
    if mode == "overlap":
        lo, hi = emitter.items()
        ready = ready_order(lo, hi, schedule.k)
        state = emitter.overlap_begin()
        landed: list = []

        def on_land(slot: int, blk: jax.Array) -> None:
            landed.append(blk)
            with jax.named_scope("sweep.pair_compute"):
                emitter.overlap_slot(state, slot, blk)
                for idx in ready[slot]:
                    emitter.overlap_emit(state, idx,
                                         landed[int(lo[idx])],
                                         landed[int(hi[idx])])

        if stack is None:
            quorum_gather(x, schedule, axis_name, overlap_fn=on_land)
        else:
            for slot in range(schedule.k):
                on_land(slot, jax.tree.map(lambda l: l[slot], stack))
        with jax.named_scope("sweep.pair_compute"):
            return emitter.overlap_finalize(state)

    quorum = stack if stack is not None else quorum_gather(x, schedule,
                                                           axis_name)
    with jax.named_scope("sweep.pair_compute"):
        emitter.prepare(quorum)
        if mode == "batched":
            return emitter.batch(quorum)

        def body(carry, item):
            return emitter.scan_emit(carry, quorum, item), None

        carry, _ = lax.scan(body, emitter.scan_init(), emitter.scan_items())
        return emitter.scan_finalize(carry)


# ---------------------------------------------------------------------------
# Shared top-k selection monoid (DESIGN.md sections 9.2, 12.2)
# ---------------------------------------------------------------------------

def topk_by_score(vals: jax.Array, idx: jax.Array, topk: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """Top-k along the last axis by the (-score, index) total order
    (DESIGN.md section 9.2).

    Pads with (NEG_INF, IDX_SENTINEL) when fewer than ``topk`` candidates.
    """
    n = vals.shape[-1]
    if n < topk:
        pad = [(0, 0)] * (vals.ndim - 1) + [(0, topk - n)]
        vals = jnp.pad(vals, pad, constant_values=NEG_INF)
        idx = jnp.pad(idx, pad, constant_values=IDX_SENTINEL)
    sv, si = lax.sort((-vals, idx.astype(jnp.int32)), num_keys=2)
    return -sv[..., :topk], si[..., :topk]


def merge_topk(va, ia, vb, ib, topk: int) -> Tuple[jax.Array, jax.Array]:
    """Merge two candidate lists, deduplicating repeated corpus indices
    (DESIGN.md section 9.2).

    Duplicates only arise from merge windows that overlap (the serving
    tree merge's wraparound; every sweep emitter *scores* each candidate
    once), so copies carry identical scores and land adjacent under the
    two-key sort — the second copy is demoted to a sentinel and a
    re-sort restores order.  Selection by a strict total order makes
    this merge associative and commutative: it is the monoid the k-NN
    scatter reduces under (DESIGN.md section 12.2).
    """
    vals = jnp.concatenate([va, vb], axis=-1)
    idx = jnp.concatenate([ia, ib], axis=-1).astype(jnp.int32)
    sv, si = lax.sort((-vals, idx), num_keys=2)
    dup = jnp.concatenate(
        [jnp.zeros_like(si[..., :1], bool),
         (si[..., 1:] == si[..., :-1]) & (sv[..., 1:] == sv[..., :-1])],
        axis=-1)
    sv = jnp.where(dup, -NEG_INF, sv)          # sv holds negated scores
    si = jnp.where(dup, IDX_SENTINEL, si)
    sv, si = lax.sort((sv, si), num_keys=2)
    return -sv[..., :topk], si[..., :topk]
