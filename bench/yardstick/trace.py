"""Reduction of a profiler trace to device busy time, idle share, kernel
time and collective time.

The trace is JAX's ``.xplane.pb`` (read with ``jax.profiler.ProfileData``).
Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation that ran on the device.  Host planes hold the
spans of the host's threads, among them the benchmark's own
``TraceAnnotation`` spans (``bench.window`` marks the measured window).
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Callable, Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(
    r"^(collective-permute|all-gather|all-reduce|all-to-all|reduce-scatter"
    r"|collective-broadcast)(-start|-done)?$")

Event = Tuple[str, float, float]          # (name, start ns, duration ns)
# a device event's name is the HLO instruction's text: "%name = shape op(...)"
HLO = re.compile(r"^%?(\S+) = .*?\b([a-z][a-z0-9\-]*)\(")


def op_kind(name: str) -> str:
    """The HLO op of an instruction's text (``custom-call``), or ''."""
    m = HLO.match(name)
    return m.group(2) if m else ""


def short_name(name: str) -> str:
    """``name.N op`` of an HLO instruction's text (``body.1 custom-call``),
    or the name as it is."""
    m = HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


def union_ns(events: Iterable[Event], lo: float = float("-inf"),
             hi: float = float("inf")) -> float:
    """Length of the union of the events' intervals, clipped to [lo, hi]."""
    spans = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events)
    total, end = 0.0, float("-inf")
    for s, e in spans:
        if e <= s:
            continue
        if s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def busy_intervals(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """The merged [start, end) intervals in which some event runs."""
    out: List[List[float]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        e = s + d
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class TraceView:
    """Device operations per device plane, and host spans, of one trace.

    ``devices``: {plane name: [(op name, start ns, duration ns)]};
    ``host``: [(thread name, span name, start ns, duration ns)].
    """

    def __init__(self, devices: Dict[str, List[Event]],
                 host: List[Tuple[str, str, float, float]]):
        self.devices = dict(sorted(devices.items()))
        self.host = host

    # ------------------------------------------------------------ window
    def window_ns(self) -> Tuple[float, float]:
        """[start, end) of the benchmark's ``bench.window`` span; without
        it, of all device operations."""
        spans = [(s, s + d) for _, n, s, d in self.host if n == WINDOW_SPAN]
        if spans:
            return min(s for s, _ in spans), max(e for _, e in spans)
        evs = [e for evs in self.devices.values() for e in evs]
        if not evs:
            return 0.0, 0.0
        return min(s for _, s, _ in evs), max(s + d for _, s, d in evs)

    # ------------------------------------------------------------ device
    def busy_s(self) -> float:
        """Seconds in the window in which some operation ran, averaged
        over the device planes."""
        if not self.devices:
            return 0.0
        lo, hi = self.window_ns()
        return sum(union_ns(evs, lo, hi) for evs in self.devices.values()) \
            / len(self.devices) / 1e9

    def op_events(self, match: Callable[[str], bool]) -> Dict[str, List[Event]]:
        """Per device plane, the operations inside the window whose name
        ``match`` accepts."""
        lo, hi = self.window_ns()
        return {p: [e for e in evs if match(e[0]) and lo <= e[1] < hi]
                for p, evs in self.devices.items()}

    def op_seconds(self, match: Callable[[str], bool]) -> Tuple[float, int]:
        """(summed device seconds over all planes, event count) of the
        matching operations."""
        per = self.op_events(match)
        return (sum(d for evs in per.values() for _, _, d in evs) / 1e9,
                sum(len(evs) for evs in per.values()))

    def collective_seconds(self) -> Tuple[float, int]:
        """Device seconds of collective operations (their HLO op kind, the
        ``-start`` and ``-done`` halves of an async one both counted)
        summed over planes."""
        return self.op_seconds(lambda n: bool(COLLECTIVE.match(op_kind(n))))

    # --------------------------------------------------------- breakdown
    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` operations (by :func:`short_name`) with most device
        time inside the window, in seconds averaged over the device
        planes."""
        lo, hi = self.window_ns()
        tot: Dict[str, float] = defaultdict(float)
        for evs in self.devices.values():
            for name, s, d in evs:
                if lo <= s < hi:
                    tot[short_name(name)] += d
        k = max(1, len(self.devices))
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, t / k / 1e9] for name, t in top]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The ``n`` longest gaps inside the window in which the first
        device ran nothing, each named by the shortest host span that
        covers the gap's middle (what the host was doing), in seconds."""
        if not self.devices:
            return []
        lo, hi = self.window_ns()
        evs = next(iter(self.devices.values()))
        busy = busy_intervals(e for e in evs if lo <= e[1] < hi)
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            out.append([self.host_activity((s + e) / 2), (e - s) / 1e9])
        return out

    def host_activity(self, t: float) -> str:
        """Name of the shortest host span (other than the window itself)
        that covers time ``t``, or ``no host span``; where that is one of the
        benchmark's own spans (``bench.*``), also the host span that ended
        last before ``t`` (``bench.knn_graph after np.asarray(...)``)."""
        best: Optional[Tuple[float, str]] = None
        last: Optional[Tuple[float, str]] = None
        for thread, name, s, d in self.host:
            if name == WINDOW_SPAN:
                continue
            if s <= t < s + d and (best is None or d < best[0]):
                best = (d, name)
            if s + d <= t and not name.startswith("bench.") \
                    and (last is None or s + d > last[0]):
                last = (s + d, name)
        label = best[1] if best else "no host span"
        if label.startswith("bench.") and last is not None:
            label += f" after {last[1]}"
        return label


def from_profile(pd) -> TraceView:
    """A :class:`TraceView` of a ``jax.profiler.ProfileData``."""
    devices: Dict[str, List[Event]] = {}
    host: List[Tuple[str, str, float, float]] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops: List[Event] = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.duration_ns)
                               for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((line.name, e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.duration_ns > 0)
    return TraceView(devices, host)


def load(path) -> TraceView:
    """A :class:`TraceView` of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(str(path)))


def load_text(text: str) -> TraceView:
    """A :class:`TraceView` of an XSpace in protobuf text format."""
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_text_proto(text))
