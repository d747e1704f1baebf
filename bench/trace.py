"""Profiler trace -> device intervals, kernel events by name, idle gaps and
the benchmark's own host spans.

The JAX profiler writes `<dir>/plugins/profile/<time>/*.xplane.pb`;
`jax.profiler.ProfileData` reads it.  Device operations are the events of
the `XLA Ops` line of every `/device:` plane.  Host spans are the
`TraceAnnotation` events the benchmark records around its own calls; the
span named `bench.window` marks the measured window on the same clock.

Everything below `reduce` is plain interval arithmetic on
(start_s, end_s) pairs, so it is checked without a chip.
"""
from __future__ import annotations

import dataclasses
import glob
import os

WINDOW_SPAN = "bench.window"
DEVICE_LINE = "XLA Ops"


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted, disjoint cover of the given intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_seconds(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(clip(intervals, lo, hi)))


def idle_gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The gaps of [lo, hi] that no interval covers, longest first."""
    gaps, t = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def host_label(spans: dict, t: float) -> str:
    """Name of the innermost host span covering time t ("none" if none)."""
    best, width = "none", float("inf")
    for name, ivs in spans.items():
        if name == WINDOW_SPAN:
            continue
        for s, e in ivs:
            if s <= t <= e and e - s < width:
                best, width = name, e - s
    return best


def short_name(hlo_text: str) -> str:
    """'%fusion.12 = f32[...] fusion(...)' -> 'fusion.12'."""
    head = hlo_text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def leaves(events: list) -> list:
    """The events that contain no other event: a loop or call operation
    spans the operations it runs, which the trace lists as well."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][2], -events[i][3]))
    parent = [False] * len(events)
    stack: list[int] = []
    for i in order:
        s, e = events[i][2], events[i][3]
        while stack and events[stack[-1]][3] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][3]:
            parent[stack[-1]] = True
        stack.append(i)
    return [ev for ev, p in zip(events, parent) if not p]


@dataclasses.dataclass
class Reduced:
    """One traced window, reduced.  Times in seconds on the trace clock."""

    window: tuple[float, float]
    ops: dict            # device plane -> [(name, label, start, end)]
    spans: dict          # host span name -> [(start, end)]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def device_intervals(self, plane: str) -> list[tuple[float, float]]:
        return [(s, e) for _, _, s, e in self.ops[plane]]

    @property
    def busy_s(self) -> float:
        """Seconds some operation ran, averaged over the device planes."""
        if not self.ops:
            return 0.0
        return sum(busy_seconds(self.device_intervals(p), *self.window)
                   for p in self.ops) / len(self.ops)

    def kernel(self, name: str) -> tuple[float, int]:
        """(device seconds in the window, event count) of the innermost
        operations named `name` (the trace names a Pallas kernel's
        operation `<name>.<k>`), averaged over the planes."""
        if not self.ops:
            return 0.0, 0
        secs, count = 0.0, 0
        for evs in self.ops.values():
            for n, _, s, e in leaves(evs):
                if n == name or n.startswith(name + "."):
                    for cs, ce in clip([(s, e)], *self.window):
                        secs += ce - cs
                        count += 1
        return secs / len(self.ops), count // len(self.ops)

    def top_ops(self, k: int = 10) -> list[list]:
        tot: dict[str, float] = {}
        for evs in self.ops.values():
            for n, _, s, e in leaves(evs):
                for cs, ce in clip([(s, e)], *self.window):
                    tot[n] = tot.get(n, 0.0) + (ce - cs) / len(self.ops)
        return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])
                [:k]]

    def top_gaps(self, k: int = 10) -> list[list]:
        """Longest idle gaps of the first device, by the host span that
        covers each gap's middle."""
        if not self.ops:
            return []
        plane = sorted(self.ops)[0]
        gaps = idle_gaps(self.device_intervals(plane), *self.window)[:k]
        return [[host_label(self.spans, 0.5 * (s + e)), e - s]
                for s, e in gaps]


def latest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _label(event) -> str:
    parts = []
    for stat in event.stats:
        val = stat[1] if len(stat) > 1 else None
        if isinstance(val, str):
            parts.append(val)
    return " ".join(parts)


def reduce(trace_dir: str, span_names) -> Reduced:
    """Read the newest trace under `trace_dir`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(latest_xplane(trace_dir))
    ops: dict = {}
    spans: dict = {n: [] for n in span_names}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != DEVICE_LINE:
                    continue
                ops.setdefault(plane.name, []).extend(
                    (short_name(ev.name), ev.name + " " + _label(ev),
                     ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        spans[ev.name].append(
                            (ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9))
    if not spans.get(WINDOW_SPAN):
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    return Reduced(window=spans[WINDOW_SPAN][0], ops=ops, spans=spans)
