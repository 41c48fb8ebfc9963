"""The trace reader: from a JAX profiler trace to device busy time, kernel
time by name, collective time and idle gaps labelled by host activity.

A trace is what ``jax.profiler.trace`` writes: an ``.xplane.pb`` file
under ``<dir>/plugins/profile/<time>/``.  ``load`` keeps two things of
it: the op-level events of every TPU core (the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane) and every event of the host plane.  All
times are nanoseconds on the profiler's common clock.

Every reduction below takes a window ``(t0_ns, t1_ns)`` and clips the
events to it, so a metric never counts work outside the traced
sub-window.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    device_ops: Dict[int, List[Event]]      # per TPU core
    host: List[Event]

    def annotation(self, name: str) -> Optional[Event]:
        """The longest host event called ``name`` (a TraceAnnotation)."""
        hits = [e for e in self.host if e.name == name]
        return max(hits, key=lambda e: e.dur_ns) if hits else None


def _event(e) -> Event:
    return Event(e.name, float(e.start_ns), float(e.duration_ns))


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(trace_dir))
    device: Dict[int, List[Event]] = {}
    host: List[Event] = []
    cpu_ops: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = [_event(e) for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            device[int(m.group(1))] = sorted(evs, key=lambda e: e.start_ns)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    host.append(_event(e))
                    if any(k == "hlo_op" for k, _ in e.stats):
                        cpu_ops.append(host[-1])
    if not device and cpu_ops:
        # JAX's CPU backend runs XLA ops on host threads: the tests'
        # traces read them as the ops of device 0
        device[0] = sorted(cpu_ops, key=lambda e: e.start_ns)
    return Trace(device_ops=device, host=host)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

Window = Tuple[float, float]


def clip(events: Iterable[Event], window: Window) -> List[Tuple[float, float]]:
    """The events' intervals clipped to ``window`` (empty ones dropped)."""
    t0, t1 = window
    out = []
    for e in events:
        a, b = max(e.start_ns, t0), min(e.end_ns, t1)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(events: Iterable[Event], window: Window) -> float:
    """Length of the union of the events' intervals inside ``window``."""
    return sum(b - a for a, b in union(clip(events, window)))


def idle_gaps(events: Iterable[Event], window: Window
              ) -> List[Tuple[float, float]]:
    """The intervals of ``window`` in which no event runs."""
    gaps, t = [], window[0]
    for a, b in union(clip(events, window)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if window[1] > t:
        gaps.append((t, window[1]))
    return gaps


def summed_ns(events: Iterable[Event], window: Window) -> float:
    """Summed device durations of ``events`` inside ``window``."""
    return sum(b - a for a, b in clip(events, window))


_SUFFIX = re.compile(r"[.\d]+$")


def op_name(e: Event) -> str:
    """The HLO instruction an op event ran: a TPU trace names each event
    by the instruction's text, ``%fusion.12 = bf16[...] fusion(...)``."""
    return e.name.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """An instruction's name without XLA's numeric suffix
    (``fusion.12`` -> ``fusion``, ``fused_local_step.3`` ->
    ``fused_local_step``), so that its instances sum under one entry."""
    return _SUFFIX.sub("", name) or name


def of_kind(events: Iterable[Event], kind: str) -> List[Event]:
    """The op events whose instruction is of ``kind``."""
    return [e for e in events if op_kind(op_name(e)) == kind]


def self_ns(events: Iterable[Event], window: Window) -> Dict[str, float]:
    """Exclusive device time by op kind inside ``window``.  The ``XLA
    Ops`` line nests ops inside the ``while`` or ``conditional`` that
    runs them, so each event's time less its children's is what it ran
    itself."""
    t0, t1 = window
    spans = sorted(((max(e.start_ns, t0), min(e.end_ns, t1),
                     op_kind(op_name(e))) for e in events
                    if min(e.end_ns, t1) > max(e.start_ns, t0)),
                   key=lambda x: (x[0], -x[1]))
    tot: Dict[str, float] = {}
    stack: List[list] = []          # [start, end, kind, self]
    for a, b, k in spans:
        while stack and stack[-1][1] <= a:
            _, _, pk, ps = stack.pop()
            tot[pk] = tot.get(pk, 0.0) + ps
        if stack:
            stack[-1][3] -= min(b, stack[-1][1]) - a
        stack.append([a, b, k, b - a])
    for _, _, pk, ps in stack:
        tot[pk] = tot.get(pk, 0.0) + ps
    return tot


def top_ops(events: Iterable[Event], window: Window, n: int = 10
            ) -> List[list]:
    """``[[op, seconds], ...]``: the ``n`` op kinds that took the most
    device time of their own inside ``window``."""
    best = sorted(self_ns(events, window).items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def label_gap(gap: Tuple[float, float], host: Sequence[Event],
              skip: Sequence[str] = ()) -> str:
    """What the host was doing in ``gap``: of the host events that
    overlap it most, the shortest, leaving out those named in ``skip``
    (the window's own annotation)."""
    a, b = gap
    best, best_key = "no host event", (0.0, 0.0)
    for e in host:
        if e.name in skip:
            continue
        key = (min(e.end_ns, b) - max(e.start_ns, a), -e.dur_ns)
        if key[0] > 0 and key > best_key:
            best, best_key = e.name, key
    return best


def longest_gaps(trace: Trace, window: Window, n: int = 10,
                 skip: Sequence[str] = ()) -> List[list]:
    """``[[host activity, seconds], ...]``: the ``n`` longest intervals of
    ``window`` in which no core of the first device ran an op, each
    labelled by :func:`label_gap`."""
    core = min(trace.device_ops)
    gaps = sorted(idle_gaps(trace.device_ops[core], window),
                  key=lambda g: g[0] - g[1])[:n]
    return [[label_gap(g, trace.host, skip), (g[1] - g[0]) * 1e-9]
            for g in gaps]
