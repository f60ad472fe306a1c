"""Reduction of a profiler trace to device busy time, program and kernel time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events: per device, its operations (the ``XLA Ops`` line) and its programs
(the ``XLA Modules`` line); the host's spans named ``bench.*``, which the
harness writes around its calls into the engine; and apart from those, the
program's own host spans, named ``engine.*``.  The reducers work on those
plain events, so a test can feed them a small trace of its own.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Iterable, List, Optional


@dataclass(frozen=True)
class Event:
    name: str
    start: float  # seconds, on the trace's clock
    dur: float  # seconds
    meta: str = ""  # the event's stat values, joined, for matching

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Device:
    ops: List[Event]
    modules: List[Event]


@dataclass
class Trace:
    devices: List[Device]
    host: List[Event]  # the harness's own spans
    spans: List[Event] = field(default_factory=list)  # the program's, by start


def _events(line, plane) -> List[Event]:
    out = []
    for ev in line.events:
        meta = " ".join(str(v) for _, v in ev.stats)
        out.append(Event(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9, meta))
    return out


def load(logdir: str) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(Path(logdir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(str(files[-1]))
    devices, host, spans = [], [], []
    for plane in pd.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:TPU:") and "XLA Ops" in lines:
            devices.append(Device(_events(lines["XLA Ops"], plane),
                                  _events(lines["XLA Modules"], plane) if "XLA Modules" in lines else []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in _events(line, plane):
                    if e.name.startswith("bench."):
                        host.append(e)
                    elif e.name.startswith("engine."):
                        spans.append(e)
    return Trace(devices, host, sorted(spans, key=lambda e: e.start))


def union(intervals: Iterable[tuple]) -> List[tuple]:
    """Merged, sorted (start, end) intervals."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_seconds(ops: List[Event], t0: float, t1: float) -> float:
    """Seconds of [t0, t1) in which some operation ran on the device."""
    clipped = [(max(e.start, t0), min(e.end, t1)) for e in ops if e.end > t0 and e.start < t1]
    return sum(e - s for s, e in union(clipped))


def matches(ev: Event, pattern: str) -> bool:
    return re.search(pattern, ev.name) is not None or re.search(pattern, ev.meta) is not None


def module_seconds(dev: Device, pattern: str) -> float:
    return sum(m.dur for m in dev.modules if re.search(pattern, m.name))


def kernel_seconds(dev: Device, kernel: str, module: Optional[str] = None) -> float:
    """Seconds of the operations matching ``kernel`` (by name or stats),
    only those inside a program matching ``module`` where one is given."""
    ops = [o for o in dev.ops if matches(o, kernel)]
    if module is None:
        return sum(o.dur for o in ops)
    spans = union((m.start, m.end) for m in dev.modules if re.search(module, m.name))
    total, i = 0.0, 0
    for o in sorted(ops, key=lambda o: o.start):
        while i < len(spans) and spans[i][1] < o.start:
            i += 1
        if i < len(spans) and spans[i][0] <= o.start <= spans[i][1]:
            total += o.dur
    return total


def top_ops(dev: Device, n: int = 10) -> List[list]:
    """The ``n`` operation names that took most device time, [name, seconds]."""
    agg: dict = {}
    for o in dev.ops:
        agg[o.name] = agg.get(o.name, 0.0) + o.dur
    return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]


def name_gap(s: float, e: float, host: List[Event]) -> str:
    """The innermost host span (the window's own aside) that covers more
    than half of [s, e); where none does, the name whose spans cover most
    of it."""
    cover: dict = {}
    inner = None
    for h in host:
        ov = min(h.end, e) - max(h.start, s)
        if h.name == "bench.window" or ov <= 0:
            continue
        cover[h.name] = cover.get(h.name, 0.0) + ov
        if 2 * ov > e - s and (inner is None or h.dur < inner.dur):
            inner = h
    if inner is not None:
        return inner.name
    return max(cover, key=cover.get) if cover else "outside any host span"


def idle_gaps(dev: Device, host: List[Event], t0: float, t1: float, n: int = 10) -> List[list]:
    """The ``n`` longest gaps in [t0, t1) with no device operation, each
    named by ``name_gap`` over ``host`` (the harness's spans, and the
    program's where given), [name, seconds]."""
    busy = union((max(e.start, t0), min(e.end, t1)) for e in dev.ops if e.end > t0 and e.start < t1)
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    return [[name_gap(s, e, host), e - s] for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]]
