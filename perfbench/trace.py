"""Reduction of a ``jax.profiler`` capture to the numbers the per-layer
readers use: per chip, the device operations of the traced window, their
busy union and self times, the time of operations matching a name pattern,
the idle gaps and what the host was doing in each.

Everything below ``load`` works on plain tuples ``(name, start_ns, dur_ns)``
so that the tests can hand it a trace written by hand.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]          # name, start_ns, duration_ns
Interval = Tuple[int, int]            # start_ns, end_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
#: operations that only contain others: their own time is their children's
CONTAINERS = ("while", "conditional", "call")
#: how many of the longest idle gaps the breakdown labels one by one
LABELLED_GAPS = 100
_KIND = re.compile(r"(?<=\s)([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_kind(name: str) -> str:
    """The operation of a device event. The profiler names an event by its
    whole HLO instruction (``%pad.4 = bf16[..]{..} pad(bf16[..] %copy.6, ..)``);
    a bare name (``fusion.5``) is its own kind less the number."""
    if " = " in name:
        m = _KIND.search(name.split(" = ", 1)[1])
        if m:
            return m.group(1)
    return name.lstrip("%").split(".")[0]


def short_name(name: str) -> str:
    """``<kind>[:<custom-call target>] <instruction>``, for the breakdown."""
    kind = op_kind(name)
    target = _TARGET.search(name)
    if target:
        kind += ":" + target.group(1)
    return f"{kind} {name.split(' = ', 1)[0]}"[:120]


def clip(events: Iterable[Event], window: Interval) -> List[Event]:
    lo, hi = window
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy(events: Iterable[Event]) -> List[Interval]:
    return union((s, s + d) for _, s, d in events)


def seconds(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals) / 1e9


def matching(events: Iterable[Event], patterns: Sequence[str]) -> List[Event]:
    rx = [re.compile(p) for p in patterns]
    return [ev for ev in events if any(r.search(ev[0]) for r in rx)]


def self_times(events: Iterable[Event]) -> Dict[str, float]:
    """Seconds by operation name, a parent's time less its children's
    (operations on one line nest: a ``while`` spans its body's)."""
    out: Dict[str, float] = {}
    stack: List[List] = []            # [name, end_ns, self_ns]

    def close(until: int) -> None:
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + own / 1e9

    for name, start, dur in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(1 << 62)
    return out


def gaps(busy_intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    out, at = [], lo
    for s, e in busy_intervals:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def label_at(host_events: Iterable[Event], t: int) -> str:
    """Name of the shortest host span that covers ``t``."""
    best: Optional[Event] = None
    for ev in host_events:
        if ev[1] <= t < ev[1] + ev[2] and (best is None or ev[2] < best[2]):
            best = ev
    return best[0] if best else "no_host_span"


def top(table: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]


class Trace:
    """The traced window: ``chips`` maps a chip's index to its device
    operations, ``host`` holds the host's spans, ``window`` is the interval
    the benchmark's own ``perfbench.fit`` spans cover."""

    def __init__(self, chips: Dict[int, List[Event]], host: List[Event],
                 span_prefix: str = "perfbench."):
        own = [ev for ev in host if ev[0].startswith(span_prefix)]
        fits = [ev for ev in own if ev[0].startswith(span_prefix + "fit")]
        marks = fits or [ev for evs in chips.values() for ev in evs]
        if not marks:
            raise ValueError("the trace holds no device operation and no "
                             "benchmark span")
        self.window: Interval = (min(ev[1] for ev in marks),
                                 max(ev[1] + ev[2] for ev in marks))
        self.chips = {c: clip(evs, self.window) for c, evs in chips.items()}
        self.host = host
        self.own_spans = own
        self.n_fits = len(fits)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self, chip: int) -> float:
        return seconds(busy(self.chips[chip]))

    def mean_busy_s(self) -> float:
        if not self.chips:
            return 0.0
        return sum(self.busy_s(c) for c in self.chips) / len(self.chips)

    def fullest_chip(self) -> int:
        return max(self.chips, key=self.busy_s)

    def matching_s(self, chip: int, patterns: Sequence[str]) -> float:
        return seconds(busy(matching(self.chips[chip], patterns)))

    def breakdown(self) -> dict:
        if not self.chips:
            return {"device_ops": [], "idle_gaps": []}
        chip = self.fullest_chip()
        ops: Dict[str, float] = {}
        for name, own in self_times(self.chips[chip]).items():
            if op_kind(name) not in CONTAINERS:
                key = short_name(name)
                ops[key] = ops.get(key, 0.0) + own
        idle: Dict[str, float] = {}
        own = set(self.own_spans)
        others = [ev for ev in self.host if ev not in own]
        idle_gaps = sorted(gaps(busy(self.chips[chip]), self.window),
                           key=lambda g: g[0] - g[1])
        # the host holds tens of thousands of spans: the longest gaps get
        # a label each, the many short ones one label together
        for k, (s, e) in enumerate(idle_gaps):
            key = "short_gaps"
            if k < LABELLED_GAPS:
                mid = (s + e) // 2
                key = f"{label_at(self.own_spans, mid)}/" \
                      f"{label_at(others, mid)}"[:120]
            idle[key] = idle.get(key, 0.0) + (e - s) / 1e9
        return {"device_ops": top(ops), "idle_gaps": top(idle)}


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` with jax alone."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    chips: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                chips.setdefault(int(m.group(1)), []).extend(
                    (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events)
            elif plane.name.startswith("/host:"):
                host.extend((ev.name, int(ev.start_ns), int(ev.duration_ns))
                            for ev in line.events)
    return Trace(chips, host)


def name_table(path: str, most: int = 60) -> str:
    """Every plane, line and its commonest event names: what to look at by
    hand before writing a pattern."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    rows = []
    for plane in data.planes:
        for line in plane.lines:
            table: Dict[str, List[float]] = {}
            for ev in line.events:
                t = table.setdefault(ev.name, [0, 0.0])
                t[0] += 1
                t[1] += ev.duration_ns / 1e6
            rows.append(f"## {plane.name} | {line.name} "
                        f"({sum(t[0] for t in table.values())} events)")
            for name, (n, ms) in sorted(table.items(),
                                        key=lambda kv: -kv[1][1])[:most]:
                rows.append(f"  {ms:12.3f} ms  x{n:<6d} {name[:160]}")
    return "\n".join(rows)
