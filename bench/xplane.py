"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-program
and per-kernel device time, and the idle gaps named by host activity.

``load`` turns the file into plain lists (``Trace``); ``reduce`` does the
arithmetic on those lists, so it can be checked on a trace written by hand.
Times are nanoseconds on the profiler's clock, which it shares between the
host and the device planes.

* Busy time is the union of the device's op intervals (line ``XLA Ops``;
  ``XLA Modules`` where a plane has no op line) inside the window,
  averaged over the device planes.
* Program time is the sum, per program name, of its executions
  (``XLA Modules``) that start inside the window; op time likewise per op
  name (without its operands), counting each op's own time: a ``while``
  op's events span those of its body.
* An idle gap is a stretch of the window in which no op runs.  It is named
  by the innermost host annotation (a ``TraceAnnotation`` named
  ``<track>/<name>``) that covers its midpoint.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

ANNOTATION = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*/\S+$")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclass
class Device:
    name: str
    ops: list = field(default_factory=list)       # (name, start, end)
    modules: list = field(default_factory=list)   # (name, start, end)


@dataclass
class Trace:
    devices: list                 # [Device]
    host: list                    # (name, start, end) annotations


def _events(line):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def short_name(name: str) -> str:
    """An op's name without its HLO operands: ``%fusion.12 = bf16[8,80]``
    of ``%fusion.12 = bf16[8,80]{1,0} fusion(...), kind=...``."""
    return name.split("{")[0].split(" fusion(")[0][:96].strip()


def self_times(events) -> list:
    """(name, start, end, self ns) for events that may nest (a ``while``
    op spans its body's ops): each event's time minus its direct
    children's."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = [[n, s, e, e - s] for n, s, e in evs]
    stack = []
    for i, (_, s, e, _) in enumerate(out):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= e - s
        stack.append(i)
    return [tuple(x) for x in out]


def from_profile(pd) -> Trace:
    """A :class:`Trace` from a ``jax.profiler.ProfileData``."""
    devices, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            d = Device(plane.name)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    d.ops = _events(line)
                elif line.name == "XLA Modules":
                    d.modules = _events(line)
            if not d.ops:
                d.ops = list(d.modules)
            devices.append(d)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [ev for ev in _events(line)
                         if ANNOTATION.match(ev[0])]
    return Trace(devices, host)


def find_xplane(log_dir) -> Path:
    """The newest ``*.xplane.pb`` under ``log_dir``."""
    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(str(path)))


def union(intervals, lo: int, hi: int) -> list:
    """Sorted disjoint union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _sum_by_name(events, lo: int, hi: int) -> dict:
    """name -> (count, ns) of events starting in [lo, hi); ``events`` are
    (name, start, end) or (name, start, end, ns)."""
    acc = {}
    for ev in events:
        name, s, e = ev[:3]
        if lo <= s < hi:
            n, t = acc.get(name, (0, 0))
            acc[name] = (n + 1, t + (ev[3] if len(ev) > 3 else e - s))
    return acc


def _host_name(host, t: int) -> str:
    best = None
    for name, s, e in host:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "no host annotation"


@dataclass
class Reduced:
    window_ns: int
    busy_ns: float                # averaged over device planes
    programs: dict                # name -> (count, total ns)
    ops: dict                     # short name -> (count, self ns)
    gaps: dict                    # host activity -> (count, total ns)
    longest_gaps: list            # (ns, host activity), longest first


def reduce(trace: Trace, window: tuple | None = None) -> Reduced:
    """Reduce ``trace`` over ``window`` = (start, end) ns; by default the
    span of all device ops."""
    all_ops = [ev for d in trace.devices for ev in d.ops]
    if window is None:
        if not all_ops:
            raise ValueError("trace holds no device op")
        window = (min(s for _, s, _ in all_ops),
                  max(e for _, _, e in all_ops))
    lo, hi = window
    busy, programs, ops, gaps, longest = 0.0, {}, {}, {}, []
    for d in trace.devices:
        u = union([(s, e) for _, s, e in d.ops], lo, hi)
        busy += sum(e - s for s, e in u)
        own = [(short_name(n), s, e, t) for n, s, e, t in self_times(d.ops)]
        for acc, evs in ((programs, d.modules), (ops, own)):
            for k, (n, t) in _sum_by_name(evs, lo, hi).items():
                n0, t0 = acc.get(k, (0, 0))
                acc[k] = (n0 + n, t0 + t)
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                name = _host_name(trace.host, (s + e) // 2)
                n0, t0 = gaps.get(name, (0, 0))
                gaps[name] = (n0 + 1, t0 + (e - s))
                longest.append((e - s, name))
    n_dev = max(1, len(trace.devices))
    longest.sort(reverse=True)
    return Reduced(window_ns=hi - lo, busy_ns=busy / n_dev,
                   programs=programs, ops=ops, gaps=gaps,
                   longest_gaps=longest[:10])


def matching(table: dict, pattern: str) -> tuple:
    """(count, total ns) over the entries of ``table`` whose name matches
    the regular expression ``pattern``."""
    rx = re.compile(pattern)
    n = t = 0
    for k, (c, d) in table.items():
        if rx.search(k):
            n, t = n + c, t + d
    return n, t


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device ops that took most
    time and the idle time by host activity, in seconds."""
    ops = sorted(red.ops.items(), key=lambda kv: -kv[1][1])[:top]
    gaps = sorted(red.gaps.items(), key=lambda kv: -kv[1][1])[:top]
    return {"device_ops": [[k, t / 1e9] for k, (_, t) in ops],
            "idle_gaps": [[k, t / 1e9] for k, (_, t) in gaps]}
