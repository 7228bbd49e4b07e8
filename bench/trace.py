"""Reduction of a profiler trace to the benchmark's device numbers.

Two stages, so that the second can be checked on a small recorded trace:

  * `load_xplane(path)` reads the `.xplane.pb` that `jax.profiler`
    writes into plain `Plane`s of `(name, start_ns, duration_ns)` events;
  * `reduce(planes, span)` takes the host span named `span` (the
    benchmark's own `TraceAnnotation`) and measures, on the device, what
    ran inside it.

Device planes are those named ``/device:TPU:<n>``; their operations are
the events on the line `OPS_LINE`. Busy time is the union of the
operation intervals, clipped to the span and averaged over the devices.
An operation that holds others, as a `while` holds its body's, is
counted by its own time: its length less its children's. Each idle gap
between operations is labelled by the innermost host event that covers
its midpoint, on the thread that holds the span.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from dataclasses import dataclass, field

#: the device line that holds one event per executed HLO operation
OPS_LINE = "XLA Ops"
#: an operation's name is its HLO text, cut to this many characters
NAME_CHARS = 160
_DEVICE = re.compile(r"^/device:TPU:\d+$")


@dataclass
class Plane:
    name: str
    lines: dict = field(default_factory=dict)   # line name -> [(n, s, d)]


@dataclass
class Summary:
    span_ns: int                 # length of the span
    busy_ns: float               # device-busy union inside it, per device
    n_devices: int
    op_ns: dict                  # op name -> summed own device ns in it
    gaps: list                   # [(label, ns)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.span_ns


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load_xplane(path: str) -> list[Plane]:
    import jax

    out = []
    for pl in jax.profiler.ProfileData.from_file(path).planes:
        plane = Plane(pl.name)
        for ln in pl.lines:
            plane.lines.setdefault(ln.name, []).extend(
                (e.name, int(e.start_ns), int(e.duration_ns))
                for e in ln.events)
        out.append(plane)
    return out


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _self_ns(ops):
    """{name: summed own ns} of clipped (name, start, end) operations:
    each operation's length less that of the operations nested in it."""
    own = collections.Counter()
    stack = []                                  # open (name, end)
    for name, a, b in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= a:
            stack.pop()
        if stack and b <= stack[-1][1]:
            own[stack[-1][0]] -= b - a
        own[name] += b - a
        stack.append((name, b))
    return own


def _host_span(planes, span):
    """(start, end, events of the span's thread) of the first host event
    named `span`."""
    for pl in planes:
        if _DEVICE.match(pl.name):
            continue
        for events in pl.lines.values():
            for name, s, d in events:
                if name == span:
                    return s, s + d, events
    raise LookupError(f"no host span named {span!r} in the trace")


def reduce(planes: list[Plane], span: str, n_gaps: int = 10) -> Summary:
    s0, s1, thread = _host_span(planes, span)
    devices = [pl for pl in planes if _DEVICE.match(pl.name)]
    busy, op_ns, gaps = 0.0, collections.Counter(), []
    inner = [(s, s + d, n) for n, s, d in thread
             if s >= s0 and s + d <= s1 and n != span]
    for pl in devices:
        ops = []
        for name, s, d in pl.lines.get(OPS_LINE, ()):
            a, b = max(s, s0), min(s + d, s1)
            if b > a:
                ops.append((name[:NAME_CHARS], a, b))
        op_ns.update(_self_ns(ops))
        merged = _union((a, b) for _, a, b in ops)
        busy += sum(b - a for a, b in merged)
        edges = [s0] + [x for iv in merged for x in iv] + [s1]
        gaps.extend((a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n_gaps]
    labelled = []
    for a, b in gaps:
        mid = (a + b) / 2
        cover = [iv for iv in inner if iv[0] <= mid < iv[1]]
        label = min(cover, key=lambda iv: iv[1] - iv[0])[2] if cover else ""
        labelled.append((f"{span}/{label}" if label else span, b - a))
    return Summary(span_ns=s1 - s0, busy_ns=busy / max(1, len(devices)),
                   n_devices=len(devices), op_ns=dict(op_ns), gaps=labelled)
