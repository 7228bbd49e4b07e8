"""Reduction of a profiler trace by the program's own spans and scopes.

`bench/trace.py` measures a sweep from outside: the harness span less
the device-busy time inside it. The program also names its own stages
on the profiler's clock (`repro.core.sweep.engine._run_jax`):

  * host spans ``sweep.grid_build``, ``sweep.stage``, ``sweep.tick_loop``,
    ``sweep.readback`` and ``sweep.finalize``, in that order, the last
    carrying the counters ``cells`` and ``loop_iterations``;
  * device scopes ``tick.front_end``, ``tick.refresh``, ``tick.arbitrate``
    and ``tick.serve`` (`repro.core.sweep.jaxbody`), which reach each
    operation's ``op_name``.

`load(path, op_names)` reads the `.xplane.pb` once into the `Plane`s of
`bench.trace` and the stats of the program's spans, and resolves each
distinct device operation's scope once: a device operation's event is
named by its HLO instruction, whose op_name the compiled loop's HLO
text gives (`hlo_op_names`). `reduce(trace, span)` then gives,
inside the harness span `span`: each program span's length, the
device-idle time inside it and its stats; the device own time of each
scope's operations inside ``sweep.tick_loop``, and the part of the loop's
operations that no scope holds; the device-idle time per program span,
and each idle gap of `LONG_GAP_NS` or more with the span that holds most
of it.
Device numbers are averaged over the devices, as in `bench.trace`.

A trace of a program without these spans or scopes reduces to empty
maps, and a trace with no device plane to None: the readers then find
nothing to read.
"""
from __future__ import annotations

import collections
import re
from dataclasses import dataclass, field

from bench.trace import _DEVICE, OPS_LINE, Plane, _host_span, _self_ns, _union

SPANS = ("sweep.grid_build", "sweep.stage", "sweep.tick_loop",
         "sweep.readback", "sweep.finalize")
SCOPES = ("tick.front_end", "tick.refresh", "tick.arbitrate", "tick.serve")
LOOP = "sweep.tick_loop"
#: the loop's operations that no tick scope holds
UNATTRIBUTED = "(unattributed)"
#: device-idle time inside the harness span that no program span covers
OUTSIDE = "(outside)"
#: an idle gap this long is listed with the program span holding most of it
LONG_GAP_NS = 1_000_000
_SCOPE = re.compile(r"(?:^|/)(tick\.[A-Za-z_]+)(?:/|$)")
_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?'
                     r'metadata=\{op_name="([^"]*)"', re.M)


@dataclass
class Trace:
    planes: list                 # bench.trace.Plane
    span_stats: dict = field(default_factory=dict)   # (name, start) -> {}
    op_scope: dict = field(default_factory=dict)     # op name -> scope|None


@dataclass
class Span:
    ns: int                      # length on the profiler's clock
    idle_ns: float               # device-idle time inside it, per device
    stats: dict                  # counters the span carries


@dataclass
class Spans:
    spans: dict                  # program span name -> Span
    scope_ns: dict               # scope (or UNATTRIBUTED) -> own device ns
    loop_busy_ns: float          # device-busy union inside sweep.tick_loop
    idle_by_span: dict           # program span (or OUTSIDE) -> idle ns
    long_gaps: list              # [(span, ns)] of gaps >= LONG_GAP_NS
    n_devices: int

    def counter(self, name: str):
        """Value of counter `name` on any program span, or None."""
        for s in self.spans.values():
            if name in s.stats:
                return s.stats[name]
        return None


def scope_of(op_name: str):
    """The tick scope in `op_name` (``.../tick.serve/...``), or None."""
    m = _SCOPE.search(op_name or "")
    return m.group(1) if m else None


def hlo_op_names(hlo_text: str) -> dict:
    """{instruction name: op_name} of an HLO module's text, as
    ``compiled.as_text()`` prints it (``metadata={op_name="..."}``)."""
    return dict(_HLO_OP.findall(hlo_text))


def _instruction(op_event: str) -> str:
    """The HLO instruction an operation event names: a device operation's
    event is named by its HLO text, ``%fusion.402 = s32[...] fusion(...)``."""
    return op_event.split(" ", 1)[0].lstrip("%")


def load(path: str, op_names: dict) -> Trace:
    """The trace at `path`, each device operation's scope taken from its
    HLO instruction's op_name in `op_names` (`hlo_op_names` of the
    program's compiled loop)."""
    import jax

    out = Trace([])
    for pl in jax.profiler.ProfileData.from_file(path).planes:
        plane = Plane(pl.name)
        device = bool(_DEVICE.match(pl.name))
        for ln in pl.lines:
            events = plane.lines.setdefault(ln.name, [])
            ops = device and ln.name == OPS_LINE
            for e in ln.events:
                name, start = e.name, int(e.start_ns)
                events.append((name, start, int(e.duration_ns)))
                if ops:
                    if name not in out.op_scope:
                        out.op_scope[name] = scope_of(
                            op_names.get(_instruction(name)))
                elif not device and name in SPANS:
                    out.span_stats[(name, start)] = dict(e.stats)
        out.planes.append(plane)
    return out


def _overlap(a0, a1, b0, b1) -> int:
    return max(0, min(a1, b1) - max(a0, b0))


def reduce(tr: Trace, span: str):
    """`Spans` of the program inside the host span `span`, or None where
    the trace has no device plane."""
    s0, s1, thread = _host_span(tr.planes, span)
    devices = [pl for pl in tr.planes if _DEVICE.match(pl.name)]
    if not devices:
        return None
    program = sorted((s, s + d, n) for n, s, d in thread
                     if n in SPANS and s >= s0 and s + d <= s1)
    idle = collections.Counter()
    long_gaps = []
    scope_ns = collections.Counter()
    loop_busy = 0
    loop = next(((a, b) for a, b, n in program if n == LOOP), None)
    for pl in devices:
        ops = []
        for name, s, d in pl.lines.get(OPS_LINE, ()):
            a, b = max(s, s0), min(s + d, s1)
            if b > a:
                ops.append((name, a, b))
        merged = _union((a, b) for _, a, b in ops)
        edges = [s0] + [x for iv in merged for x in iv] + [s1]
        first = 0                       # gaps and spans are both sorted
        for a, b in zip(edges[::2], edges[1::2]):
            while first < len(program) and program[first][1] <= a:
                first += 1
            parts = {}
            for p0, p1, n in program[first:]:
                if p0 >= b:
                    break
                parts[n] = _overlap(a, b, p0, p1)
            parts[OUTSIDE] = (b - a) - sum(parts.values())
            idle.update(parts)
            if b - a >= LONG_GAP_NS:
                long_gaps.append((max(parts, key=parts.get), b - a))
        if loop is None:
            continue
        in_loop = [(n, max(a, loop[0]), min(b, loop[1]))
                   for n, a, b in ops if min(b, loop[1]) > max(a, loop[0])]
        loop_busy += sum(b - a for a, b in _union(
            (a, b) for _, a, b in in_loop))
        for name, ns in _self_ns(in_loop).items():
            scope_ns[tr.op_scope.get(name) or UNATTRIBUTED] += ns
    k = len(devices)
    spans = {n: Span(b - a, idle[n] / k, tr.span_stats.get((n, a), {}))
             for a, b, n in program}
    return Spans(spans=spans,
                 scope_ns={n: ns / k for n, ns in scope_ns.items()},
                 loop_busy_ns=loop_busy / k,
                 idle_by_span={n: ns / k for n, ns in idle.items()},
                 long_gaps=sorted(long_gaps, key=lambda g: -g[1]),
                 n_devices=k)
