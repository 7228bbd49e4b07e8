"""The reduction of a profiler trace by the program's own spans and
scopes (`bench.spans`), the readers of the metrics built on it, and the
profile run that calls them: by hand on made-up planes, and on a small
cell on the CPU. `bench.trace` keeps its numbers on the trace it was
checked on."""
import hashlib
import json
import os

import pytest
from _bench_helpers import REPO, make_root, stand_in

from bench import harness, profile_sweep, spans, trace
from bench.trace import Plane

FIXTURES = os.path.join(REPO, "tests", "bench_harness", "fixtures")
OLD = os.path.join(FIXTURES, "tiny_sweep.xplane.pb")
SCOPE = {"fusion.front": "tick.front_end", "fusion.ref": "tick.refresh",
         "fusion.arb": "tick.arbitrate", "fusion.serve": "tick.serve"}


def _trace():
    """A sweep in the harness span [1000, 20000]: the five program spans,
    the loop's `while` holding one operation of each scope and an
    unscoped copy, a staging op and a readback copy."""
    host = Plane("/host:CPU", {"python": [
        ("sweep", 1000, 19000),
        ("sweep.grid_build", 1100, 1900), ("_Grid", 1200, 1500),
        ("sweep.stage", 3000, 1000), ("sweep.tick_loop", 4000, 11000),
        ("sweep.readback", 15000, 1000), ("sweep.finalize", 16000, 3000),
        ("sweep.finalize", 30000, 10)]})
    ops = [("broadcast.1", 3500, 500),              # stage: busy
           ("while.1", 4500, 10000),                # loop: 10000 busy
           ("fusion.front", 4600, 1000), ("fusion.ref", 5600, 2000),
           ("fusion.arb", 7600, 3000), ("fusion.serve", 10600, 2500),
           ("copy.7", 13100, 400),
           ("copy.9", 15200, 300)]                  # readback: busy
    dev = Plane("/device:TPU:0", {trace.OPS_LINE: ops})
    return spans.Trace(
        [host, dev],
        span_stats={("sweep.finalize", 16000): {"cells": 4,
                                                "loop_iterations": 50}},
        op_scope=dict(SCOPE))


def test_spans_scopes_and_idle_by_hand():
    sp = spans.reduce(_trace(), "sweep")
    assert list(sp.spans) == list(spans.SPANS)
    assert {n: s.ns for n, s in sp.spans.items()} == {
        "sweep.grid_build": 1900, "sweep.stage": 1000,
        "sweep.tick_loop": 11000, "sweep.readback": 1000,
        "sweep.finalize": 3000}
    # idle: [1000,3500] [4000,4500] [14500,15200] [15500,20000]
    assert sp.idle_by_span == {
        "sweep.grid_build": 1900, "sweep.stage": 500,
        "sweep.tick_loop": 500 + 500, "sweep.readback": 200 + 500,
        "sweep.finalize": 3000, spans.OUTSIDE: 100 + 1000}
    assert sum(sp.idle_by_span.values()) == 19000 - 500 - 10000 - 300
    assert {n: s.idle_ns for n, s in sp.spans.items()} == {
        n: ns for n, ns in sp.idle_by_span.items() if n != spans.OUTSIDE}
    # the while's own time (10000 less its body's 8900) and the unscoped
    # copy are the loop's unattributed remainder
    assert sp.scope_ns == {"tick.front_end": 1000, "tick.refresh": 2000,
                           "tick.arbitrate": 3000, "tick.serve": 2500,
                           spans.UNATTRIBUTED: 1100 + 400}
    assert sp.loop_busy_ns == sum(sp.scope_ns.values()) == 10000
    assert sp.counter("loop_iterations") == 50 and sp.counter("cells") == 4
    assert sp.counter("absent") is None
    assert sp.n_devices == 1 and sp.long_gaps == []


def test_long_gaps_go_to_the_span_holding_most_of_them(monkeypatch):
    monkeypatch.setattr(spans, "LONG_GAP_NS", 1000)
    sp = spans.reduce(_trace(), "sweep")
    assert sp.long_gaps == [("sweep.finalize", 4500),
                            ("sweep.grid_build", 2500)]


def test_program_without_spans_or_scopes_reduces_to_nothing():
    """The trace of a program that records neither (the parent of the
    instrumentation): empty maps, and every new reader reads None."""
    tr = _trace()
    tr.planes[0].lines["python"] = [("sweep", 1000, 19000)]
    tr.span_stats, tr.op_scope = {}, {}
    sp = spans.reduce(tr, "sweep")
    assert sp.spans == {} and sp.scope_ns == {} and sp.loop_busy_ns == 0
    assert sp.idle_by_span == {spans.OUTSIDE: 19000 - 500 - 10000 - 300}
    ctx = _ctx(sp, 100)
    assert all(harness.metric_reader(n)(ctx) is None
               for n in profile_sweep.READERS)


def test_no_device_plane_reduces_to_none():
    tr = _trace()
    tr.planes = tr.planes[:1]
    assert spans.reduce(tr, "sweep") is None


def _ctx(sp, sum_cell_ticks):
    from types import SimpleNamespace
    return SimpleNamespace(spans=sp, sum_cell_ticks=sum_cell_ticks)


def test_readers_by_hand():
    ctx = _ctx(spans.reduce(_trace(), "sweep"), 150)
    read = {n: harness.metric_reader(n)(ctx) for n in profile_sweep.READERS}
    assert read == {
        "grid_build_ms": 1900 / 1e6, "stage_ms": 1000 / 1e6,
        "readback_ms": 1000 / 1e6, "finalize_ms": 3000 / 1e6,
        "tick_front_end_us_per_tick": 1000 / 1e3 / 50,
        "tick_refresh_us_per_tick": 2000 / 1e3 / 50,
        "tick_arbitrate_us_per_tick": 3000 / 1e3 / 50,
        "tick_serve_us_per_tick": 2500 / 1e3 / 50,
        "tick_loop_useful_share": 100.0 * 150 / (4 * 50)}


@pytest.mark.parametrize("op_name, scope", [
    ("jit(loop)/while/body/tick.serve/scatter-add", "tick.serve"),
    ("jit(loop)/while/body/tick.front_end/while/body/add", "tick.front_end"),
    ("tick.refresh", "tick.refresh"),
    ("jit(loop)/while/body/add", None),
    ("jit(loop)/while/cond/lt", None),
    ("jit(loop)/while/body/ticker.x/add", None),
    (None, None)])
def test_scope_of(op_name, scope):
    assert spans.scope_of(op_name) == scope


def test_hlo_op_names_and_instruction():
    hlo = """HloModule jit_loop
  %wrapped_iota = s32[4,1]{1,0} fusion(), kind=kLoop, calls=%c, metadata={op_name="jit(loop)/while/body/iota" stack_frame_id=2}
  %get-tuple-element.17 = s32[] get-tuple-element(%p), index=0
  ROOT %fusion.402 = s32[7680]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(loop)/while/body/tick.arbitrate/gather"}
"""
    names = spans.hlo_op_names(hlo)
    assert names == {
        "wrapped_iota": "jit(loop)/while/body/iota",
        "fusion.402": "jit(loop)/while/body/tick.arbitrate/gather"}
    assert spans._instruction(
        "%fusion.402 = s32[7680]{0:T(1024)} fusion(s32[240,32,8] %a)") \
        == "fusion.402"


def test_old_fixture_summary_is_unchanged():
    s = trace.reduce(trace.load_xplane(OLD), "sweep")
    assert (s.span_ns, s.busy_ns, s.n_devices) == (53059529, 5654391.0, 1)
    assert len(s.op_ns) == 250 and sum(s.op_ns.values()) == 5654391
    assert s.gaps[:3] == [
        ("sweep/PjitFunction(convert_element_type)", 12087746),
        ("sweep/DevicePutWithSharding", 11162232),
        ("sweep/$array.py:631 _value", 4961660)]
    digest = hashlib.sha256(json.dumps(
        [s.span_ns, s.busy_ns, s.n_devices, sorted(s.op_ns.items()),
         s.gaps]).encode()).hexdigest()
    assert digest == ("27a5364e48392438a844acbb6039f3b50f757b7c"
                      "47b63aa04e4cf27e683dfd67")


# ------------------------------------------------------------ profile run
def test_profile_run_without_chip_refuses(tmp_path):
    assert profile_sweep.profile("ddr3-1333-1ch1r.fig_closed", 1) is None


@pytest.mark.parametrize("traffic", ["tiny_closed", "tiny_open"])
def test_profile_run_on_a_small_cell(tmp_path, traffic):
    """On the CPU the trace has no device plane: the run completes, the
    traced sweep equals the warm one, and no metric is reported."""
    root = make_root(tmp_path)
    res = profile_sweep.profile(f"ddr3-1333-1ch1r.{traffic}", 2 ** 31 + 9,
                                root=str(root),
                                require_chip=False, system=stand_in())
    assert res["same_as_warm"] is True
    assert res["device"]["platform"] == "cpu"
    assert res["metrics"] == {} and res["breakdown"] == {}
    assert res["cost"]["traced_s"] > 0 and res["cost"]["read_s"] > 0
    assert 0 < res["cost"]["inactive_spans_s"] < 1e-3
