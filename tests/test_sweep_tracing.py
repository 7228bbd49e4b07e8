"""The sweep's device path on the profiler's clock: the host spans of
`sweep(..., backend="jax")`, the counters they carry, and the tick-phase
scopes in the compiled loop, for both modes and for a DRAM with bank
groups.

A small grid is swept once warm, then once more inside a caller's span
under `jax.profiler.trace`; the trace is read back with `ProfileData`.
"""
import glob
import re

import jax
import pytest
from conftest import lpddr4

from repro.core.refresh.timing import timing_for_density
from repro.core.sweep import SweepSpec, jaxbody, sweep
from repro.core.sweep.engine import _Grid, _jax_arbiter

SPANS = ("sweep.grid_build", "sweep.stage", "sweep.tick_loop",
         "sweep.readback", "sweep.finalize")
SCOPES = ("tick.front_end", "tick.refresh", "tick.arbitrate", "tick.serve")
CALLER = "caller"

SPECS = {
    "closed": SweepSpec(policies=("ref_ab", "dsarp", "hira"),
                        scenarios=("closed_mixed",), densities=(8, 32),
                        reqs=160, seed=3, mode="closed"),
    "open": SweepSpec(policies=("ref_pb", "darp", "elastic"),
                      scenarios=("mixed", "write_burst_draining"),
                      densities=(32,), reqs=120, seed=3),
    # 2 ranks x 4 groups of 2 banks: 4 same-bank sets per cell
    "groups": SweepSpec(
        policies=("ref_pb", "dsarp"), scenarios=("closed_multirank",),
        densities=(32,), reqs=160, seed=3, mode="closed", n_ranks=2,
        n_bank_groups=4, timing={32: timing_for_density(
            32, n_ranks=2, n_bank_groups=4, tCCD_L=9.0, tCCD_S=6.0)}),
    # 2 channels x 2 ranks of LPDDR4 with the per-bank issue rules
    "lpddr4": SweepSpec(
        policies=("ref_pb", "darp"), scenarios=("closed_multirank",),
        densities=(32,), reqs=160, seed=3, mode="closed", dt_ns=5.0,
        n_ranks=2, n_channels=2,
        timing={32: lpddr4(32, n_ranks=2, n_channels=2)}),
}
#: refresh units and bank groups each spec's cells have
UNITS = {"closed": (8, 1), "open": (8, 1), "groups": (4, 4),
         "lpddr4": (32, 1)}
#: the stream lanes each spec's loop picks a core's next request over:
#: the closed specs' N (160 requests over 4 and 8 cores); open mode has
#: no streams
PICK_LANES = {"closed": 40, "open": 0, "groups": 20, "lpddr4": 20}
#: the per-bank issue rules: tpbR2pbR in ticks (90 ns of 5 ns ticks) and
#: refresh rounds (1) where the spec's DRAM states them
REFPB_RULES = {"closed": (0, 0), "open": (0, 0), "groups": (0, 0),
               "lpddr4": (18, 1)}


def _final_t(spec) -> int:
    """The times the loop runs on `spec`, read from its final state."""
    cfg, cst, s0 = jaxbody.program(_Grid(spec))
    return int(jaxbody.run_loop(cfg, cst, _jax_arbiter("jnp"), s0)["t"])


@pytest.fixture(scope="module", params=sorted(SPECS))
def traced(request, tmp_path_factory):
    """(spec name, results, [(name, start, end, stats)] of the caller's span
    and every `sweep.*` span, in start order)."""
    spec = SPECS[request.param]
    sweep(spec, backend="jax")                    # compile outside the trace
    logdir = str(tmp_path_factory.mktemp(f"trace_{request.param}"))
    with jax.profiler.trace(logdir):
        with jax.profiler.TraceAnnotation(CALLER):
            res = sweep(spec, backend="jax")
    path, = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    events = [
        (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
        for pl in jax.profiler.ProfileData.from_file(path).planes
        for ln in pl.lines for e in ln.events
        if e.name == CALLER or e.name.startswith("sweep.")]
    events.sort(key=lambda ev: (ev[1], -ev[2]))
    return request.param, res, events


def test_spans_once_each_in_order_disjoint_inside_caller(traced):
    _, _, events = traced
    (caller, c0, c1, _), *spans = events
    assert caller == CALLER
    assert tuple(n for n, *_ in spans) == SPANS
    for _, s, e, _ in spans:
        assert c0 <= s <= e <= c1
    for (_, _, end, _), (_, start, _, _) in zip(spans, spans[1:]):
        assert end <= start


def test_finalize_carries_cells_and_loop_iterations(traced):
    name, res, events = traced
    stats = {n: st for n, _, _, st in events}
    assert all(not stats[n] for n in SPANS[:-1])
    counters = stats["sweep.finalize"]
    assert set(counters) == {"cells", "loop_iterations", "refresh_units",
                             "bank_groups", "stream_pick_lanes",
                             "refpb_spacing_ticks", "refpb_rounds"}
    assert counters["cells"] == len(res.cells) == len(SPECS[name].cells())
    assert counters["loop_iterations"] == _final_t(SPECS[name])
    assert (counters["refresh_units"],
            counters["bank_groups"]) == UNITS[name]
    assert counters["stream_pick_lanes"] == PICK_LANES[name]
    assert (counters["refpb_spacing_ticks"],
            counters["refpb_rounds"]) == REFPB_RULES[name]
    if SPECS[name].mode == "closed":
        assert PICK_LANES[name] == _Grid(SPECS[name]).N
    ticks = [round(c.makespan / SPECS[name].dt_ns) for c in res.cells]
    if SPECS[name].mode == "closed":
        # a core finishes at the tick its last request retires: the loop
        # runs until the slowest cell's last core has finished
        assert counters["loop_iterations"] > max(ticks)
    else:
        # the loop stops once the last request has started; an open
        # cell's makespan also holds that request's latency
        assert counters["loop_iterations"] <= max(ticks)
        assert counters["loop_iterations"] + 4096 > max(ticks)


@pytest.mark.parametrize("mode", sorted(SPECS))
def test_compiled_loop_carries_every_tick_scope(mode):
    cfg, cst, s0 = jaxbody.program(_Grid(SPECS[mode]))
    hlo = jaxbody.run_loop.lower(cfg, cst, _jax_arbiter("jnp"),
                                 s0).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope
