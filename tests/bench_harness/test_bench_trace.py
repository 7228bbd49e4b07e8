"""The reduction from a profiler trace to device-busy time, idle share,
the time of each operation and the idle gaps labelled by host span: by
hand on a made-up trace, and on a small trace recorded on a TPU v5 lite
(a warm 1-cell sweep inside the harness span `sweep`)."""
import os

import pytest
from _bench_helpers import REPO

from bench import trace
from bench.trace import Plane

FIXTURE = os.path.join(REPO, "tests", "bench_harness", "fixtures",
                       "tiny_sweep.xplane.pb")


def _planes(*devices):
    host = Plane("/host:CPU", {"python": [
        ("sweep", 1000, 9000), ("pjit", 1500, 1100),
        ("device_get", 6000, 3000), ("after", 12000, 10)]})
    return [host] + [Plane(f"/device:TPU:{i}", {trace.OPS_LINE: ops,
                                                "XLA Modules": [("m", 0, 1)]})
                     for i, ops in enumerate(devices)]


def test_busy_union_idle_and_labelled_gaps_by_hand():
    s = trace.reduce(_planes([("fusion.1", 500, 1500), ("fusion.2", 3000, 1000),
                              ("while", 3500, 1000), ("copy", 9500, 1000)]),
                     "sweep")
    # clipped to [1000, 10000]: [1000,2000] + [3000,4500] + [9500,10000]
    assert s.span_ns == 9000 and s.busy_ns == 3000 and s.n_devices == 1
    assert s.idle_share == pytest.approx(2 / 3)
    assert s.op_ns == {"fusion.1": 1000, "fusion.2": 1000, "while": 1000,
                       "copy": 500}
    assert s.gaps == [("sweep/device_get", 5000), ("sweep/pjit", 1000)]


def test_nested_ops_count_their_own_time():
    """A loop's body ops run inside the loop's own event: the loop is
    counted by the time its body leaves, the union by the loop."""
    s = trace.reduce(_planes([("while.1", 2000, 6000), ("fusion.1", 2500, 1000),
                              ("fusion.2", 4000, 1500), ("fusion.1", 6000, 500),
                              ("copy", 8500, 500)]), "sweep")
    assert s.busy_ns == 6000 + 500
    assert s.op_ns == {"while.1": 3000, "fusion.1": 1500, "fusion.2": 1500,
                       "copy": 500}
    assert sum(s.op_ns.values()) == s.busy_ns


def test_busy_is_averaged_over_devices():
    s = trace.reduce(_planes([("a", 1000, 3000)], [("b", 0, 20000)]),
                     "sweep")
    assert s.n_devices == 2 and s.busy_ns == (3000 + 9000) / 2


def test_missing_span_raises():
    with pytest.raises(LookupError):
        trace.reduce(_planes([]), "window")


def test_recorded_tpu_trace():
    s = trace.reduce(trace.load_xplane(FIXTURE), "sweep")
    assert s.n_devices == 1
    assert 0 < s.busy_ns < s.span_ns
    assert 0 < s.idle_share < 1
    assert sum(s.op_ns.values()) >= s.busy_ns
    assert s.gaps and all(label.startswith("sweep") for label, _ in s.gaps)
    assert s.gaps == sorted(s.gaps, key=lambda g: -g[1])
