"""The peaks table and the least bytes behind `tick_loop_roofline`."""
import json
import os

import pytest
from _bench_helpers import REPO

from bench import harness, roofline
from bench.traffic import build


def test_v5e_peaks():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 8.19e11
    assert p["bf16_flops_per_s"] == 1.97e14


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")


def test_fig_closed_least_bytes_by_hand():
    with open(os.path.join(REPO, "bench", "workloads",
                           "fig_closed.json")) as f:
        mix = json.load(f)
    with open(os.path.join(REPO, "bench", "configs",
                           "ddr3-1333-1ch1r.json")) as f:
        config = json.load(f)
    # 5 scenarios x 4 cores x 500 requests, each 4 int32 fields + a flag:
    streams = 5 * 4 * 500 * (4 * 4 + 1)            # 170,000
    # 8 policies x 5 scenarios x 3 densities cells, each 11 four-byte
    # fields + the finished flag + 4 cores' finish times:
    results = 8 * 5 * 3 * (11 * 4 + 1 + 4 * 4)     # 7,320
    traffic = build(mix, config, 0, harness.load_reference(config))
    assert roofline.least_bytes(traffic) \
        == streams + results == 177_320
