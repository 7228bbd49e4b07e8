"""The chip's peaks, and the least bytes a sweep has to move.

`least_bytes` counts what any implementation of the tick loop must move
through device memory, from the traffic's shapes alone: each scenario's
demand stream read once, and each cell's result written once. Four
bytes for every integer or float field, one for a flag.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")

#: per request of a demand stream: bank, row, subarray and arrival tick
#: (open loop) or think gap (closed loop), and the write flag
REQUEST_BYTES = 4 * 4 + 1
#: per cell: makespan, reads, writes, mean and p99 read latency, per-bank
#: and all-bank refreshes, row hits and misses, energy, largest lag, and
#: the finished flag; closed-loop cells add one finish time per core
CELL_BYTES = 11 * 4 + 1
CORE_BYTES = 4


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of `device_kind`; an unknown kind raises."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}: known {sorted(table)}")
    return table[device_kind]


def least_bytes(traffic) -> int:
    n_req = sum(int(s.is_write.size) for s in traffic.scenarios)
    n_cells = len(traffic.policies) * len(traffic.densities)
    total = n_req * REQUEST_BYTES
    for s in traffic.scenarios:
        cores = s.n_cores if traffic.mode == "closed" else 0
        total += n_cells * (CELL_BYTES + CORE_BYTES * cores)
    return total
