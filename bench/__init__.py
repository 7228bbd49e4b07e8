"""The benchmark of the sweep engine's device path: harness, traffic,
plain reference, trace reduction and per-layer metric readers. See
`bench/harness.py` and `PERF.md`."""
