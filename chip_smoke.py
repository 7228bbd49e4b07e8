"""Smoke run of the main paths on one TPU chip: the sweep engine's device
path, and `EngineCore` serving qwen2-0.5b at its published widths.

  python chip_smoke.py [--seed N]

One process; it starts no other. Without a TPU it exits non-zero before
running anything and prints no result: it never falls back to the CPU.
The phases, each of which raises when a check fails:

  sweep_closed  the paper's closed-loop figure grid (`fig_grids`: 8
                policies x 5 closed scenarios x 3 densities, 2000
                requests, seed 0) on `sweep(..., backend="jax")`, every
                CellResult compared bit for bit with backend="batched"
                (host numpy);
  sweep_open    the same check on `sweep_grid`'s open-loop grid (8 x 8 x
                3, 400 requests, seed 0);
  serve         EngineCore built as `repro.launch.serve` builds it, at
                qwen2-0.5b's published widths with weights drawn from
                `--seed`: 4 prompts of 3-32 tokens, 8 new tokens each,
                under the darp policy. Every request must end DONE with 8
                tokens, the engine must not time out, and every decode
                step's logits must be finite.

Each phase runs twice, cold then warm, and prints one line: its size, the
seconds spent in backend compiles, the cold and warm run seconds, and the
device's peak bytes in use. Those are smoke timings of one run, not
benchmark metrics. The last line is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.fig_refresh import (CLOSED_FIG_SCENARIOS, DENSITIES,  # noqa: E402
                                    FIG3_POLICIES, GRID_POLICIES,
                                    GRID_SCENARIOS)
from repro.common.compile_cache import use_compile_cache  # noqa: E402
from repro.common.config import get_arch  # noqa: E402
from repro.core.sweep import SweepSpec, sweep  # noqa: E402
from repro.launch.serve import build_engine, prompts  # noqa: E402
from repro.serving import RequestState  # noqa: E402
from repro.serving.paged_decode import paged_decode_forward  # noqa: E402

#: the event JAX records for each backend (XLA) compile
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

#: new tokens per served request
NEW_TOKENS = 8

CLOSED_GRID = SweepSpec(policies=FIG3_POLICIES,
                        scenarios=CLOSED_FIG_SCENARIOS, densities=DENSITIES,
                        reqs=2000, seed=0, mode="closed")
OPEN_GRID = SweepSpec(policies=GRID_POLICIES, scenarios=GRID_SCENARIOS,
                      densities=DENSITIES, reqs=400, seed=0)


@contextlib.contextmanager
def _compile_seconds():
    """Yields a one-element list that sums backend compile seconds."""
    spent = [0.0]

    def listen(event, secs, **_):
        if event == BACKEND_COMPILE:
            spent[0] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield spent
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def _peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def sweep_phase(spec: SweepSpec) -> dict:
    """`spec` on backend="jax", cold then warm, each compared cell by cell
    with backend="batched"."""
    with _compile_seconds() as spent:
        t0 = time.perf_counter()
        cold = sweep(spec, backend="jax")
        t1 = time.perf_counter()
        warm = sweep(spec, backend="jax")
        t2 = time.perf_counter()
    ref = sweep(spec, backend="batched")
    bad = [(r.policy, r.scenario, r.density_gb)
           for c, w, r in zip(cold.cells, warm.cells, ref.cells)
           if c != r or w != r]
    if bad or len(cold.cells) != len(ref.cells):
        raise AssertionError(
            f"backend='jax' differs from backend='batched' in {len(bad)} "
            f"of {len(ref.cells)} cells, first {bad[:4]}")
    return {"cells": len(ref.cells), "reqs": spec.reqs,
            "compile_s": spent[0], "cold_s": t1 - t0, "warm_s": t2 - t1,
            "bit_identical": True, "peak_bytes_in_use": _peak_bytes()}


def serve_phase(cfg, *, n_requests: int = 4, seed: int = 0) -> dict:
    """Serve `n_requests` mixed prompts of `NEW_TOKENS` new tokens each
    under darp, through a fresh engine twice, cold then warm; both runs
    must finish every request and agree token for token."""
    finite = []

    def decode(*args):
        logits, k_new, v_new = paged_decode_forward(*args)
        finite.append(bool(jnp.isfinite(logits[:, :cfg.vocab_size]).all()))
        return logits, k_new, v_new

    runs = []
    with _compile_seconds() as spent:
        for _ in range(2):
            eng = build_engine(cfg, policy="darp", seed=seed,
                               decode_fn=decode)
            handles = [eng.submit(p, NEW_TOKENS, rid=i) for i, p in enumerate(
                prompts(n_requests, True, cfg.vocab_size))]
            t0 = time.perf_counter()
            eng.run_until_done(max_rounds=2000)
            secs = time.perf_counter() - t0
            if eng.stats["timed_out"]:
                raise AssertionError("EngineCore timed out before draining")
            bad = [(h.rid, h.state.value, len(h.tokens)) for h in handles
                   if h.state is not RequestState.DONE
                   or len(h.tokens) != NEW_TOKENS]
            if bad:
                raise AssertionError(
                    f"requests not DONE with {NEW_TOKENS} tokens: {bad}")
            runs.append((secs, [h.tokens for h in handles]))
            del eng, handles
    if not finite or not all(finite):
        raise AssertionError(
            f"non-finite decode logits in {finite.count(False)} of "
            f"{len(finite)} decode steps")
    if runs[0][1] != runs[1][1]:
        raise AssertionError("cold and warm runs served different tokens")
    return {"requests": n_requests, "new_tokens": NEW_TOKENS,
            "policy": "darp",
            "compile_s": spent[0], "cold_s": runs[0][0],
            "warm_s": runs[1][0], "decode_steps": len(finite),
            "peak_bytes_in_use": _peak_bytes()}


def _report(phase: str, out: dict) -> None:
    print(f"smoke {phase} " + " ".join(f"{k}={v}" for k, v in out.items()),
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the served model's random weights")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (device 0 is {dev.platform!r})"
              "; nothing was run", file=sys.stderr)
        return 1
    print(f"compile cache: {use_compile_cache()}", flush=True)
    print(f"smoke timings of one run on {dev.device_kind}, not benchmark "
          "metrics", flush=True)
    _report("sweep_closed", sweep_phase(CLOSED_GRID))
    _report("sweep_open", sweep_phase(OPEN_GRID))
    _report("serve", serve_phase(get_arch("qwen2-0.5b"), seed=args.seed))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
