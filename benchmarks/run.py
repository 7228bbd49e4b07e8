"""Benchmark harness: one entry per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV (per the repo convention); detailed
dicts go to results/bench/*.json.

  fig1  paper Fig.1: perf loss of REF_ab/REF_pb vs ideal across densities
        (closed-loop weighted speedup — the paper's metric)
  fig2  paper Fig.2: SARP service-timeline (read behind refresh)
  fig3  paper Fig.3: DSARP perf+energy vs baselines (closed-loop ws)
  sweep_grid     batched sweep engine: timed open-loop policy x scenario
                 x density grid vs the scalar tick oracle + legacy
                 DramSim loop
  sweep_closed_loop   closed-loop grid vs looping DramSim.run_ticks per
                 cell, with the bit_identical conformance flag
  sweep_multirank     the [channel, rank, bank] hierarchy: closed grid
                 at n_ranks in {1,2,4}, bit_identical per rank count,
                 per-rank-count weighted speedup vs ideal
  sweep_subarray      the [bank, subarray] hierarchy: subarray-storm grid
                 at n_subarrays in {1,4,8}, bit_identical per subarray
                 count, per-count weighted speedup vs ideal
  sweep_mega     the fused Pallas tick-loop megakernel: giga-sweep
                 ladder (10^3/10^4/10^5 cells) vs the jitted
                 lax.while_loop backend, 1/2/4-way shard_map,
                 bit_identical spot checks, warm-kernel regression
                 guard vs the batched backend on the 8x8x3 grid
  command_trace  command layer: DFI-trace emission overhead (enabled vs
                 disabled run_ticks), validator violations, round-trip
                 bit_identical flag
  darp_ckpt      framework DARP: checkpoint flush scheduling overhead
  serving        framework DARP: serving maintenance policies (legacy shim)
  serving_lifecycle   EngineCore request lifecycle: TTFT/TPOT percentiles
                 under a mixed-prompt batch with chunked prefill
  serving_cosim  serving <-> DRAM co-sim: scenario KV page traffic
                 replayed through DramSim per refresh policy; tick-space
                 TTFT/TPOT p99 orderings (dsarp<=darp<=ref_pb<=all_bank)
                 and the bit-identical replay pin
  sarp_bytes     framework SARP: fused vs serial paged-attn HBM traffic
  kernel_micro   CPU reference micro-latencies

`docs/figures.md` maps every emitted artifact to its paper figure.
"""
from __future__ import annotations

import json
import os
import sys
import time

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "bench")


def _emit(name: str, us: float, derived: str, payload) -> None:
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, f"{name}.json"), "w") as f:
        json.dump(payload, f, indent=1, default=str)
    print(f"{name},{us:.1f},{derived}")


def main() -> None:
    fast = "--fast" in sys.argv
    # the grid figures run through the batched sweep engine, so the
    # per-cell load no longer needs to shrink much in --fast mode; the
    # closed-loop demand must still span several tREFI intervals or
    # all-bank refresh barely fires
    reqs = 800 if fast else 2000

    from benchmarks import fig_refresh as FR
    from benchmarks import bench_framework as BF
    from repro.common.compile_cache import use_compile_cache

    use_compile_cache()

    t0 = time.perf_counter()
    runs = FR.fig_grids(reqs=reqs)     # one sweep set feeds fig1 AND fig3
    f1 = FR.fig1(reqs=reqs, runs=runs)
    _emit("fig1_refresh_loss", (time.perf_counter() - t0) * 1e6,
          f"refpb_loss_32gb={f1[32]['ref_pb']:.3f};"
          f"refab_loss_32gb={f1[32]['ref_ab']:.3f}", f1)

    t0 = time.perf_counter()
    f2 = FR.fig2()
    _emit("fig2_sarp_timeline", (time.perf_counter() - t0) * 1e6,
          f"refpb_p99={f2['ref_pb']['p99_read_ns']:.0f}ns;"
          f"sarp_p99={f2['sarp_pb']['p99_read_ns']:.0f}ns;"
          f"sarp_overlapped_serves="
          f"{f2['sarp_pb']['serves_during_sibling_refresh']}", f2)

    t0 = time.perf_counter()
    f3 = FR.fig3(reqs=reqs, runs=runs)
    _emit("fig3_dsarp", (time.perf_counter() - t0) * 1e6,
          f"dsarp_impr_32gb={f3[32]['dsarp']['improvement_vs_refab']:.3f};"
          f"dsarp_energy_vs_refab={f3[32]['dsarp']['energy_vs_refab']:.3f}",
          f3)

    t0 = time.perf_counter()
    sg = FR.sweep_grid(fast=fast)
    _emit("sweep_grid", (time.perf_counter() - t0) * 1e6,
          f"vs_dramsim_loop={sg['speedup_vs_dramsim_loop']}x;"
          f"vs_scalar_tick={sg['speedup_vs_scalar_tick']}x;"
          f"bit_identical={sg['bit_identical']}", sg)

    t0 = time.perf_counter()
    cl = FR.closed_loop(fast=fast)
    _emit("sweep_closed_loop", (time.perf_counter() - t0) * 1e6,
          f"vs_dramsim_ticks={cl['speedup_vs_dramsim_ticks']}x;"
          f"bit_identical={cl['bit_identical']}", cl)

    t0 = time.perf_counter()
    mr = FR.sweep_multirank(fast=fast)
    ws2 = mr["per_rank_count"][2]["weighted_speedup_vs_ideal"]
    _emit("sweep_multirank", (time.perf_counter() - t0) * 1e6,
          f"bit_identical={mr['bit_identical']};"
          f"dsarp_ws_2rank_32gb={ws2['dsarp'][32]};"
          f"refab_ws_2rank_32gb={ws2['ref_ab'][32]}", mr)

    t0 = time.perf_counter()
    ss = FR.sweep_subarray(fast=fast)
    ws8 = ss["per_subarray_count"][8]["weighted_speedup_vs_ideal"]
    _emit("sweep_subarray", (time.perf_counter() - t0) * 1e6,
          f"bit_identical={ss['bit_identical']};"
          f"sarp_ws_8sub_32gb={ws8['sarp_pb'][32]};"
          f"refpb_ws_8sub_32gb={ws8['ref_pb'][32]}", ss)

    t0 = time.perf_counter()
    sm = FR.sweep_mega(fast=fast)
    top = sm["ladder"][-1]
    _emit("sweep_mega", (time.perf_counter() - t0) * 1e6,
          f"cells={top['cells']};"
          f"mega_cells_per_s={top['mega_cells_per_s']};"
          f"vs_jax={top['speedup_vs_jax']}x;"
          f"fused_beats_batched="
          f"{sm['ref_grid_8x8x3']['fused_beats_batched']};"
          f"bit_identical={sm['bit_identical']}", sm)

    t0 = time.perf_counter()
    ct = FR.command_trace(fast=fast)
    _emit("command_trace", (time.perf_counter() - t0) * 1e6,
          f"overhead_pct={ct['overhead_pct']};"
          f"violations={ct['violations']};"
          f"bit_identical={ct['bit_identical']}", ct)

    t0 = time.perf_counter()
    ck = BF.bench_darp_ckpt(steps=20 if fast else 40)
    _emit("darp_ckpt", ck["darp"]["mean_step_ms"] * 1e3,
          f"darp_overhead={ck['darp']['overhead_pct']}%;"
          f"sync_overhead={ck['all_bank']['overhead_pct']}%", ck)

    t0 = time.perf_counter()
    sv = BF.bench_serving(n_requests=4 if fast else 6,
                          max_new=12 if fast else 24,
                          policies=FR.SERVING_POLICIES)
    _emit("serving_policies", (time.perf_counter() - t0) * 1e6,
          f"darp_stalls={sv['darp']['forced_stalls']};"
          f"allbank_stalls={sv['all_bank']['forced_stalls']};"
          f"darp_tps={sv['darp']['tok_per_s']}", sv)

    t0 = time.perf_counter()
    sl = BF.bench_serving_lifecycle(n_requests=4 if fast else 6,
                                    max_new=8 if fast else 12)
    _emit("serving_lifecycle", (time.perf_counter() - t0) * 1e6,
          f"darp_ttft_p50_ms={sl['darp']['ttft']['p50_ms']};"
          f"darp_tpot_p50_ms={sl['darp']['tpot']['p50_ms']};"
          f"prefill_calls={sl['darp']['prefill_calls']};"
          f"decode_calls={sl['darp']['decode_calls']}", sl)

    t0 = time.perf_counter()
    # fast mode trims the policy sweep, not the request count — the p99
    # orderings only stabilize at a few hundred requests
    sc = BF.bench_serving_cosim(
        n_requests=200, scenario="serving_bursty",
        policies=(("darp", "all_bank") if fast
                  else ("dsarp", "darp", "ref_pb", "all_bank")))
    _emit("serving_cosim", (time.perf_counter() - t0) * 1e6,
          f"ttft_p99_ordered={sc['ttft_p99_ordered']};"
          f"tpot_p99_ordered={sc['tpot_p99_ordered']};"
          f"stall_ordered={sc['stall_ordered']};"
          f"bit_identical={sc['bit_identical']};"
          f"darp_ttft_p99={sc['darp']['ttft_ticks']['p99']};"
          f"allbank_ttft_p99={sc['all_bank']['ttft_ticks']['p99']}", sc)

    sb = BF.bench_sarp_bytes()
    _emit("sarp_decode_bytes", 0.0,
          f"serial_over_fused={sb['serial_over_fused']:.1f}x;"
          f"bf16_over_fused={sb['bf16_over_fused']:.1f}x", sb)

    km = BF.bench_kernel_micro()
    _emit("kernel_micro", km["flash_ref_us"],
          f"ssd={km['ssd_ref_us']}us;quant={km['kv_quant_us']}us", km)


if __name__ == "__main__":
    main()
