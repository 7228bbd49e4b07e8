"""Serving launcher: the request-lifecycle EngineCore over the paged int8
KV cache, with registry-resolved maintenance policies and per-request
TTFT/TPOT metrics.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --requests 8 --new 16 --policy darp --mixed

Exits non-zero if the engine times out before draining (livelock is never
masked), which makes this the CI serving smoke.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp

from repro.common.compile_cache import use_compile_cache
from repro.common.config import get_arch
from repro.core.policy import list_policies
from repro.kvcache import PagedKVConfig
from repro.models.api import get_model
from repro.models.dims import make_dims
from repro.serving import EngineConfig, EngineCore


def prompts(n: int, mixed: bool, vocab: int):
    """Deterministic prompt set; --mixed varies lengths (3..32 tokens) the
    way a real arrival mix would."""
    lens = [3 + (11 * i) % 30 for i in range(n)] if mixed else [3] * n
    return [[1 + i] + [(5 * j + i) % max(2, vocab - 1) + 1
                       for j in range(l - 1)]
            for i, l in enumerate(lens)]


def build_engine(cfg, *, policy: str = "darp", seed: int = 0,
                 page_size: int = 4, chunk: int = 8, max_queue: int = 64,
                 decode_fn=None) -> EngineCore:
    """An `EngineCore` serving `cfg` with f32 weights initialised from
    `seed` (no checkpoint is read). `decode_fn` replaces the decode
    forward, with the same signature."""
    dims = make_dims(cfg, tp=1, param_dtype=jnp.float32,
                     compute_dtype=jnp.float32)
    params = get_model(cfg).init(jax.random.PRNGKey(seed), cfg, dims)
    kv_cfg = PagedKVConfig(
        n_layers=cfg.n_layers, n_kv_heads=dims.n_kv,
        head_dim=cfg.attention.head_dim, page_size=page_size,
        n_pages=256, n_staging=24, n_groups=4, max_seqs=8)
    return EngineCore(params, cfg, dims, kv_cfg, EngineConfig(
        max_batch=4, policy=policy, max_queue=max_queue,
        prefill_chunk=chunk), decode_fn=decode_fn)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--new", type=int, default=16)
    ap.add_argument("--policy", default="darp", choices=list_policies())
    ap.add_argument("--page-size", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=8,
                    help="prompt tokens per batched prefill round")
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--mixed", action="store_true",
                    help="mixed prompt lengths (3..32 tokens)")
    ap.add_argument("--max-rounds", type=int, default=2000)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    eng = build_engine(cfg, policy=args.policy, seed=args.seed,
                       page_size=args.page_size, chunk=args.chunk,
                       max_queue=args.max_queue)
    handles = [eng.submit(p, args.new, rid=i)
               for i, p in enumerate(prompts(args.requests, args.mixed,
                                              cfg.vocab_size))]
    t0 = time.perf_counter()
    eng.run_until_done(max_rounds=args.max_rounds)
    wall = time.perf_counter() - t0
    summ = eng.metrics_summary()
    print(f"policy={args.policy} tokens={eng.stats['tokens']} "
          f"tok/s={eng.stats['tokens']/wall:.1f} "
          f"forced_stalls={eng.stats['stall_rounds']} "
          f"evictions={eng.stats['evictions']} "
          f"prefill_calls={eng.stats['prefill_calls']} "
          f"decode_calls={eng.stats['decode_calls']}")
    print(f"ttft_ms p50={summ['ttft']['p50_ms']} p99={summ['ttft']['p99_ms']} "
          f"| tpot_ms p50={summ['tpot']['p50_ms']} "
          f"p99={summ['tpot']['p99_ms']} | cache={eng.cache.stats}")
    for h in handles:
        print(f"  rid={h.rid} state={h.state.value} prompt={len(h.prompt)} "
              f"tokens={len(h.tokens)} ttft={h.ttft*1e3:.1f}ms")
    if eng.stats["timed_out"]:
        print("TIMED OUT before draining", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
