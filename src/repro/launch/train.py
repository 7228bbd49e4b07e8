"""Training launcher.

Real run (CPU / real TPU devices):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
      --steps 100 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt

On a real cluster this binary is started once per host (jax.distributed
initializes from the cluster env); the mesh comes from launch/mesh.py and
the data pipeline shards by host id.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointConfig
from repro.common.compile_cache import use_compile_cache
from repro.common.config import get_arch
from repro.core.policy import list_policies
from repro.data import Prefetcher, SyntheticLMData
from repro.models.dims import make_dims
from repro.optim import OptConfig
from repro.train import Trainer, TrainerConfig, make_state, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-interval", type=int, default=25)
    ap.add_argument("--ckpt-policy", default="darp",
                    choices=list_policies())
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    dims = make_dims(cfg, tp=1, param_dtype=jnp.float32,
                     compute_dtype=jnp.float32)
    ocfg = OptConfig(lr=args.lr, warmup_steps=max(2, args.steps // 20),
                     total_steps=args.steps)
    state = make_state(jax.random.PRNGKey(args.seed), cfg, dims, ocfg)
    step_fn = make_train_step(cfg, dims, ocfg, accum=args.accum)
    kind = ("encdec" if cfg.family == "encdec"
            else ("embeds" if cfg.frontend == "embed" else "tokens"))
    data = Prefetcher(iter(SyntheticLMData(
        cfg.vocab_size, batch=args.batch, seq=args.seq, seed=args.seed,
        embed_dim=cfg.d_model, kind=kind)))
    ck = None
    if args.ckpt_dir:
        ck = CheckpointConfig(directory=args.ckpt_dir,
                              interval=args.ckpt_interval,
                              policy=args.ckpt_policy)
    tr = Trainer(TrainerConfig(total_steps=args.steps, ckpt=ck, log_every=10),
                 step_fn, state, data)
    if tr.maybe_restore():
        print(f"restored from step {tr.start_step - 1}")
    out = tr.run()
    data.close()
    print("done:", out)
    for h in tr.history:
        print(f"  step {h['step']:5d} loss {h['loss']:.4f} dt {h['dt']*1e3:.0f}ms")
    if tr.engine:
        print("ckpt stats:", tr.engine.stats)


if __name__ == "__main__":
    main()
