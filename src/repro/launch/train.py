"""End-to-end training driver (deliverable b): QAT (FTTQ) LM pretraining
with checkpoint/restart, synthetic token data, and optional mesh execution.

CPU-scale example (~100M params, a few hundred steps):
    PYTHONPATH=src python -m repro.launch.train --preset 100m --steps 300

Production pods would launch the same driver per host with a real mesh
(--mesh single|multi uses forced host devices only for demonstration;
on TPU the same code paths pick up the real topology).

XLA latency-hiding knobs used on real TPU (documented here; harmless on CPU):
    --xla_tpu_enable_latency_hiding_scheduler=true
    --xla_tpu_overlap_compute_collective_tc=true
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_reduced
from repro.data.synthetic import synthetic_tokens, token_batches
from repro.launch.env import configure_compile_cache
from repro.models.transformer import ModelConfig, param_count
from repro.optim import adam, warmup_cosine_schedule
from repro.train import (
    TrainerConfig, init_train_state, make_train_step,
    latest_step, restore_checkpoint, save_checkpoint,
)

PRESETS = {
    # ~100M-param dense LM for the end-to-end example.
    "100m": dict(name="lm-100m", family="dense", n_layers=12, d_model=768,
                 vocab_size=32768, n_heads=12, n_kv_heads=12, d_ff=3072),
    "10m": dict(name="lm-10m", family="dense", n_layers=6, d_model=256,
                vocab_size=8192, n_heads=8, n_kv_heads=4, d_ff=1024),
    "1m": dict(name="lm-1m", family="dense", n_layers=4, d_model=128,
               vocab_size=1024, n_heads=4, n_kv_heads=2, d_ff=512),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="1m", choices=list(PRESETS))
    ap.add_argument("--arch", default=None, help="use a reduced arch config instead")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--no-qat", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    configure_compile_cache()

    if args.arch:
        cfg = get_reduced(args.arch)
    else:
        cfg = ModelConfig(**PRESETS[args.preset])
    print(f"model={cfg.name} params={param_count(cfg) / 1e6:.1f}M "
          f"qat={not args.no_qat}")

    tcfg = TrainerConfig(qat=not args.no_qat, pod_compression=False,
                         microbatches=args.microbatches)
    optimizer = adam(warmup_cosine_schedule(args.lr, 20, args.steps))
    state = init_train_state(cfg, tcfg, optimizer, jax.random.PRNGKey(0))
    step_fn = jax.jit(make_train_step(cfg, tcfg, optimizer))

    toks = synthetic_tokens(jax.random.PRNGKey(1),
                            max(args.batch * (args.seq + 1) * 64, 200_000),
                            vocab=cfg.vocab_size)
    cursor = 0
    start = 0
    if args.resume and args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        state, meta = restore_checkpoint(args.ckpt_dir, example_state=state)
        cursor = meta.get("data_cursor", 0)
        start = meta["step"]
        print(f"resumed from step {start} (cursor={cursor})")
    batches = token_batches(toks, args.batch, args.seq, start=cursor)

    t0 = time.time()
    for i in range(start, args.steps):
        batch, cursor = next(batches)
        state, metrics = step_fn(state, batch)
        if (i + 1) % args.log_every == 0:
            dt = (time.time() - t0) / args.log_every
            tok_s = args.batch * args.seq / dt
            print(f"step {i + 1:5d}  loss={float(metrics['loss']):.4f}  "
                  f"gnorm={float(metrics['grad_norm']):.2f}  "
                  f"{dt * 1e3:.0f} ms/step  {tok_s:.0f} tok/s", flush=True)
            t0 = time.time()
        if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, i + 1, state,
                            metadata={"data_cursor": cursor})
    print("done. final loss:", float(metrics["loss"]))


if __name__ == "__main__":
    main()
