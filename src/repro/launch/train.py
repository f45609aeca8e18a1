"""Training launcher: run any assigned architecture on the local device
pool (TPU slice in production; CPU here) with a chosen parallelism plan.

  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m \
      --technique fsdp --devices 8 --steps 100 --batch 8 --seq 512 \
      [--reduced] [--ckpt /tmp/ck.npz] [--resume]

On a real TPU slice, run one process per host with the same flags; jax
initializes the global device pool and the per-job mesh spans it.
"""
from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--technique", default="fsdp")
    ap.add_argument("--devices", type=int, default=0,
                    help="0 = all local devices")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU smoke scale)")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    import jax

    from ..configs import get_config
    from ..checkpoint.store import (load_checkpoint, load_metadata,
                                    save_checkpoint)
    from ..core.library import ParallelismLibrary
    from ..data.synthetic import SyntheticLM
    from ..optim.adamw import AdamWConfig
    from ..parallelism.build import BuiltJob

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    n_dev = args.devices or len(jax.devices())
    lib = ParallelismLibrary()
    tech = lib.get(args.technique)
    if not tech.search_space(cfg, n_dev):
        raise SystemExit(
            f"{args.technique} invalid for {cfg.name} at {n_dev} devices "
            f"(valid: {[t for t, g in lib.candidates(cfg, [n_dev])]})")
    plan = tech.plan(cfg, n_dev)
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(1, args.steps // 20))
    built = BuiltJob(cfg, plan, opt_cfg, devices=jax.devices()[:n_dev])
    params, opt = built.init(jax.random.PRNGKey(0))
    start = 0
    if args.resume and args.ckpt:
        meta = load_metadata(args.ckpt) or {}
        start = int(meta.get("step", 0))
        state = load_checkpoint(args.ckpt, {"params": params, "opt": opt})
        params, opt = state["params"], state["opt"]
        print(f"resumed from {args.ckpt} at step {start}")

    print(f"{cfg.name}: {args.technique} x{n_dev} devices, "
          f"batch {args.batch} x seq {args.seq}, steps {start}..{args.steps}")
    data = SyntheticLM(cfg, seed=0).batches(
        args.batch, args.seq, num_batches=args.steps - start)
    t0 = time.perf_counter()
    m = {}
    for i, b in enumerate(data, start=start):
        params, opt, m = built.step(params, opt, built.place_batch(b))
        if (i + 1) % args.log_every == 0:
            jax.block_until_ready(m["loss"])
            dt = (time.perf_counter() - t0) / (i + 1 - start)
            print(f"step {i + 1:6d}  loss {float(m['loss']):.4f}  "
                  f"ppl {float(m['perplexity']):.1f}  "
                  f"grad_norm {float(m['grad_norm']):.2f}  "
                  f"{dt * 1e3:.0f} ms/step", flush=True)
    jax.block_until_ready(params)
    if args.ckpt:
        save_checkpoint(args.ckpt, {"params": params, "opt": opt},
                        {"step": args.steps,
                         "loss": float(m.get("loss", float("nan")))})
        print(f"saved {args.ckpt}")


if __name__ == "__main__":
    main()
