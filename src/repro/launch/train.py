"""Training launcher: ``python -m repro.launch.train --arch <id> [--smoke]``.

Runs the end-to-end training loop (data pipeline → jitted hyperstep →
checkpoint/restart) on the local devices. ``--smoke`` selects the reduced
same-family config (CPU-runnable); the published configs need an accelerator
(``chip_smoke.py`` runs one on a TPU) or the dry-run (``repro.launch.dryrun``).
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import get_config
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.optim.adamw import AdamW
from repro.optim.schedule import linear_warmup_cosine, wsd
from repro.train.loop import TrainConfig, train


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    # minicpm's distinctive recipe is WSD; everything else gets cosine
    sched = (wsd(args.lr, warmup=10, total=args.steps)
             if args.arch == "minicpm-2b"
             else linear_warmup_cosine(args.lr, warmup=10, total=args.steps))
    opt = AdamW(schedule=sched)
    tcfg = TrainConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                       ckpt_every=args.ckpt_every, seed=args.seed)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                      global_batch=args.batch, seed=args.seed)
    out = train(cfg, tcfg, opt, data_cfg=data)
    final = out["history"][-1]
    row = out["plan_row"] or {}
    print(f"[done] arch={args.arch} steps={args.steps} "
          f"final_loss={final['loss']:.4f} devices={len(jax.devices())} "
          f"stragglers={len(out['stragglers'])}")
    if row:
        print(f"[predicted_vs_measured] pred={row['predicted_seconds']:.4g}s "
              f"meas={row['measured_seconds']:.4g}s "
              f"ratio={row['pred_over_meas']:.3g} "
              f"bw_heavy pred={row['bandwidth_heavy_predicted']:.0f} "
              f"meas={row['bandwidth_heavy_measured']:.0f}")


if __name__ == "__main__":
    main()
