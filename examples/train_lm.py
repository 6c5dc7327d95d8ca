"""End-to-end LM training driver (~100M-param config, CPU-runnable demo).

Trains a trimmed qwen2.5-family model on the deterministic synthetic stream
with the full production loop: AdamW + cosine schedule, remat, gradient
accumulation, async atomic checkpointing, preemption handler, straggler
watchdog, and auto-resume.  Loss visibly drops within ~30 steps.

At full scale the same loop runs under ``launch/mesh.make_production_mesh``
with FSDP+TP shardings (exercised by the dry-run) — nothing here changes.

  PYTHONPATH=src python examples/train_lm.py --steps 30
  # kill it mid-run and re-run: it resumes from the last checkpoint.
"""
import argparse
import dataclasses

from repro.configs import get
from repro.launch.train import train


def main():
    from repro.compile_cache import configure_caches

    configure_caches()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--hundred-m", action="store_true",
                    help="use a ~100M-param config instead of the smoke "
                         "config (minutes per step on CPU)")
    args = ap.parse_args()

    cfg = get("qwen2.5-14b").reduced()
    if args.hundred_m:
        cfg = dataclasses.replace(
            cfg, n_layers=8, d_model=768, n_heads=12, n_kv_heads=4,
            head_dim=64, d_ff=2048, vocab=32768)   # ~0.1B params
    print(f"training {cfg.name} variant: ~{cfg.param_count()/1e6:.1f}M params")

    _, losses = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                      ckpt_dir=args.ckpt_dir, ckpt_every=10,
                      grad_accum=args.grad_accum, peak_lr=3e-3)
    print(f"loss: {losses[0]:.3f} -> {losses[-1]:.3f}")


if __name__ == "__main__":
    main()
