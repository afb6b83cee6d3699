"""Quickstart: train the bench LM on the synthetic corpus (CPU).

    PYTHONPATH=src python examples/quickstart.py --steps 250

Produces results/bench_lm_ckpt/.
Training is fault-tolerant: rerunning resumes from the latest checkpoint;
`--fail-at-step N` demonstrates the injected-failure restart drill.
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.configs.paper_llama import bench_lm  # noqa: E402
from repro.data.pipeline import DataConfig  # noqa: E402
from repro.launch.train import train_loop  # noqa: E402
from repro.training.optimizer import AdamWConfig  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=250)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default="results/bench_lm_ckpt")
    ap.add_argument("--fail-at-step", type=int, default=None)
    args = ap.parse_args()

    cfg = bench_lm()
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          batch_size=args.batch)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=20,
                          total_steps=args.steps)
    _, _, hist = train_loop(
        cfg, data_cfg, opt_cfg, steps=args.steps, ckpt_dir=args.ckpt,
        ckpt_every=50, fail_at_step=args.fail_at_step)
    print(f"[quickstart] loss {hist[0]['loss']:.3f} -> "
          f"{hist[-1]['loss']:.3f} over {len(hist)} steps")


if __name__ == "__main__":
    main()
