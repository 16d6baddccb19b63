"""End-to-end LM training driver: Byzantine-robust cubic Newton training a
language model from the assigned-architecture zoo on synthetic token
streams, with 25% of the data-parallel workers mounting a Gaussian attack.

Default is a CPU-friendly reduced model; pass --preset 100m --steps 300 for
the ~100M-parameter few-hundred-step run on real hardware.

    PYTHONPATH=src python examples/train_lm.py --arch deepseek-moe-16b --steps 40
"""
import argparse

from repro.launch.train import run_training


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-moe-16b")
    ap.add_argument("--preset", default="smoke", choices=["smoke", "100m", "full"])
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--m-workers", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--attack", default="gaussian")
    ap.add_argument("--alpha", type=float, default=0.25)
    args = ap.parse_args()

    _, hist, _ = run_training(
        arch=args.arch,
        preset=args.preset,
        steps=args.steps,
        m_workers=args.m_workers,
        seq_len=args.seq_len,
        attack=args.attack,
        alpha=args.alpha,
        # β > α (build-time validated): trim strictly more than corrupted
        beta=max(args.alpha + 1.0 / args.m_workers, 0.25),
        solver_iters=2,
        ckpt_dir="results/train_lm_ckpt",
    )
    drop = (hist[0] - hist[-1]) / hist[0] * 100
    print(f"loss {hist[0]:.3f} → {hist[-1]:.3f}  ({drop:.1f}% drop under "
          f"{args.attack}@{args.alpha:.0%} attack)")


if __name__ == "__main__":
    main()
