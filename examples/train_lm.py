"""Train a reduced-config LM on synthetic tokens and watch the loss drop,
with a mid-run checkpoint + resume (the fault-tolerance path).

    PYTHONPATH=src python examples/train_lm.py [--arch h2o-danube-1.8b]
"""
import argparse
import tempfile

from repro.launch.train import main as train_main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-1.8b")
    ap.add_argument("--steps", type=int, default=120)
    args = ap.parse_args()

    with tempfile.TemporaryDirectory() as d:
        print(f"== training {args.arch} (reduced config) for "
              f"{args.steps} steps ==")
        train_main(["--arch", args.arch, "--smoke",
                    "--steps", str(args.steps), "--batch", "8",
                    "--seq", "256", "--lr", "1e-3",
                    "--ckpt-dir", d, "--ckpt-every", "40"])
        print("\n== simulated preemption: resuming from the checkpoint ==")
        train_main(["--arch", args.arch, "--smoke",
                    "--steps", str(args.steps + 30), "--batch", "8",
                    "--seq", "256", "--lr", "1e-3",
                    "--ckpt-dir", d, "--ckpt-every", "40"])


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
