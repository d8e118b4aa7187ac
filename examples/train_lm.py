"""End-to-end LM training driver over the 10-arch zoo.

    # CPU demo: ~5M-param xLSTM, 200 steps, loss visibly decreasing
    PYTHONPATH=src python examples/train_lm.py

    # any zoo arch, reduced config
    PYTHONPATH=src python examples/train_lm.py --arch jamba-v0.1-52b --steps 50

    # full-config on a pod (what launch/train.py + launch/mesh.py target)
    PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m \
        --steps 500 --batch 64 --seq 1024 --ckpt /ckpt --resume auto

This wraps repro.launch.train: sharded params, AdamW, deterministic
resumable data, atomic checkpoints, SIGTERM-graceful preemption. The
smoke configs keep CPU wall-time sane; the same driver lowers the full
configs on the production mesh (see launch/dryrun.py for proof of
compile at 256/512 chips)."""
import argparse
import sys
import tempfile

sys.path.insert(0, "src")

from repro.launch import compile_cache
from repro.launch import train as train_mod


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full-config", action="store_true",
                    help="train the assigned full config (pod-scale!)")
    args = ap.parse_args()

    with tempfile.TemporaryDirectory() as ckpt:
        argv = ["--arch", args.arch,
                "--steps", str(args.steps),
                "--batch", str(args.batch),
                "--seq", str(args.seq),
                "--ckpt", ckpt, "--ckpt-every", str(max(args.steps // 2, 1)),
                "--resume", "auto"]
        if not args.full_config:
            argv.append("--smoke")
        return train_mod.main(argv)


if __name__ == "__main__":
    sys.exit(main())
