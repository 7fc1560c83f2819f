"""Smoke tier: train every example on the hermetic CPU mesh.

Mirrors the role of the reference's tests/multi_gpu_tests.sh (train ~40
example models end-to-end in CI, DP-only, small budgets): each script
runs in its own process on an 8-device virtual CPU mesh with tiny
epochs/batch so the whole tier finishes in minutes, and a non-zero exit
from any script fails the tier.

Run: python examples/run_all.py [--only SUBSTR] [--timeout SECONDS]
"""
import argparse
import os
import re
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

# script (relative to examples/) -> extra args tuned for a CPU smoke
# run.  Native scripts run DP-only, mirroring multi_gpu_tests.sh's
# batch=64*GPUs DP-only convention (an unbounded Unity search on the
# wide multi-tower models runs for tens of minutes on CPU); the
# pytorch leg keeps a small MCMC budget so the search path stays
# exercised end-to-end.
_DP = ["--only-data-parallel"]
SCRIPTS = {
    "python/native/mlp.py": ["-e", "2", *_DP],
    "python/native/alexnet_cifar10.py": ["-e", "1", "-b", "32", *_DP],
    "python/native/resnet.py": ["-e", "1", "-b", "8", *_DP],
    "python/native/inception.py": ["-e", "1", "-b", "8", *_DP],
    "python/native/resnext.py": ["-e", "1", "-b", "8", *_DP],
    "python/native/dlrm.py": ["-e", "1", "-b", "32", *_DP],
    "python/native/xdl.py": ["-e", "1", "-b", "32", *_DP],
    "python/native/candle_uno.py": [
        "-e", "1", "-b", "16", "--width", "512", "--feature-depth", "4", *_DP,
    ],
    "python/native/moe.py": ["-e", "1", "-b", "32", *_DP],
    "python/native/transformer.py": ["-e", "1", "-b", "8", *_DP],
    "python/native/gpt.py": ["-e", "1", "-b", "8", *_DP],
    "python/native/serve_gpt.py": ["-e", "5", "-b", "4", *_DP],
    "python/keras/seq_mnist_mlp.py": ["-e", "1", "--num-samples", "512"],
    "python/keras/func_cifar10_cnn.py": [
        "-e", "1", "-b", "32", "--num-samples", "256",
    ],
    "python/keras/func_cifar10_cnn_concat.py": [
        "-e", "1", "-b", "32", "--num-samples", "256",
    ],
    "python/keras/seq_mnist_cnn.py": [
        "-e", "1", "-b", "32", "--num-samples", "256",
    ],
    "python/keras/func_mnist_mlp_concat.py": [
        "-e", "1", "--num-samples", "512",
    ],
    "python/keras/func_cifar10_alexnet.py": [
        "-e", "1", "-b", "32", "--num-samples", "256",
    ],
    "python/keras/seq_reuters_mlp.py": [
        "-e", "1", "-b", "32", "--num-samples", "256",
    ],
    "python/keras/callback_demo.py": [
        "-e", "2", "--num-samples", "512", "--floor", "0.05",
    ],
    "python/keras/elementwise.py": [
        "-e", "1", "-b", "32", "--num-samples", "512",
    ],
    "python/pytorch/resnet50_search.py": [
        "-e", "1", "-b", "4", "--budget", "4",
    ],
}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--only", default="", help="substring filter")
    p.add_argument("--timeout", type=int, default=900)
    args = p.parse_args()

    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
    # every child is a CPU process: it never asks for a chip this or
    # any other process may hold (one process per chip)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    failed = []
    for rel, extra in SCRIPTS.items():
        if args.only and args.only not in rel:
            continue
        script = os.path.join(HERE, rel)
        t0 = time.perf_counter()
        try:
            proc = subprocess.run(
                [sys.executable, script, *extra],
                env=env, capture_output=True, text=True,
                timeout=args.timeout,
            )
            rc, err = proc.returncode, proc.stderr
        except subprocess.TimeoutExpired:
            rc, err = -1, f"timed out after {args.timeout}s"
        dt = time.perf_counter() - t0
        status = "ok" if rc == 0 else f"FAIL rc={rc}"
        print(f"{rel:45s} {dt:7.1f}s  {status}", flush=True)
        if rc != 0:
            failed.append(rel)
            sys.stderr.write((err or "")[-2000:] + "\n")
    if failed:
        print(f"\n{len(failed)} failed: {failed}")
        sys.exit(1)
    print("\nall examples passed")


if __name__ == "__main__":
    main()
