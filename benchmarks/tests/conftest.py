"""Tests of the benchmark's own code: CPU only, never a chip."""
import json
import os
import subprocess
import sys

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TOY = os.path.join(ROOT, "benchmarks", "tests", "toy.BENCHMARK.json")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def run_cell(args, root=ROOT, extra_path=None):
    """`python3 benchmarks/run.py <args>` on the CPU: (rc, stdout, stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if extra_path:
        env["PYTHONPATH"] = extra_path
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py")] + args,
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    return p.returncode, p.stdout, p.stderr


def result_line(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


@pytest.fixture(scope="session")
def toy_train():
    rc, out, err = run_cell(
        ["--benchmark", TOY, "--rehearse-cpu", "--workload",
         "toy-bert.toy-train", "--seed", "3000000017", "--seconds", "1",
         "--trace", "0"])
    assert rc == 0, err[-2000:]
    return out


@pytest.fixture(scope="session")
def toy_serve():
    rc, out, err = run_cell(
        ["--benchmark", TOY, "--rehearse-cpu", "--workload",
         "toy-gpt2.toy-serve", "--seed", "3000000019", "--seconds", "3",
         "--trace", "0"])
    assert rc == 0, err[-2000:]
    return out
