"""The control has to come out as NOT correct: the reference put in the
program's place one precision below the stated one (here, at the toy
size, bfloat16 under a float32 configuration; on the chip, fp8 under
bfloat16 — PERF.md has those readings) fails the same comparison, on
the same limits, that the program passes on every seed."""
import os
import sys

import pytest

from conftest import ROOT, TOY

sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

SEEDS = [2_300_000_011, 2_300_007_930, 2**31 + 77]


def sweep(workload, seconds):
    import run as harness
    from benchmarks import check

    ctx, driver = harness.make_context(
        ["--workload", workload, "--seed", "1", "--seconds", str(seconds),
         "--benchmark", TOY, "--rehearse-cpu"])
    quiet = lambda *_: None
    rows = list(driver.sweep(ctx, SEEDS, set(SEEDS)))
    assert len(rows) == len(SEEDS)
    for row in rows:
        assert check.verdict(row["program"], ctx.cfg["tolerance"], quiet), row
        assert not check.verdict(row["control"], ctx.cfg["tolerance"],
                                 quiet), row
    return rows


def test_training_control_fails_and_program_passes():
    rows = sweep("toy-bert.toy-train", 1)
    worst = max(max(r["program"].values()) for r in rows)
    best = min(min(r["control"].values()) for r in rows)
    assert best > 3 * worst


def test_serving_control_fails_and_program_passes():
    sweep("toy-gpt2.toy-serve", 3)


def test_missing_statistic_is_not_correct():
    from benchmarks import check

    tol = {"grad.ffn": {"limit": 1.0}, "grad.head": {"limit": 1.0}}
    assert not check.verdict({"grad.ffn": 0.1}, tol, lambda *_: None)
    assert not check.verdict({"grad.ffn": 0.1, "grad.head": float("nan")},
                             tol, lambda *_: None)
    assert check.verdict({"grad.ffn": 0.1, "grad.head": 1.0}, tol,
                         lambda *_: None)
