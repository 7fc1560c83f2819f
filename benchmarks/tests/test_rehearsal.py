"""CPU rehearsals of run.py at a toy size: the contract's last line is
well-formed, no device metric is printed, a CPU backend without the
rehearsal option is refused, and the references agree with the program
in float32 (the check's own numbers are read from the earlier lines)."""
import json
import os
import re
import subprocess
import sys

from conftest import ROOT, TOY, result_line, run_cell

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def checks(stdout):
    return {m.group(1): float(m.group(2)) for m in re.finditer(
        r"^check (\S+): (\S+) \(limit", stdout, re.M)}


def test_train_rehearsal_line(toy_train):
    line = result_line(toy_train)
    assert KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"]
    assert "compiles_inside_window=0" in toy_train


def test_bert_reference_agrees_with_program_in_float32(toy_train):
    got = checks(toy_train)
    assert set(got) == {"grad.embedding", "grad.attention", "grad.ffn",
                        "grad.layernorm", "grad.head"}
    # float32 program against float32 reference: rounding order only
    assert max(got.values()) < 5e-6, got


def test_serve_rehearsal_line(toy_serve):
    line = result_line(toy_serve)
    assert KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert "compiles_inside_window=0" in toy_serve


def test_serve_per_layer_values_come_from_readers_or_counters():
    rc, out, err = run_cell(
        ["--benchmark", TOY, "--rehearse-cpu", "--workload",
         "toy-gpt2.toy-serve", "--seed", "7", "--seconds", "3",
         "--trace", "1"])
    assert rc == 0, err[-2000:]
    line = result_line(out)
    # a reader file, and three that have none: the driver's counters
    assert set(line["metrics"]) == {
        "serve.build_front_s", "loadgen.late_p95_ms",
        "ttft_p95_ms.overload", "sched.tokens_per_s.capacity"}
    assert line["metrics"]["sched.tokens_per_s.capacity"]["value"] > 0


def test_gpt2_reference_agrees_with_program_in_float32(toy_serve):
    got = checks(toy_serve)
    # prefill + paged decode through the cache picked, at every served
    # position, the reference's own best token (or one within rounding)
    assert got["exact.wrong_outputs"] == 0
    assert got["regret.mean"] < 1e-5, got


def test_trace_run_on_cpu_prints_no_device_metric():
    rc, out, err = run_cell(
        ["--benchmark", TOY, "--rehearse-cpu", "--workload",
         "toy-bert.toy-train", "--seed", "5", "--seconds", "1",
         "--trace", "1"])
    assert rc == 0, err[-2000:]
    line = result_line(out)
    assert "step.device_ms" not in line["metrics"]
    assert "busy_s" not in line["device"]
    assert "compile.step_s" in line["metrics"]


def test_cpu_without_rehearsal_is_refused():
    rc, out, err = run_cell(
        ["--benchmark", TOY, "--workload", "toy-bert.toy-train", "--seed",
         "1", "--seconds", "1", "--trace", "0"])
    assert rc == 2
    assert "{" not in out
    assert "no accelerator" in err


def test_four_chip_cell_on_four_virtual_devices(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    rc, out, err = run_cell(
        ["--benchmark", TOY, "--rehearse-cpu", "--workload",
         "toy-bert.toy-train-4chip", "--seed", "12", "--seconds", "1",
         "--trace", "0"])
    assert rc == 0, err[-2000:]
    line = result_line(out)
    assert line["correct"] is True and line["device"]["count"] == 4
    assert "mesh={'data': 4}" in out


def test_stall_probe_tells_a_stalled_thread_from_a_stalled_process():
    """A sleep of the feeding thread shows as the window's longest gap
    between dispatches, inside `train_step`, while the ticker thread
    kept ticking; and the stalled window did fewer steps."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "tools",
                                      "stall_probe.py"),
         "--benchmark", TOY, "--rehearse-cpu", "--workload",
         "toy-bert.toy-train", "--seconds", "1", "--depths", "2",
         "--stalls", "0,0.4"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    plain, stalled = [json.loads(line) for line in p.stdout.splitlines()
                      if line.startswith("{")]
    assert plain["host_gap"]["ms"] < 200 < 400 <= stalled["host_gap"]["ms"]
    assert stalled["host_gap"]["in_train_step_ms"] >= 400
    assert stalled["ticker_gap_ms"] < 200
    assert stalled["steps"] < plain["steps"]
