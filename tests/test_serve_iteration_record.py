"""`scripts/serve_iteration_record.py` on the CPU twin of cell 10: a
whole run through the harness, then the loop's record by iteration out
of the span ring, with the collector's passes on the same clock."""
import json
import os
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_a_whole_run_then_the_gaps_between_its_passes(tmp_path):
    record = tmp_path / "record.jsonl"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one CPU device, as the rehearsals run
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts",
                                      "serve_iteration_record.py"),
         "--benchmark", os.path.join(ROOT, "benchmarks", "tests",
                                     "toy-evabyte.BENCHMARK.json"),
         "--rehearse-cpu", "--workload", "toy-evabyte.toy-evabyte-serve",
         "--seed", "2147484301", "--seconds", "2", "--trace", "0",
         "--record", str(record)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    said = {line.split(" ", 1)[0]: line.split(" ", 1)[1]
            for line in done.stdout.splitlines()
            if line.startswith(("gc: ", "iterations: ", '{"correct"'))}
    # the harness's own result line is still the run's: nothing is patched
    assert '"correct": true' in done.stdout
    its = json.loads(said["iterations:"])
    assert its["passes_in_window"] > 50
    assert (its["gap_median_ms"] <= its["gap_mean_ms"]
            <= its["gap_longest_ms"])
    by_generation = json.loads(said["gc:"].split(" thresholds")[0])
    assert by_generation["0"]["collections"] > 0
    rows = [json.loads(line) for line in record.read_text().splitlines()]
    starts = [r for r in rows if r["name"].endswith(".dispatch")]
    # on the window's clock: passes before its opening and after it
    assert min(r["t"] for r in starts) < 0 < max(r["t"] for r in starts)
    assert any(r["name"] == "sched.admit" and r["admitted"] >= 1
               for r in rows)
    assert [r["t"] for r in starts] == sorted(r["t"] for r in starts)
