"""A later PR adds a configuration, a traffic mix and a per-layer
reader as NEW files plus entries in BENCHMARK.json, and edits no file
that is there: done here in a temporary copy."""
import hashlib
import json
import os
import shutil

from conftest import ROOT, result_line, run_cell


def digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "benchmarks")):
        if "__pycache__" in d or os.sep + "out" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def test_new_cell_from_new_files_only(tmp_path):
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    before = digest(root)
    bench_dir = os.path.join(root, "benchmarks")
    cfg = json.load(open(os.path.join(bench_dir, "configs", "toy-bert.json")))
    cfg["num_hidden_layers"] = 3
    json.dump(cfg, open(os.path.join(bench_dir, "configs", "toy3.json"), "w"))
    tr = json.load(open(os.path.join(bench_dir, "traffic", "toy-train.json")))
    tr["seq"] = 8
    json.dump(tr, open(os.path.join(bench_dir, "traffic", "short.json"), "w"))
    with open(os.path.join(bench_dir, "readers", "steps.count.py"), "w") as f:
        f.write("def read(ctx, metric):\n"
                "    return ctx.counters.get('steps')\n")
    bench = json.load(open(os.path.join(bench_dir, "tests",
                                        "toy.BENCHMARK.json")))
    bench["configs"].append({"name": "toy3", "source": "test", "reduced": [],
                             "file": "benchmarks/configs/toy3.json",
                             "why": "test"})
    bench["workloads"].append({"name": "toy3.short", "config": "toy3",
                               "traffic": "short", "chips": 1, "why": "t"})
    bench["end_to_end"][0]["workloads"].append("toy3.short")
    bench["per_layer"].append({
        "name": "steps.count", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "executor",
        "moves": "train_tokens_per_s_per_chip", "workloads": ["toy3.short"]})
    path = os.path.join(root, "BENCHMARK.json")
    json.dump(bench, open(path, "w"))

    args = ["--benchmark", path, "--rehearse-cpu", "--workload",
            "toy3.short", "--seed", "11", "--seconds", "1"]
    rc, out, err = run_cell(args + ["--trace", "0"], root, extra_path=ROOT)
    assert rc == 0, err[-2000:]
    line = result_line(out)
    assert line["correct"] is True
    assert "train_tokens_per_s_per_chip" in line["metrics"]
    rc, out, err = run_cell(args + ["--trace", "1"], root, extra_path=ROOT)
    assert rc == 0, err[-2000:]
    line = result_line(out)
    assert line["metrics"]["steps.count"]["value"] > 0
    after = digest(root)
    assert {k: after[k] for k in before} == before, "an existing file changed"
