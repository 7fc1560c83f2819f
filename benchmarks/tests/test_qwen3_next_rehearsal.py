"""CPU rehearsal of the cell PR 34 added, at a toy size, from files of
its own (`toy-qwen3-next.BENCHMARK.json`, `configs/toy-qwen3-next.json`,
`traffic/toy-qwen3-next-serve.json`): the qwen3_next family behind the
serving driver on one device, the whole `run.py` command."""
import os
import re

from conftest import ROOT, result_line, run_cell

TOY = os.path.join(ROOT, "benchmarks", "tests",
                   "toy-qwen3-next.BENCHMARK.json")
CELL = "toy-qwen3-next.toy-qwen3-next-serve"


def run(seed, trace):
    rc, out, err = run_cell(
        ["--benchmark", TOY, "--rehearse-cpu", "--workload", CELL,
         "--seed", str(seed), "--seconds", "3", "--trace", str(trace)])
    assert rc == 0, err[-2000:]
    return out


def test_qwen3_next_serve_rehearsal_agrees_with_its_reference_in_float32():
    out = run(3000000023, 0)
    line = result_line(out)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert "compiles_inside_window=0" in out
    got = {m.group(1): float(m.group(2)) for m in re.finditer(
        r"^check (\S+): (\S+) \(limit", out, re.M)}
    # chunked prefill that carries conv tail and S from chunk to chunk,
    # then decode from the per-slot state and the paged grouped-query
    # cache, picked at every served position the reference's best token
    assert got["exact.wrong_outputs"] == 0 and got["regret.mean"] < 1e-5


def test_qwen3_next_per_layer_metrics_read_the_dispatch_args():
    out = run(7, 1)
    m = {k: v["value"] for k, v in result_line(out)["metrics"].items()}
    # the device_trace metric is left out of a rehearsal
    assert set(m) == {"serve.build_front_s", "decode.rows.capacity",
                      "moe.held_pairs.capacity",
                      "moe.load_max_over_mean.capacity",
                      "kv.read_share.capacity",
                      "rstate.touched_over_live.capacity",
                      "rstate.bytes_share.capacity"}
    assert m["rstate.touched_over_live.capacity"] >= 1
    assert 0 < m["rstate.bytes_share.capacity"] < 100
    assert m["moe.held_pairs.capacity"] > 0
    assert 0 < m["kv.read_share.capacity"] < 100
    assert "rstate.touched_over_live:" in out and "rstate.bytes_share:" in out
