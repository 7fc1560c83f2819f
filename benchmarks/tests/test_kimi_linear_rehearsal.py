"""CPU rehearsal of the cell PR 43 added, at a toy size, from files of
its own (`toy-kimi-linear.BENCHMARK.json`, `configs/toy-kimi-linear.json`,
`traffic/toy-kimi-linear-train.json`): the kimi_linear family under the
training driver on one device, the whole `run.py` command."""
import os
import re

from conftest import ROOT, result_line, run_cell

TOY = os.path.join(ROOT, "benchmarks", "tests",
                   "toy-kimi-linear.BENCHMARK.json")
CELL = "toy-kimi-linear.toy-kimi-linear-train"
GROUPS = ("embedding", "head", "kda", "mla", "dense_mlp", "router",
          "experts", "shared_expert", "norm")


def run(seed, trace):
    rc, out, err = run_cell(
        ["--benchmark", TOY, "--rehearse-cpu", "--workload", CELL,
         "--seed", str(seed), "--seconds", "2", "--trace", str(trace)])
    assert rc == 0, err[-2000:]
    return out


def test_kimi_linear_train_rehearsal_agrees_with_its_reference_in_float32():
    out = run(3000000029, 0)
    line = result_line(out)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert "compiles_inside_window=0" in out
    got = {m.group(1): float(m.group(2)) for m in re.finditer(
        r"^check grad\.(\S+): (\S+) \(limit", out, re.M)}
    # the first-step gradient of the timed batch through FFModel.compile
    # and train_step (remat on, as the real cell), by group: the chunked
    # delta rule against the reference's scan a position
    assert set(got) == set(GROUPS) and max(got.values()) < 2e-6


def test_kimi_linear_per_layer_metrics_read_the_chunk_the_op_picked():
    out = run(7, 1)
    m = {k: v["value"] for k, v in result_line(out)["metrics"].items()}
    # `flash.roofline_share.train`, `kda.device_share.train` and
    # `kda.core_roofline_share.train` are device_trace metrics: left out
    # of a rehearsal
    assert set(m) == {"compile.step_s", "step.host_ms",
                      "moe_train.held_pairs",
                      "moe_train.load_max_over_mean",
                      "moe_train.rows_computed_over_routed",
                      "kda.chunk_tokens.train"}
    # seq 16 is one chunk of one sub-chunk: the chunked rule, not the scan
    assert m["kda.chunk_tokens.train"] == 16
    assert m["moe_train.held_pairs"] > 0
    assert "0 dropped in" in out
