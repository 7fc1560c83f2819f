"""CPU rehearsal of the cell PR 36 added, at a toy size, from files of
its own (`toy-lfm2.BENCHMARK.json`, `configs/toy-lfm2.json`,
`traffic/toy-lfm2-train.json`): the lfm2_moe family under the training
driver on one device, the whole `run.py` command."""
import os
import re

from conftest import ROOT, result_line, run_cell

TOY = os.path.join(ROOT, "benchmarks", "tests", "toy-lfm2.BENCHMARK.json")
CELL = "toy-lfm2.toy-lfm2-train"
GROUPS = ("embedding", "head", "conv", "attention", "dense_mlp", "router",
          "experts", "norm")


def run(seed, trace):
    rc, out, err = run_cell(
        ["--benchmark", TOY, "--rehearse-cpu", "--workload", CELL,
         "--seed", str(seed), "--seconds", "2", "--trace", str(trace)])
    assert rc == 0, err[-2000:]
    return out


def test_lfm2_train_rehearsal_agrees_with_its_reference_in_float32():
    out = run(3000000029, 0)
    line = result_line(out)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert "compiles_inside_window=0" in out
    got = {m.group(1): float(m.group(2)) for m in re.finditer(
        r"^check grad\.(\S+): (\S+) \(limit", out, re.M)}
    # the first-step gradient of the timed batch through FFModel.compile
    # and train_step (remat on, as the real cell), by group
    assert set(got) == set(GROUPS) and max(got.values()) < 2e-6


def test_lfm2_per_layer_metrics_read_the_train_step_moe_span():
    out = run(7, 1)
    m = {k: v["value"] for k, v in result_line(out)["metrics"].items()}
    # `flash.roofline_share.train` is a device_trace metric: left out of
    # a rehearsal
    assert set(m) == {"compile.step_s", "step.host_ms",
                      "moe_train.held_pairs",
                      "moe_train.load_max_over_mean",
                      "moe_train.rows_computed_over_routed"}
    assert m["moe_train.held_pairs"] > 0
    assert m["moe_train.load_max_over_mean"] >= 1
    # the toy step's 32 rows take the dense product: every held expert
    # over every row, so at least experts_total / top_k = 4 when routing
    # is even, fewer where training has pulled rows onto the held ones
    assert m["moe_train.rows_computed_over_routed"] >= 1
    assert "0 dropped in" in out
