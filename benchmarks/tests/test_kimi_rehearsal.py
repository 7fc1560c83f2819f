"""CPU rehearsals of the cells PR 29 added, at a toy size, from files of
their own (`toy-kimi.BENCHMARK.json`, `configs/toy-kimi.json`,
`traffic/toy-kimi-serve.json`): the kimi_k2 family behind the serving
driver on one device, and the four-chip training cell's own traffic
file (`seq512-4chip.json`) on four virtual devices with the toy
encoder."""
import os
import re

from conftest import ROOT, result_line, run_cell

TOY_KIMI = os.path.join(ROOT, "benchmarks", "tests",
                        "toy-kimi.BENCHMARK.json")


def run(cell, seed, trace):
    rc, out, err = run_cell(
        ["--benchmark", TOY_KIMI, "--rehearse-cpu", "--workload", cell,
         "--seed", str(seed), "--seconds", "3", "--trace", str(trace)])
    assert rc == 0, err[-2000:]
    return out


def test_kimi_serve_rehearsal_agrees_with_its_reference_in_float32():
    out = run("toy-kimi.toy-kimi-serve", 3000000023, 0)
    line = result_line(out)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert "compiles_inside_window=0" in out
    got = {m.group(1): float(m.group(2)) for m in re.finditer(
        r"^check (\S+): (\S+) \(limit", out, re.M)}
    # prefill + paged decode through the LATENT cache picked, at every
    # served position, the reference's own best token
    assert got["exact.wrong_outputs"] == 0 and got["regret.mean"] < 1e-5


def test_kimi_per_layer_metrics_read_the_dispatch_args():
    line = result_line(run("toy-kimi.toy-kimi-serve", 7, 1))
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # the device_trace metric is left out of a rehearsal
    assert set(m) == {"serve.build_front_s", "decode.rows.capacity",
                      "moe.held_pairs.capacity",
                      "moe.load_max_over_mean.capacity",
                      "kv.read_share.capacity"}
    assert m["moe.held_pairs.capacity"] > 0
    assert m["moe.load_max_over_mean.capacity"] >= 1
    assert 0 < m["kv.read_share.capacity"] < 100


def test_four_chip_traffic_file_on_four_virtual_devices(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    out = run("toy-bert.seq512-4chip", 12, 1)
    line = result_line(out)
    assert line["correct"] is True and line["device"]["count"] == 4
    assert "mesh={'data': 4}" in out
    # collective.exposed_ms reads the device trace: none in a rehearsal
    assert set(line["metrics"]) == {"compile.step_s"}
