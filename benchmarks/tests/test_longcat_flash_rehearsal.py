"""CPU rehearsal of the cell PR 47 added, at a toy size, from files of
its own (`toy-longcat-flash.BENCHMARK.json`,
`configs/toy-longcat-flash.json`, `traffic/toy-longcat-flash-serve.json`):
the longcat_flash family (two latent planes a layer, identity experts in
a softmax router, the experts' shortcut) behind the serving driver on
one device, and its two readers on the run's own spans."""
import os
import re

from conftest import ROOT, result_line, run_cell

TOY = os.path.join(ROOT, "benchmarks", "tests",
                   "toy-longcat-flash.BENCHMARK.json")
CELL = "toy-longcat-flash.toy-longcat-flash-serve"


def run(seed, trace):
    rc, out, err = run_cell(
        ["--benchmark", TOY, "--rehearse-cpu", "--workload", CELL,
         "--seed", str(seed), "--seconds", "3", "--trace", str(trace)])
    assert rc == 0, err[-2000:]
    return out


def test_serve_rehearsal_agrees_with_its_reference_in_float32():
    out = run(3000000023, 0)
    line = result_line(out)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert "compiles_inside_window=0" in out
    got = {m.group(1): float(m.group(2)) for m in re.finditer(
        r"^check (\S+): (\S+) \(limit", out, re.M)}
    # chunked prefill + paged decode through both planes of every layer
    # picked, at every served position, the reference's own best token
    assert got["exact.wrong_outputs"] == 0 and got["regret.mean"] < 1e-5


def test_per_layer_metrics_read_the_dispatch_args():
    out = run(7, 1)
    m = {k: v["value"] for k, v in result_line(out)["metrics"].items()}
    # the two device_trace metrics (decode.hbm_roofline_share,
    # prefill.roofline_share) return None in a rehearsal: left out
    assert set(m) == {"serve.build_front_s", "decode.rows.capacity",
                      "moe.held_pairs.capacity",
                      "moe.load_max_over_mean.capacity",
                      "kv.read_share.capacity",
                      "moe.zero_pick_share.capacity"}
    # 8 of the toy router's 24 outputs are identity experts
    assert 10 < m["moe.zero_pick_share.capacity"] < 60
    assert re.search(r"^moe\.zero_pick_share: .* real picks a row: least "
                     r"\d+, mean [\d.]+, most \d+ of 6$", out, re.M)
    assert m["moe.held_pairs.capacity"] > 0
    assert 0 < m["kv.read_share.capacity"] < 100
