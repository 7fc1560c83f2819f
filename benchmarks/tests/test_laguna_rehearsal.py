"""CPU rehearsal of the cell PR 55 added, at a toy size, from files of
its own (`toy-laguna.BENCHMARK.json`, `configs/toy-laguna.json`,
`traffic/toy-laguna-serve.json`): the laguna family (window layers'
rings of 12 rows beside full layers' pages, 6 and 8 query heads over 2
key/value heads, 8 of 16 experts held) behind the serving driver on one
device, and its counter-fed readers on the run's own spans."""
import os
import re

from conftest import ROOT, result_line, run_cell

TOY = os.path.join(ROOT, "benchmarks", "tests", "toy-laguna.BENCHMARK.json")
CELL = "toy-laguna.toy-laguna-serve"


def run(seed, trace):
    rc, out, err = run_cell(
        ["--benchmark", TOY, "--rehearse-cpu", "--workload", CELL,
         "--seed", str(seed), "--seconds", "3", "--trace", str(trace)])
    assert rc == 0, err[-2000:]
    return out


def test_serve_rehearsal_agrees_with_its_reference_in_float32():
    out = run(3000000029, 0)
    line = result_line(out)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert "compiles_inside_window=0" in out
    got = {m.group(1): float(m.group(2)) for m in re.finditer(
        r"^check (\S+): (\S+) \(limit", out, re.M)}
    # chunked prefill + decode through the rings and the pool picked, at
    # every served position, the reference's own best token
    assert got["exact.wrong_outputs"] == 0 and got["regret.mean"] < 1e-5


def test_per_layer_metrics_read_the_dispatch_args():
    out = run(7, 1)
    m = {k: v["value"] for k, v in result_line(out)["metrics"].items()}
    # the three device_trace metrics (swa.device_share, gqa_read.hbm_share,
    # serve.mfu_share) return None in a rehearsal: left out
    assert set(m) == {"serve.build_front_s", "sampled.rows.capacity",
                      "swa.live_over_read.capacity",
                      "swa.state_over_full.capacity"}
    said = re.search(r"^swa\.live_over_read: (\d+) ring rows visible to "
                     r"the queries of (\d+) dispatches, (\d+) rows read$",
                     out, re.M)
    live, n, built = map(int, said.groups())
    # 3 window layers x 4 slots x a ring of 12 rows a dispatch; a query
    # sees at most the window's 8 rows, a pass's four queries share a read
    assert built == n * 3 * 4 * 12 and 0 < live < built
    assert m["swa.live_over_read.capacity"] == 100.0 * live / built
    # two full layers of five hold pages; every slot's rings beside them
    assert 40.0 < m["swa.state_over_full.capacity"]
