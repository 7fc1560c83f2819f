"""CPU rehearsal of the cell PR 57 added, at a toy size, from files of
its own (`toy-glm52.BENCHMARK.json`, `configs/toy-glm52.json`,
`traffic/toy-glm52-serve.json`): the glm_dsa family (latent attention
over the 8 keys an indexer picks out of up to 64, the picks of a `full`
layer read by the `shared` layers above it, an index-key pool beside the
latent pool, 4 of 16 experts held) behind the serving driver on one
device, and its counter-fed reader on the run's own spans."""
import os
import re

from conftest import ROOT, result_line, run_cell

TOY = os.path.join(ROOT, "benchmarks", "tests", "toy-glm52.BENCHMARK.json")
CELL = "toy-glm52.toy-glm52-serve"


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
    # chunked prefill + decode through both pools picked, at every
    # served position, the reference's own best token
    assert got["exact.wrong_outputs"] == 0 and got["regret.mean"] < 1e-5


def test_per_layer_metrics_read_the_dispatch_args():
    out = run(7, 1)
    m = {k: v["value"] for k, v in result_line(out)["metrics"].items()}
    # the four device_trace metrics (dsa.device_share,
    # indexer.roofline_share, selected_read.hbm_share, serve.mfu_share)
    # return None in a rehearsal: left out
    assert set(m) == {"serve.build_front_s", "sampled.rows.capacity",
                      "dsa.selected_over_live.capacity"}
    said = re.search(r"^dsa\.selected_over_live: (\d+) keys attended of "
                     r"(\d+) live, a layer, over (\d+) dispatches; (\d+) of "
                     r"(\d+) rows had a query past index_topk$", out, re.M)
    selected, live, n, past, rows = map(int, said.groups())
    # prompts of 4-44 tokens against index_topk 8: most queries stand
    # past it, and read 8 keys where a dense read attends up to 64
    assert 0 < selected < live and 0 < past <= rows and n > 0
    assert m["dsa.selected_over_live.capacity"] == 100.0 * selected / live
    assert m["dsa.selected_over_live.capacity"] < 70.0
