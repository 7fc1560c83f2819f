"""CPU rehearsal of the cell PR 51 added, at a toy size, from files of
its own (`toy-evabyte.BENCHMARK.json`, `configs/toy-evabyte.json`,
`traffic/toy-evabyte-serve.json`): the evabyte family (EVA layers whose
windows roll over every 16 positions and whose summaries grow one every
4; head 0 of three sampled) behind the serving driver on one device,
and its counter-fed readers on the run's own spans."""
import os
import re

from conftest import ROOT, result_line, run_cell

TOY = os.path.join(ROOT, "benchmarks", "tests", "toy-evabyte.BENCHMARK.json")
CELL = "toy-evabyte.toy-evabyte-serve"


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
    # chunked prefill + decode through windows and summaries picked, at
    # every served position, the reference's own best byte of head 0
    assert got["exact.wrong_outputs"] == 0 and got["regret.mean"] < 1e-5


def test_per_layer_metrics_read_the_dispatch_args():
    out = run(7, 1)
    m = {k: v["value"] for k, v in result_line(out)["metrics"].items()}
    # the three device_trace metrics (eva.device_share, eva.read_hbm_share,
    # serve.mfu_share) return None in a rehearsal: left out
    assert set(m) == {"serve.build_front_s", "decode.rows.capacity",
                      "eva.live_over_read.capacity"}
    # 4 slots x (16 + 16) rows a layer are read; a live row sees at most
    # 16 singletons and 12 summaries, and not every slot is live
    assert 0 < m["eva.live_over_read.capacity"] < 100 * 28 / 32
    said = re.search(r"^eva\.live_over_read: (\d+) live rows \((\d+) of "
                     r"windows, (\d+) summaries\) of (\d+) read over "
                     r"(\d+) decode dispatches$", out, re.M)
    live, window, summary, built, n = map(int, said.groups())
    assert live == window + summary and built == n * 2 * 4 * 32
    assert summary > 0  # prompts past the first window are in the mix
