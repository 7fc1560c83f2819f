"""CPU rehearsal of the cell PR 41 added, at a toy size, from files of
its own (`toy-ouro.BENCHMARK.json`, `configs/toy-ouro.json`,
`traffic/toy-ouro-serve.json`): the looped ouro family behind the
serving driver on one device, the whole `run.py` command, and the
readers of the loop's span args on its output."""
import os
import re

from conftest import ROOT, result_line, run_cell

TOY = os.path.join(ROOT, "benchmarks", "tests", "toy-ouro.BENCHMARK.json")
CELL = "toy-ouro.toy-ouro-serve"


def reader_module(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"),
        os.path.join(ROOT, "benchmarks", "readers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run(seed, trace):
    rc, out, err = run_cell(
        ["--benchmark", TOY, "--rehearse-cpu", "--workload", CELL,
         "--seed", str(seed), "--seconds", "3", "--trace", str(trace)])
    assert rc == 0, err[-2000:]
    return out


def test_ouro_serve_rehearsal_agrees_with_its_reference_in_float32():
    out = run(3000000023, 0)
    line = result_line(out)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert "compiles_inside_window=0" in out
    got = {m.group(1): float(m.group(2)) for m in re.finditer(
        r"^check (\S+): (\S+) \(limit", out, re.M)}
    # chunked prefill that writes a plane of keys and values a pass,
    # then decode through the same planes, picked at every served
    # position the reference's best token
    assert got["exact.wrong_outputs"] == 0 and got["regret.mean"] < 1e-5
    # a block of the one table is 3 passes x 2 layers of pages
    assert '"bytes_per_token": 1536' in out


def test_ouro_per_layer_metrics_read_the_loop_from_the_dispatch_args():
    out = run(7, 1)
    m = {k: v["value"] for k, v in result_line(out)["metrics"].items()}
    # the two device_trace metrics are left out of a rehearsal
    assert set(m) == {"serve.build_front_s", "decode.rows.capacity",
                      "kv.read_share.capacity",
                      "loop.weight_passes.capacity",
                      "loop.exit_expected_pass.capacity"}
    assert m["loop.weight_passes.capacity"] == 3.0
    assert 1.0 < m["loop.exit_expected_pass.capacity"] < 3.0
    assert 0 < m["kv.read_share.capacity"] < 100
    assert "loop.exit_expected_pass: exit pdf by pass" in out


def test_the_trace_readers_leave_their_metric_out_without_a_trace():
    """On a run with no device trace (and on a program without the
    loop's span args: the parent's) the four readers return None and do
    not raise."""
    import types

    from benchmarks.families import gpt2, ouro

    for fam in (ouro, gpt2):
        ctx = types.SimpleNamespace(
            family=fam, peak={"hbm_bytes_per_s": 819e9}, cfg={},
            trace_summary=None, trace_dir=None, trace_window_s=None,
            counters={}, spans={}, out=print)
        for name in ("loop.weight_passes", "loop.exit_expected_pass",
                     "paged_read.hbm_share", "loop.hbm_roofline_share"):
            assert reader_module(name).read(ctx, {"name": name}) is None


def test_the_two_shares_divide_what_their_docstrings_say():
    """The device-trace readers on a made-up stretch: two decode
    dispatches of the real configuration's sizes, 40 ms busy each, 20 ms
    of it under `MultiHeadAttention | paged_read`."""
    import json
    import time
    import types

    from benchmarks import device_scopes as ds
    from benchmarks.families import ouro
    from flexflow_tpu.obs.trace import span

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ouro-2.6b-serve.json")) as f:
        cfg = json.load(f)
    t0 = time.monotonic()
    for _ in range(2):
        with span("sched.decode.dispatch", rows=16, feeding=0, slots=16,
                  kv_blocks_live=160, kv_blocks_dense=320, loop_steps=4):
            pass
    row = ds.Row()
    row.seconds, row.events = 0.040, 2 * 192 * 3
    rows = {ds.Key("step", "MultiHeadAttention", "paged_read", "forward",
                   "custom-call"): row}
    ctx = types.SimpleNamespace(
        family=ouro, cfg=cfg, peak={"hbm_bytes_per_s": 819e9}, out=print,
        _trace_t0=t0, trace_window_s=time.monotonic() - t0 + 1.0,
        trace_summary={"modules": {"jit_step": [(0, 1, 0.040), (1, 2, 0.040)]}},
        scope_view_read=True, scope_view=(rows, {"step": 2}), counters={})
    block = 4 * 48 * 16 * 2 * 16 * 128 * 2  # planes x page x k, v x heads x d x bf16
    assert ouro.kv_block_bytes(cfg) == block == 25_165_824
    assert ouro.parameters(cfg) == 2_667_974_657
    read = reader_module("paged_read.hbm_share").read(ctx, {})
    assert abs(read - 100 * (160 * block / 819e9) / 0.020) < 1e-6
    layers = 48 * 51_388_416 + 2048
    floor = (2 * (4 * layers + 2049 + 49_152 * 2048 + 16 * 2048)
             + 160 * block) / 819e9
    whole = reader_module("loop.hbm_roofline_share").read(ctx, {})
    assert abs(whole - 100 * floor / 0.040) < 1e-6
    assert 0 < read < 100 and 0 < whole < 100
    assert reader_module("loop.weight_passes").read(ctx, {}) == 4.0
