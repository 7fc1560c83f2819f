"""CPU rehearsal of the cell PR 62 added, at a toy size, from files of
its own (`toy-granite-hybrid.BENCHMARK.json`,
`configs/toy-granite-hybrid.json`,
`traffic/toy-granite-hybrid-serve.json`): the granite_hybrid family (one
period of ten layers: nine Mamba-2 layers whose state lives in per-slot
arrays, one grouped-query layer without positions in the paged pool, a
tied head, four multipliers) behind the serving driver on one device,
and its counter-fed readers on the run's own spans."""
import os
import re

from conftest import ROOT, result_line, run_cell

TOY = os.path.join(ROOT, "benchmarks", "tests",
                   "toy-granite-hybrid.BENCHMARK.json")
CELL = "toy-granite-hybrid.toy-granite-hybrid-serve"


def run(seed, trace):
    rc, out, err = run_cell(
        ["--benchmark", TOY, "--rehearse-cpu", "--workload", CELL,
         "--seed", str(seed), "--seconds", "3", "--trace", str(trace)])
    assert rc == 0, err[-2000:]
    return out


def test_serve_rehearsal_agrees_with_its_reference_in_float32():
    out = run(3000000031, 0)
    line = result_line(out)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert "compiles_inside_window=0" in out
    got = {m.group(1): float(m.group(2)) for m in re.finditer(
        r"^check (\S+): (\S+) \(limit", out, re.M)}
    # chunked prefill through the pass + decode through state and pool
    # picked, at every served position, the reference's own best token
    assert got["exact.wrong_outputs"] == 0 and got["regret.mean"] < 1e-5


def test_per_layer_metrics_read_the_dispatch_args():
    out = run(7, 1)
    m = {k: v["value"] for k, v in result_line(out)["metrics"].items()}
    # the three device_trace metrics (serve.mfu_share, ssm.device_share,
    # ssm.state_hbm_share) return None in a rehearsal: left out
    assert set(m) == {"serve.build_front_s", "sampled.rows.capacity",
                      "sampled.kv_read_share.capacity",
                      "rstate.touched_over_live.capacity",
                      "prefill.useful_share.capacity"}
    said = re.search(r"^rstate\.touched_over_live: (\d+) rows' state read "
                     r"and written for (\d+) rows advanced, over (\d+) "
                     r"decode and prefill dispatches$", out, re.M)
    touched, live, n = map(int, said.groups())
    # the plain form (what a CPU picks) touches every one of the 4 slots
    assert touched == 4 * n and 0 < live <= touched
    assert m["rstate.touched_over_live.capacity"] == touched / live


def test_a_planted_state_fault_is_in_the_program_and_is_taken_out_again():
    """`tools/ssm_state_faults.py planted`: inside, the step's chunk
    leaves every odd row's state as it found it (or reads its
    neighbour's); outside, the program is what it was."""
    import importlib.util

    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops import mamba2

    spec = importlib.util.spec_from_file_location(
        "ssm_state_faults", os.path.join(ROOT, "benchmarks", "tools",
                                         "ssm_state_faults.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    rng = np.random.default_rng(0)
    b, s, h, p, n = 4, 3, 2, 4, 8
    S0, x, B, C, dt = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
                       for shape in ((b, h, p, n), (b, s, h, p), (b, s, n),
                                     (b, s, n), (b, s, h)))
    fed = (x, B, C, jnp.abs(dt), -jnp.ones(h), jnp.ones(h))
    sound, want = mamba2.ssd_chunk, mamba2.ssd_chunk(S0, *fed)
    with tool.planted("frozen_half"):
        S1, y = mamba2.ssd_chunk(S0, *fed)
    assert np.array_equal(S1[1::2], S0[1::2])
    assert np.array_equal(S1[0::2], want[0][0::2])
    assert np.array_equal(y, want[1])
    with tool.planted("neighbour"):
        S1, _ = mamba2.ssd_chunk(S0, *fed)
    assert not np.allclose(S1, want[0])
    assert np.array_equal(S1, sound(jnp.roll(S0, 1, axis=0), *fed)[0])
    assert mamba2.ssd_chunk is sound
