#!/usr/bin/env python3
"""Record the small xplane `tests/test_turn_readers.py` reads: a toy
Kimi-K2 server (`configs/toy-kimi.json` cut to one layer, a routed
one: on the one-pass prefill program; a capture holds every
program's instructions, so the smaller the graph the smaller the file)
answering a few requests under `jax.profiler`, the Python tracer off,
so that the host plane holds little beside the program's spans.  The requests arrive
together and ask for a few tokens: some passes sample (riders beside
feeding rows), the last iterations are plain decode dispatches.

    chiprun --chips 1 -- python3 benchmarks/tools/record_turn_spans.py

writes ``chiprun_out/recorded_turn.xplane.pb``; copy it to
``benchmarks/tests/recorded_turn.xplane.pb``.  On a CPU backend the
file has no device plane and is of no use to that test.
"""
from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import numpy as np

    from benchmarks.run import find_xplane, load_json, load_module
    from flexflow_tpu.serving import build_front

    cfg = dict(load_json(os.path.join(ROOT, "benchmarks", "configs",
                                      "toy-kimi.json")),
               num_hidden_layers=1, first_k_dense_replace=0)
    fam = load_module("families", cfg["family"])
    ff = fam.build_server(cfg, jax.devices()[:1])
    ff.set_weights(fam.make_weights(cfg, 1, "program"))
    front = build_front(ff)
    rng = np.random.default_rng(1)

    def ask(n_prompt, n_new):
        return front.generate_async(
            rng.integers(1, cfg["vocab_size"], n_prompt).tolist(), n_new, 0.0)

    try:
        for h in [ask(19, 4), ask(30, 4)]:      # every program compiled
            h.wait(600.0)
        out = tempfile.mkdtemp()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(out, profiler_options=options)
        handles = [ask(n, new) for n, new in ((21, 5), (3, 6), (14, 4))]
        for h in handles:
            h.wait(60.0)
        time.sleep(0.05)                         # an idle turn or two
        jax.profiler.stop_trace()
    finally:
        front.close(10.0)
    dest = os.path.join(ROOT, "chiprun_out")
    os.makedirs(dest, exist_ok=True)
    pb = os.path.join(dest, "recorded_turn.xplane.pb")
    shutil.copy(find_xplane(out), pb)
    print(f"{pb}: {os.path.getsize(pb)} bytes, platform "
          f"{jax.devices()[0].platform}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
