#!/usr/bin/env python3
"""Record the small xplane `tests/test_host_spans.py` reads: a toy
GPT-2 server (`configs/toy-gpt2.json`) answering a few requests for a
fraction of a second under `jax.profiler`, with the Python tracer off so
that the host plane holds little beside the program's spans.

    chiprun --chips 1 -- python3 benchmarks/tools/record_host_spans.py

writes ``chiprun_out/recorded_spans.xplane.pb``; copy it to
``benchmarks/tests/recorded_spans.xplane.pb``.  On a CPU backend the
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

    cfg = load_json(os.path.join(ROOT, "benchmarks", "configs",
                                 "toy-gpt2.json"))
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
        handles = [ask(n, 6) for n in (21, 9, 14)]
        for h in handles:
            h.wait(60.0)
        time.sleep(0.1)                          # an idle turn or two
        jax.profiler.stop_trace()
    finally:
        front.close(10.0)
    dest = os.path.join(ROOT, "chiprun_out")
    os.makedirs(dest, exist_ok=True)
    dest = os.path.join(dest, "recorded_spans.xplane.pb")
    shutil.copy(find_xplane(out), dest)
    print(f"{dest}: {os.path.getsize(dest)} bytes, platform "
          f"{jax.devices()[0].platform}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
