#!/usr/bin/env python3
"""Record the small xplane `tests/test_device_scope_reduction.py` reads:
the toy `lfm2_moe` training graph (`configs/toy-lfm2.json`: short convs,
grouped-query attention, routed experts, `remat` on, Adam) taking a few
steps under `jax.profiler`, with the Python tracer off so that the file
holds little beside the device's events and their metadata.

    chiprun --chips 1 -- python3 benchmarks/tools/record_device_scopes.py

writes ``chiprun_out/recorded_scopes.xplane.pb``; copy it to
``benchmarks/tests/recorded_scopes.xplane.pb``.  On a CPU backend the
file has no device plane and is of no use to that test.
"""
from __future__ import annotations

import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
STEPS = 4


def without_planes(src: str, dest: str, drop=("/host:metadata",)) -> None:
    """Copy an xplane file without the planes called ``drop``: the
    programs' serialized HLO (`/host:metadata`) is most of a small
    capture and no reader here opens it."""
    from benchmarks.device_scopes import _fields, _text

    with open(src, "rb") as f:
        buf = f.read()
    kept, at = bytearray(), 0
    for number, wire, value in _fields(buf, 0, len(buf)):
        end = value[1] if wire else None
        if number == 1 and wire == 2:
            name = next((_text(buf, v) for f2, w2, v in _fields(buf, *value)
                         if f2 == 2 and w2 == 2), "")
            if name in drop:
                # the field's one-byte key and its length's varint lie
                # before its payload
                n, width = value[1] - value[0], 1
                while n >= 0x80:
                    n, width = n >> 7, width + 1
                kept += buf[at:value[0] - width - 1]
                at = end
    kept += buf[at:]
    with open(dest, "wb") as f:
        f.write(kept)


def main() -> int:
    import jax
    import numpy as np

    from benchmarks.run import find_xplane, load_json, load_module

    cfg = load_json(os.path.join(ROOT, "benchmarks", "configs",
                                 "toy-lfm2.json"))
    traffic = load_json(os.path.join(ROOT, "benchmarks", "traffic",
                                     "toy-lfm2-train.json"))
    fam = load_module("families", cfg["family"])
    batch, seq = traffic["batch_per_chip"], traffic["seq"]
    ff = fam.build_model(cfg, batch, seq, 1)
    fam.compile_model(ff, cfg, jax.devices()[:1])
    ff.set_weights(fam.make_weights(cfg, 1, "program"))
    inputs, labels = fam.make_batch(cfg, batch, seq,
                                    np.random.default_rng(1))
    jax.block_until_ready(ff.train_step(inputs, labels)["loss"])  # compiled
    out = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    for _ in range(STEPS):
        loss = ff.train_step(inputs, labels)["loss"]
    jax.block_until_ready(loss)
    jax.profiler.stop_trace()
    dest = os.path.join(ROOT, "chiprun_out")
    os.makedirs(dest, exist_ok=True)
    dest = os.path.join(dest, "recorded_scopes.xplane.pb")
    without_planes(find_xplane(out), dest)
    print(f"{dest}: {os.path.getsize(dest)} bytes, {STEPS} steps, platform "
          f"{jax.devices()[0].platform}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
