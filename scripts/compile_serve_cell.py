#!/usr/bin/env python3
"""Rehearsal 3 for a SERVING cell: compile its two step programs at the
real size for a described v5e chip in the sandbox (weights abstract,
`jax.default_backend` answering "tpu" while they are built, as
`dump_step_hlo.py` does) and print, for each, the seconds the compile
took, `memory_analysis()`, the optimized HLO's instruction count and
its largest `copy` instructions.

    JAX_PLATFORMS=cpu python3 scripts/compile_serve_cell.py \
        --workload <cell> [--set key=value ...] [--hlo DIR]

`--set total_ut_steps=1` compiles the same graph with another value of a
published key (what a looped family's programs are compared with: the
region must not grow them).  Nothing runs; no time of the chip's comes
out of it."""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "scripts"))

import numpy as np  # noqa: E402

import dump_step_hlo as dump  # noqa: E402

COPY = re.compile(r"^\s*(?:ROOT )?\S+ = (\w+\[[\d,]*\])\S* copy\(", re.M)
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = ", re.M)


def nbytes(shape: str) -> int:
    dtype, dims = re.match(r"(\w+)\[([\d,]*)\]", shape).groups()
    width = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1,
             "f16": 2, "s8": 1, "u8": 1}.get(dtype, 4)
    return width * int(np.prod([int(x) for x in dims.split(",") if x] or [1]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--set", action="append", default=[],
                    help="key=value (JSON) over the configuration's file")
    ap.add_argument("--hlo", default=None, help="also write the text here")
    args = ap.parse_args()
    cfg, _, fam = dump.load_cell(args.workload)
    for kv in args.set:
        key, value = kv.split("=", 1)
        cfg[key] = json.loads(value)
    on_chip, _ = dump.described_chip()

    for name, traced in dump.serve_programs(fam, cfg, on_chip):
        t0 = time.monotonic()
        compiled = traced.lower(lowering_platforms=("tpu",)).compile()
        seconds = time.monotonic() - t0
        text = dump.stripped(compiled)
        m = compiled.memory_analysis()
        copies = sorted(((nbytes(s), s) for s in COPY.findall(text)),
                        reverse=True)
        print(json.dumps({
            "program": name, "compile_s": round(seconds, 1),
            "instructions": len(INSTRUCTION.findall(text)),
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "held_at_once_bytes": (
                m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes),
            "copies": len(copies),
            "largest_copies": [f"{s} ({b / 1e6:.1f} MB)"
                               for b, s in copies[:6]],
        }), flush=True)
        if args.hlo:
            os.makedirs(args.hlo, exist_ok=True)
            with open(os.path.join(
                    args.hlo, f"{args.workload}.{name}.hlo.txt"), "w") as f:
                f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
