#!/usr/bin/env python3
"""The optimized HLO of a cell's step programs, compiled at the REAL
size for a described v5e chip in the sandbox, with `metadata={...}`
stripped: what two trees are compared by when a change must not move
the compiled program (a refactor, a scope, a rename).

    JAX_PLATFORMS=cpu python3 scripts/dump_step_hlo.py \
        --workload <cell> --out <dir>

writes `<dir>/<cell>.<program>.hlo.txt` (a training cell: `step`; a
serving cell: `step` and `prefill`).  Unpack BOTH trees at ONE path in
turn (a Mosaic kernel's body embeds its call stack's file names) and
`diff` the files; what is left are the `tpu_custom_call` lines, whose
Mosaic payloads embed their call stack's source LINES (ROADMAP D16), and
nothing else.  Nothing runs and no
time comes out of it; weights stay abstract (`compile_train_cell.py`'s
way), `jax.default_backend` answers "tpu" while the programs are built.
"""
from __future__ import annotations

import argparse
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, os.path.join(ROOT, "scripts"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import run as harness  # noqa: E402  (benchmarks/run.py)
from compile_train_cell import abstract_train_model  # noqa: E402

METADATA = re.compile(r",? ?metadata=\{[^{}]*(?:\{[^{}]*\}[^{}]*)*\}")
# the module's tables of file names, function names, locations and stack
# frames, which its instructions' metadata points into
FRAME_TABLES = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*\n",
    re.M)


def stripped(compiled) -> str:
    return METADATA.sub("", FRAME_TABLES.sub("", compiled.as_text()))


def train_programs(fam, cfg, traffic, on_chip, rng):
    batch, seq = traffic["batch_per_chip"], traffic["seq"]
    ff = abstract_train_model(fam, cfg, batch, seq)
    inputs, labels = fam.make_batch(cfg, batch, seq,
                                    np.random.default_rng(0))
    structs = on_chip((ff._weights, ff._opt_state, ff._state,
                       {k: jnp.asarray(v) for k, v in inputs.items()},
                       jnp.asarray(labels)))
    jax.default_backend = lambda: "tpu"
    yield "step", ff._step_fn.trace(*structs, rng)


def serve_programs(fam, cfg, on_chip):
    from flexflow_tpu.serving.scheduler import PagedKVDecodeModel

    jax.default_backend = lambda: "tpu"
    ff = fam.build_server(cfg, jax.devices()[:1])
    if ff._weights is None:
        ff._weights = jax.eval_shape(
            lambda: fam.make_weights(cfg, 0, "program"))
    c = ff.config
    model = PagedKVDecodeModel(
        ff, batch_slots=c.serving_slots, page_size=c.kv_page_size,
        num_blocks=c.kv_pool_blocks or None, devices=jax.devices()[:1],
        prefill_chunk=c.prefill_chunk, prefix_cache=c.prefix_cache)
    b = model.batch_slots
    ints = np.zeros((b,), np.int32)
    table = np.zeros((b, model.max_blocks_per_seq), np.int32)
    rows = (ints,) if model.has_slot_state else ()
    # (a family on the one-pass program keeps its ids on the device: the
    # last dispatch's `prev_ids` and `take_prev` behind `row_tokens`,
    # None where the decode step takes none)
    ids = (ints, ints) if model.keeps_ids else ()
    w, st = on_chip((model.ffd._weights, model._state))
    yield "step", model._step_fn.trace(
        w, st, *on_chip((ints, ints, table) + (rows or (None,) * bool(ids))
                        + ids))
    # (the one-pass program takes `row_tokens` whatever the family)
    fed = (ints,) if model.prefill_passes == 1 else rows
    yield "prefill", model._prefill_fn.trace(
        w, st, *on_chip((np.zeros((b, model.prefill_chunk), np.int32), ints,
                         table) + fed + ids))


def load_cell(workload: str):
    """(configuration, traffic, family module) of a cell of
    BENCHMARK.json."""
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = harness.load_json(os.path.join(ROOT, entry["file"]))
    traffic = harness.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", cell["traffic"] + ".json"))
    return cfg, traffic, harness.load_module("families", cfg["family"])


def described_chip():
    """(on_chip, rng): a tree's arrays as `ShapeDtypeStruct`s on the
    first chip of the compile-only `v5e:2x2` topology, and a PRNG key's
    struct there."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            np.shape(x), x.dtype, sharding=chip), tree)

    return on_chip, jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                         sharding=chip)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    cfg, traffic, fam = load_cell(args.workload)
    on_chip, rng = described_chip()
    programs = (train_programs(fam, cfg, traffic, on_chip, rng)
                if traffic["driver"] == "train"
                else serve_programs(fam, cfg, on_chip))
    os.makedirs(args.out, exist_ok=True)
    for name, traced in programs:
        compiled = traced.lower(lowering_platforms=("tpu",)).compile()
        path = os.path.join(args.out, f"{args.workload}.{name}.hlo.txt")
        with open(path, "w") as f:
            f.write(stripped(compiled))
        print(f"{path}: {os.path.getsize(path)} bytes", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
