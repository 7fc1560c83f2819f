#!/usr/bin/env python3
"""Rehearsal 3 for a training cell: compile its jitted train step, and
the family's reference gradient, at the REAL size for a described
v5e chip in the sandbox, and print what the compiler says each needs.

    JAX_PLATFORMS=cpu python3 scripts/compile_train_cell.py \
        --workload lfm2-8b-a1b-ep4-train.seq4096-1chip [--batch N] [--no-reference]

Nothing runs and no time comes out of it.  The graph is built and
`FFModel.compile` is walked on the CPU with the weights left abstract;
while the step is traced `jax.default_backend` is made to answer "tpu",
so that every choice by backend (`pick_tiling`, the flash kernels) is
the chip's.  The compiler counts one program at a time: add what the
process keeps beside it (the program's weights and Adam state while the
reference runs).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import run as harness  # noqa: E402  (benchmarks/run.py)


def gb(n):
    return f"{n / 1e9:.2f} GB"


def report(name, compiled, seconds):
    m = compiled.memory_analysis()
    print(f"{name}: compiled in {seconds:.0f} s; arguments "
          f"{gb(m.argument_size_in_bytes)}, outputs "
          f"{gb(m.output_size_in_bytes)} (aliased "
          f"{gb(m.alias_size_in_bytes)}), temporaries "
          f"{gb(m.temp_size_in_bytes)}; at once "
          f"{gb(m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes)}",
          flush=True)
    return compiled


def abstract_train_model(fam, cfg, batch: int, seq: int, devices=None):
    """The family's model built and compiled on the CPU with its weights
    and Adam state left ABSTRACT (`jax.eval_shape`): the real size costs
    no memory.  Shared with `dump_step_hlo.py`.  `devices`: the described
    chips of a compile-only topology to lay the mesh over instead of the
    first CPU device (`grad_overlap_probe.py`: four); nothing can be
    placed on those, so the step counter stays abstract too."""
    import flexflow_tpu.optimizer as opt_mod
    from flexflow_tpu.executor import GraphExecutor

    real_init = GraphExecutor.init_weights
    GraphExecutor.init_weights = lambda self, seed=0, state_only=False: \
        jax.eval_shape(lambda: real_init(self, seed, state_only))
    real_state = opt_mod.AdamOptimizer.init_state

    described = devices is not None

    def abstract_state(self, w):
        state = jax.eval_shape(lambda: real_state(self, jax.tree.map(
            lambda x: jnp.zeros(x.shape, x.dtype), w)))
        if described:
            return state
        # the step counter is placed on the mesh: a real scalar
        return {k: v if isinstance(v, dict) else jnp.zeros(v.shape, v.dtype)
                for k, v in state.items()}

    opt_mod.AdamOptimizer.init_state = abstract_state
    if described:
        GraphExecutor.shard_opt_state = lambda self, opt_state: opt_state
    else:
        devices = jax.devices()[:1]
    ff = fam.build_model(cfg, batch, seq, len(devices))
    fam.compile_model(ff, cfg, devices)
    return ff


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, default=0,
                    help="another batch_per_chip than the traffic file's")
    ap.add_argument("--no-reference", action="store_true")
    ap.add_argument("--no-step", action="store_true")
    ap.add_argument("--remat", type=int, choices=(0, 1), default=None,
                    help="override the configuration's assumed.remat")
    ap.add_argument("--keep", default=None,
                    help="what a checkpointed segment keeps (a key of "
                         "executor._REMAT_POLICIES; default: the first)")
    args = ap.parse_args()
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = harness.load_json(os.path.join(ROOT, entry["file"]))
    traffic = harness.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", cell["traffic"] + ".json"))
    if args.remat is not None:
        cfg["assumed"]["remat"] = bool(args.remat)
    fam = harness.load_module("families", cfg["family"])
    batch, seq = args.batch or traffic["batch_per_chip"], traffic["seq"]

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    if not args.no_step:
        ff = abstract_train_model(fam, cfg, batch, seq)
        inputs, labels = fam.make_batch(cfg, batch, seq,
                                        np.random.default_rng(0))
        structs = on_chip((ff._weights, ff._opt_state, ff._state,
                           {k: jnp.asarray(v) for k, v in inputs.items()},
                           jnp.asarray(labels)))
        rng = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                   sharding=chip)
        jax.default_backend = lambda: "tpu"
        if args.keep:
            ff.executor.remat_keep = args.keep
        t0 = time.monotonic()
        traced = ff._step_fn.trace(*structs, rng)
        if getattr(ff.executor, "remat_segments", 0):
            from flexflow_tpu.executor import remat_kept_bytes

            print(f"  {ff.executor.remat_segments} segments checkpointed, "
                  f"keeping {ff.executor.remat_keep}: "
                  f"{gb(remat_kept_bytes(traced.jaxpr.jaxpr, ff.executor._remat_plan))}"
                  " of kept values", flush=True)
        compiled = traced.lower(lowering_platforms=("tpu",)).compile()
        report(f"train step, batch {batch} x seq {seq}", compiled,
               time.monotonic() - t0)
        text = compiled.as_text()
        print("  tpu_custom_call sites:", text.count("tpu_custom_call"),
              "; ragged-dot custom calls:",
              text.count('custom_call_target="RaggedDot') or
              text.lower().count("ragged"), flush=True)
        out = os.path.join(ROOT, "chiprun_out")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, "compile_train_cell.hlo.txt"), "w") as f:
            f.write(text)
    if not args.no_reference:
        jax.default_backend = lambda: "cpu"
        shapes = jax.eval_shape(
            lambda: fam.to_reference_layout(jax.tree.map(
                lambda s: jnp.zeros(s, jnp.float32), fam.op_shapes(cfg),
                is_leaf=lambda x: isinstance(x, tuple))))
        ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=chip)
        t0 = time.monotonic()
        compiled = fam._grads.trace(
            on_chip(shapes), ids, ids, json.dumps(cfg, sort_keys=True),
            "float32").lower(lowering_platforms=("tpu",)).compile()
        report("reference gradient (float32, highest)", compiled,
               time.monotonic() - t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
