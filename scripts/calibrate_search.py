"""Fit the search's overlap constants from measured step times.

Runs a fixed MLP under dp / dp x tp / tp strategies on the live
backend, measures real steady-state step times, and least-squares fits
`overlap_fraction` / `sync_overlap_fraction` (sim/calibrate.py).  The
fitted constants persist beside the op-cost cache
(~/.cache/flexflow_tpu/overlap_constants.json) and are picked up by
both search entry points on the next run.

So far this has only been run on the hermetic CPU mesh; on a
multi-chip host the same command refits against ICI (not yet done on
the four-chip v5e — ROADMAP Queue 1).  Usage:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python scripts/calibrate_search.py [--out PATH]
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--out", default=None, help="constants JSON path")
    p.add_argument("-n", "--num-devices", type=int, default=8)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--hidden", type=int, default=1024)
    args = p.parse_args()

    import jax
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.fftype import ActiMode
    from flexflow_tpu.ops.op import ShardConfig
    from flexflow_tpu.sim.calibrate import (calibrate_overlap,
                                            save_overlap_constants)
    from flexflow_tpu.sim.machine_model import SimpleMachineModel
    from flexflow_tpu.sim.simulator import make_cost_model
    from flexflow_tpu.strategy import Strategy, data_parallel_strategy

    n = args.num_devices
    devices = jax.devices()[:n]
    batch, hidden = args.batch, args.hidden

    def build():
        ff = FFModel(FFConfig(batch_size=batch, num_devices=n))
        x = ff.create_tensor([batch, hidden], name="x")
        t = x
        for i in range(4):
            t = ff.dense(t, hidden, activation=ActiMode.RELU, name=f"fc{i}")
        ff.dense(t, 8, name="head")
        return ff

    def make_inputs(ff):
        rs = np.random.RandomState(0)
        xs = jax.device_put(rs.randn(batch, hidden).astype(np.float32),
                            ff.executor.input_shardings()["x"])
        ys = jax.device_put(rs.randint(0, 8, batch).astype(np.int32),
                            ff.executor.label_sharding())
        return {"x": xs}, ys

    def megatron(tp_degree, dp_degree):
        axes = {}
        if dp_degree > 1:
            axes["data"] = dp_degree
        axes["model"] = tp_degree
        s = Strategy(mesh_axes=axes)
        if dp_degree > 1:
            s.edge_ops["__inputs__"] = [
                ("repartition", {"dim": 0, "degree": dp_degree})]
        for i in range(4):
            s.shard_configs[f"fc{i}"] = ShardConfig(
                channel=tp_degree if i % 2 == 0 else 1,
                reduction=1 if i % 2 == 0 else tp_degree,
            )
        return s

    half = max(2, n // 2)
    strategies = [
        (data_parallel_strategy(1), 1),  # anchors the compute scale
        (data_parallel_strategy(n), n),
        (megatron(half, n // half), n),
        (megatron(n, 1), n),
    ]

    machine = SimpleMachineModel(num_nodes=1, devices_per_node=n)
    cost_model = make_cost_model(FFConfig(num_devices=n), machine)
    fit = calibrate_overlap(build, strategies, devices, machine,
                            cost_model, make_inputs)
    path = save_overlap_constants(fit, args.out)
    print(f"fitted: {fit} -> {path}")


if __name__ == "__main__":
    main()
