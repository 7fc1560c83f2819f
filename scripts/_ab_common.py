"""Shared measurement harness for the scripts/ A/B kits
(inception_taso_ab.py, catalog_mlp_ab.py): warmup + device-resident
batch + INTERLEAVED best-of-N windows, so any time-correlated drift
(clock, thermals, a noisy host) hits every variant equally."""
from __future__ import annotations

import sys
import time
from typing import Callable, Dict, List, Sequence, Tuple


def make_train_window(ff, inputs, labels, iters: int) -> Callable[[], float]:
    """Device-put the batch, warm up, and return a window() closure
    measuring seconds/step over `iters` serial steps with ONE hard
    sync (fetching the loss drains the donated-weight chain)."""
    import jax

    put = {
        k: jax.device_put(v, ff.executor.input_shardings()[k])
        for k, v in inputs.items()
    }
    ys = jax.device_put(labels, ff.executor.label_sharding())
    for _ in range(3):
        m = ff.train_step(put, ys)
    _ = float(m["loss"])

    def window() -> float:
        t0 = time.perf_counter()
        for _ in range(iters):
            m = ff.train_step(put, ys)
        _ = float(m["loss"])
        return (time.perf_counter() - t0) / iters

    return window


def interleaved_best(windows: Dict[str, Callable[[], float]],
                     rounds: int) -> Dict[str, List[float]]:
    """Run each variant's window once per round, A/B/A/B...; returns
    per-variant per-round seconds/step."""
    samples: Dict[str, List[float]] = {tag: [] for tag in windows}
    for r in range(rounds):
        for tag, win in windows.items():
            samples[tag].append(win())
        print(f"window {r}: " + " ".join(
            f"{tag}={samples[tag][-1]*1e3:.2f}ms" for tag in windows),
            file=sys.stderr)
    return samples


def summarize(samples: Dict[str, List[float]]) -> Dict[str, Dict]:
    return {
        tag: {
            "step_ms": round(min(s) * 1e3, 3),
            "window_ms": [round(x * 1e3, 3) for x in s],
        }
        for tag, s in samples.items()
    }
