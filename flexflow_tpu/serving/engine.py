"""Inference engine: static-strategy compiled forward with batch
buckets (reference triton/src: ONNX parse -> static LayerStrategy ->
Legion inference; here ONNX/torch/Keras all funnel through FFModel and
the engine jits its forward per power-of-two batch bucket)."""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from ..fftype import CompMode
from ..model import FFModel


def _value_info_shape(vi):
    """Static dims (None for symbolic) from a graph input, covering both
    the vendored protowire.ValueInfo and onnx's ValueInfoProto."""
    shape = getattr(vi, "shape", None)
    if shape is not None or not hasattr(vi, "type"):
        return shape
    dims = []
    for d in vi.type.tensor_type.shape.dim:
        dims.append(d.dim_value if d.dim_value > 0 else None)
    return dims or None


def _bucket(n: int, max_batch: int, multiple: int = 1) -> int:
    """Next power of two >= n, rounded up to `multiple` (the mesh's
    data-axis size — every bucket must shard evenly).  The cap is the
    largest multiple of `multiple` <= max_batch (at least `multiple`),
    so the invariant holds even when max_batch itself doesn't divide."""
    cap = max((max_batch // multiple) * multiple, multiple)
    b = 1
    while b < n:
        b <<= 1
    if b % multiple:
        b = ((b + multiple - 1) // multiple) * multiple
    return min(max(b, multiple), cap)


class InferenceEngine:
    """Wraps a compiled FFModel for inference: pads requests to the
    next power-of-two bucket, runs the jitted forward, strips padding.

    `from_onnx` mirrors the Triton backend's model source; any FFModel
    (hand-built, torch.fx- or Keras-imported) works via `__init__`.
    """

    def __init__(self, ff: FFModel, max_batch: int = 64):
        if ff.executor is None:
            raise ValueError("compile() the model before serving it")
        self.ff = ff
        self.max_batch = max_batch
        self._fwd = ff.executor.build_forward()
        self._input_names = [op.name for op in ff.layers.source_ops()]
        self.requests_served = 0

    @classmethod
    def from_onnx(cls, path: str, batch_size: int = 64, devices=None,
                  strategy=None, **kwargs) -> "InferenceEngine":
        from ..config import FFConfig
        from ..onnx_frontend.model import ONNXModel

        cfg = FFConfig(batch_size=batch_size)
        ff = FFModel(cfg)
        om = ONNXModel(path)
        tensors = []
        for vi in om.graph.input:
            if vi.name in om.initializers:
                continue
            shape = _value_info_shape(vi)
            if not shape or any(d is None for d in shape[1:]):
                raise ValueError(
                    f"ONNX input {vi.name!r} needs a static shape to "
                    f"serve (got {shape}); re-export with fixed dims"
                )
            tensors.append(
                ff.create_tensor([batch_size] + [int(d) for d in shape[1:]],
                                 name=vi.name)
            )
        om.apply(ff, tensors)
        ff.compile(comp_mode=CompMode.INFERENCE, strategy=strategy,
                   devices=devices)
        om.copy_weights(ff)
        return cls(ff, max_batch=batch_size, **kwargs)

    def chunk_cap(self) -> int:
        """Largest request slice one jitted forward takes: max_batch
        rounded down to the mesh's data-axis multiple (single source of
        the sharding invariant for infer() and the DynamicBatcher)."""
        dp = self.ff.mesh.shape.get("data", 1) if self.ff.mesh else 1
        return max((self.max_batch // dp) * dp, dp)

    # ------------------------------------------------------------------
    def infer(self, inputs: Dict[str, np.ndarray]) -> np.ndarray:
        """One batch (any size <= max_batch * k — larger requests are
        chunked); returns the sink output as numpy."""
        n = len(next(iter(inputs.values())))
        chunk_cap = self.chunk_cap()
        outs: List[np.ndarray] = []
        start = 0
        while start < n:
            take = min(chunk_cap, n - start)
            chunk = {k: v[start:start + take] for k, v in inputs.items()}
            outs.append(self._infer_bucketed(chunk, take))
            start += take
        self.requests_served += 1
        return np.concatenate(outs) if len(outs) > 1 else outs[0]

    def _infer_bucketed(self, chunk: Dict[str, np.ndarray], n: int) -> np.ndarray:
        return np.asarray(self.dispatch(chunk, n))[:n]

    def dispatch(self, chunk: Dict[str, np.ndarray], n: int):
        """ASYNC half of a bucketed forward: pad to the bucket, device_put,
        launch the jitted forward, and return the device array WITHOUT
        waiting — jax dispatch is asynchronous, so the caller can overlap
        assembling the next batch with this one's device time (the
        DynamicBatcher's pipeline).  `np.asarray(result)[:n]` completes it."""
        import jax

        dp = self.ff.mesh.shape.get("data", 1) if self.ff.mesh else 1
        b = _bucket(n, self.max_batch, multiple=dp)
        padded = {}
        for k, v in chunk.items():
            if len(v) < b:
                pad = np.zeros((b - len(v),) + v.shape[1:], v.dtype)
                v = np.concatenate([v, pad])
            padded[k] = v
        sh = self.ff.executor.input_shardings()
        put = {k: jax.device_put(v, sh[k]) for k, v in padded.items()}
        return self._fwd(self.ff._weights, self.ff._state, put)

    def input_names(self) -> Sequence[str]:
        return list(self._input_names)

    def input_specs(self) -> Dict[str, np.dtype]:
        """name -> numpy dtype of each model input (from the compiled
        tensor specs, so HTTP payloads need not guess)."""
        return {
            op.name: op.outputs[0].shape.dtype.np_dtype  # jnp: knows bf16
            for op in self.ff.layers.source_ops()
        }
