"""On-chip probe #4: whole-model A/B of candidate ResNet-50 step
optimizations (a microbench is blind to the fusion context the real
step gives an op; the steady-state step time with a fetched loss is
the clock that counts).

Variants (monkeypatched, no repo change until a win is measured):
  base     — current code
  dot1x1   — 1x1 convs as lax.dot_general (XLA can epilogue-fuse into a
             dot; it cannot fuse into a conv custom-call); stride-2
             downsample 1x1 convs slice first (reads 1/4 of x)
"""
import sys, time
import os
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

dev = jax.devices()[0]
print("device:", dev, flush=True)

import bench
from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.torch_frontend.model import PyTorchModel
from flexflow_tpu.ops import dense as dense_mod
from flexflow_tpu.ops.dense import Conv2DParams, apply_activation

leg = bench.MANIFEST["legs"]["resnet50"]
sys.path.insert(0, os.path.join(_REPO, "examples", "python", "pytorch"))
from resnet50_search import ResNet50

B, px = leg["batch"], leg["px"]


def build():
    cfg = FFConfig(batch_size=B, num_devices=1, compute_dtype="bfloat16")
    ff = FFModel(cfg)
    x = ff.create_tensor([B, 3, px, px], name="input")
    (out,) = PyTorchModel(ResNet50(classes=leg["classes"])).torch_to_ff(ff, [x])
    ff.softmax(out)
    ff.compile(optimizer=SGDOptimizer(lr=0.1),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=[dev])
    r = np.random.RandomState(0)
    xs = jax.device_put(r.randn(B, 3, px, px).astype(np.float32),
                        ff.executor.input_shardings()["input"])
    ys = jax.device_put(r.randint(0, leg["classes"], B).astype(np.int32),
                        ff.executor.label_sharding())
    for _ in range(3):
        m = ff.train_step({"input": xs}, ys)
    loss = float(m["loss"])
    dt = bench._steady_state(ff, {"input": xs}, ys, 40)
    return dt, loss


orig_forward = dense_mod.Conv2D.forward


def dot1x1_forward(self, inputs, weights, *, training=False, rng=None):
    (x,) = inputs
    p: Conv2DParams = self.params
    nhwc = getattr(self, "_data_layout", "nchw") == "nhwc"
    if (nhwc and tuple(p.kernel) == (1, 1) and tuple(p.padding) == (0, 0)
            and p.groups == 1):
        w = weights[0]
        wt = jnp.transpose(w.reshape(w.shape[0], w.shape[1]), (1, 0)).astype(x.dtype)
        xs = x if tuple(p.stride) == (1, 1) else x[:, ::p.stride[0], ::p.stride[1], :]
        y = lax.dot_general(xs, wt, (((3,), (0,)), ((), ())))
        if p.use_bias:
            y = y + weights[1][None, None, None, :]
        return [apply_activation(y, p.activation)]
    return orig_forward(self, inputs, weights, training=training, rng=rng)


for name, fwd in [("base", orig_forward), ("dot1x1", dot1x1_forward)]:
    dense_mod.Conv2D.forward = fwd
    dt, loss = build()
    print(f"{name:8s}: {dt*1e3:7.2f} ms/step  ({B/dt:6.0f} img/s)  loss={loss:.4f}",
          flush=True)
dense_mod.Conv2D.forward = orig_forward
