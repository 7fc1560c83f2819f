"""Compare XLA bytes-accessed of resnet step variants (no timing needed,
cost_analysis is exact for static shapes): did the dot form let XLA fuse
the BN stats pass into the GEMM (bytes drop ~4GB) or not?"""
import sys
import os
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

dev = jax.devices()[0]
import bench
from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.torch_frontend.model import PyTorchModel
from flexflow_tpu.ops import dense as dense_mod
from flexflow_tpu.ops.dense import Conv2DParams, apply_activation

leg = bench.MANIFEST["legs"]["resnet50"]
sys.path.insert(0, os.path.join(_REPO, "examples", "python", "pytorch"))
from resnet50_search import ResNet50
B, px = leg["batch"], leg["px"]


def build_lowered():
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
    import jax.random as jr
    lowered = ff.executor._step_fn.lower(
        ff._weights, ff._opt_state, ff._state, {"input": xs}, ys, jr.key(0))
    an = lowered.compile().cost_analysis()
    return an.get("bytes accessed"), an.get("flops")


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
    b, f = build_lowered()
    print(f"{name:8s}: bytes={b/1e9:.2f} GB  flops={f/1e12:.2f} TF", flush=True)
dense_mod.Conv2D.forward = orig_forward
