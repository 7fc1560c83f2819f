"""On-chip probe #7: cvjp2 — BN backward over raw-x reductions.

dgamma = s*sum(dy*x) + (-mean*s)*sum(dy);  dbeta = sum(dy)
dx = gamma*s*(dy - sum_dy/n - xhat*sum_dyxhat/n), xhat recomputed
     elementwise inside the dx pass (x is read there anyway).

Forward identical to base (precomputed scale/shift, one fused pass, no
xhat materialization).  Backward: exactly two passes over (dy, x[, y]).
"""
import sys, functools
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
from flexflow_tpu.ops import norm as norm_mod
from flexflow_tpu.ops.norm import BatchNormParams

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


orig_forward = norm_mod.BatchNorm.forward


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _bn_apply(x, gamma, beta, mean, invstd, axes, bshape, relu):
    scale = gamma.astype(jnp.float32) * invstd
    shift = beta.astype(jnp.float32) - mean * scale
    y = x * scale.reshape(bshape).astype(x.dtype) \
        + shift.reshape(bshape).astype(x.dtype)
    if relu:
        y = jax.nn.relu(y)
    return y


def _bn_apply_fwd(x, gamma, beta, mean, invstd, axes, bshape, relu):
    y = _bn_apply(x, gamma, beta, mean, invstd, axes, bshape, relu)
    return y, (x, gamma, mean, invstd, y if relu else None)


def _bn_apply_bwd(axes, bshape, relu, res, dy):
    x, gamma, mean, invstd, y = res
    if relu:
        dy = jnp.where(y > 0, dy, jnp.zeros_like(dy))
    n = 1
    for a in axes:
        n *= x.shape[a]
    dyf = dy.astype(jnp.float32)
    sum_dy = jnp.sum(dyf, axis=axes)
    sum_dyx = jnp.sum(dyf * x.astype(jnp.float32), axis=axes)
    s = invstd
    sum_dyxhat = s * sum_dyx - mean * s * sum_dy
    dgamma = sum_dyxhat
    dbeta = sum_dy
    gs = (gamma.astype(jnp.float32) * s).reshape(bshape)
    c1 = (sum_dy / n).reshape(bshape)
    c2 = (sum_dyxhat / n).reshape(bshape)
    ms = (mean * s).reshape(bshape)
    sb = s.reshape(bshape)
    # xhat recomputed inline: x*sb - ms
    dx = (gs * (dyf - c1 - (x.astype(jnp.float32) * sb - ms) * c2)).astype(x.dtype)
    return (dx, dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype),
            jnp.zeros_like(mean), jnp.zeros_like(invstd))


_bn_apply.defvjp(_bn_apply_fwd, _bn_apply_bwd)


def cvjp2_forward(self, inputs, weights, *, training=False, rng=None):
    (x,) = inputs
    p: BatchNormParams = self.params
    gamma, beta, rmean, rvar = weights
    nhwc = getattr(self, "_data_layout", "nchw") == "nhwc"
    axes = (0, 1, 2) if nhwc else (0, 2, 3)
    bshape = (1, 1, 1, -1) if nhwc else (1, -1, 1, 1)
    if not training:
        return orig_forward(self, inputs, weights, training=training, rng=rng)
    mean = jnp.mean(x, axis=axes, dtype=jnp.float32)
    var = jnp.maximum(
        jnp.mean(jnp.square(x.astype(jnp.float32)), axis=axes) - jnp.square(mean),
        0.0)
    invstd = lax.rsqrt(var + p.eps)
    new_rmean = p.momentum * rmean + (1 - p.momentum) * mean.astype(rmean.dtype)
    new_rvar = p.momentum * rvar + (1 - p.momentum) * var.astype(rvar.dtype)
    y = _bn_apply(x, gamma, beta, lax.stop_gradient(mean),
                  lax.stop_gradient(invstd), axes, bshape, p.relu)
    return [y, new_rmean, new_rvar]


for name, fwd in [("base", orig_forward), ("cvjp2", cvjp2_forward)]:
    norm_mod.BatchNorm.forward = fwd
    try:
        dt, loss = build()
        print(f"{name:8s}: {dt*1e3:7.2f} ms/step  ({B/dt:6.0f} img/s)  loss={loss:.4f}",
              flush=True)
    except Exception as e:
        print(f"{name:8s}: FAILED {type(e).__name__}: {e}", flush=True)
norm_mod.BatchNorm.forward = orig_forward
