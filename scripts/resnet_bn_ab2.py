"""On-chip probe #6: whole-model A/B of BN restructurings.

Trace probe #5 showed the step's time sunk in backward mega-fusions that
RECOMPUTE the BN-apply chain inside every consumer (wgrad / dgrad / BN
reduce), running at 290-520 GB/s vs the 819 peak.  Variants:

  base     — current code (XLA recomputes xhat per consumer)
  barrier  — optimization_barrier on BN forward output: forces the
             normalized tensor to materialize once, consumers read it
  cvjp     — custom_vjp BN(+relu): saves xhat + invstd; backward is the
             classic two-pass formula over saved tensors (no recompute,
             no conv inside reduce fusions)
"""
import sys
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


def barrier_forward(self, inputs, weights, *, training=False, rng=None):
    y, rm, rv = orig_forward(self, inputs, weights, training=training, rng=rng)
    return [lax.optimization_barrier(y), rm, rv]


# ---- custom_vjp BN(+relu) training path -------------------------------
import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _bn_train(x, gamma, beta, axes, bshape, eps, relu):
    y, *_ = _bn_fwd_core(x, gamma, beta, axes, bshape, eps, relu)
    return y


def _bn_fwd_core(x, gamma, beta, axes, bshape, eps, relu):
    mean = jnp.mean(x, axis=axes, dtype=jnp.float32)
    var = jnp.maximum(
        jnp.mean(jnp.square(x.astype(jnp.float32)), axis=axes) - jnp.square(mean),
        0.0)
    invstd = lax.rsqrt(var + eps)
    xhat = ((x.astype(jnp.float32) - mean.reshape(bshape))
            * invstd.reshape(bshape)).astype(x.dtype)
    y = xhat * gamma.reshape(bshape).astype(x.dtype) \
        + beta.reshape(bshape).astype(x.dtype)
    if relu:
        y = jax.nn.relu(y)
    return y, xhat, invstd, mean, var


def _bn_fwd(x, gamma, beta, axes, bshape, eps, relu):
    y, xhat, invstd, _, _ = _bn_fwd_core(x, gamma, beta, axes, bshape, eps, relu)
    return y, (xhat, invstd, gamma, y if relu else None)


def _bn_bwd(axes, bshape, eps, relu, res, dy):
    xhat, invstd, gamma, y = res
    if relu:
        dy = jnp.where(y > 0, dy, jnp.zeros_like(dy))
    n = 1
    for a in axes:
        n *= xhat.shape[a]
    dyf = dy.astype(jnp.float32)
    xf = xhat.astype(jnp.float32)
    dbeta = jnp.sum(dyf, axis=axes)
    dgamma = jnp.sum(dyf * xf, axis=axes)
    g = gamma.astype(jnp.float32) * invstd
    dx = (g.reshape(bshape) * (dyf - (dbeta / n).reshape(bshape)
                               - xf * (dgamma / n).reshape(bshape))).astype(xhat.dtype)
    return dx, dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype)


_bn_train.defvjp(_bn_fwd, _bn_bwd)


def cvjp_forward(self, inputs, weights, *, training=False, rng=None):
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
    new_rmean = p.momentum * rmean + (1 - p.momentum) * mean.astype(rmean.dtype)
    new_rvar = p.momentum * rvar + (1 - p.momentum) * var.astype(rvar.dtype)
    y = _bn_train(x, gamma, beta, axes, bshape, p.eps, p.relu)
    return [y, new_rmean, new_rvar]


variants = [("base", orig_forward), ("barrier", barrier_forward),
            ("cvjp", cvjp_forward)]
for name, fwd in variants:
    norm_mod.BatchNorm.forward = fwd
    try:
        dt, loss = build()
        print(f"{name:8s}: {dt*1e3:7.2f} ms/step  ({B/dt:6.0f} img/s)  loss={loss:.4f}",
              flush=True)
    except Exception as e:
        print(f"{name:8s}: FAILED {type(e).__name__}: {e}", flush=True)
norm_mod.BatchNorm.forward = orig_forward
