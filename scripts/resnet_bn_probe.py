"""On-chip probe: where does the ResNet-50 bench step spend its HBM
traffic, and can a Pallas fused BN-apply pass beat XLA's?

Runs three measurements (manifest workload, b256 224px bf16):
 1. full step (baseline);
 2. eval-mode BN (no batch-stats pass: apply from running stats) —
    isolates the stats-read cost;
 3. XLA cost-analysis bytes accessed vs the model's theoretical
    minimum HBM traffic.
Plus a microbench: XLA fused bn-apply+relu+residual vs a Pallas
single-pass kernel at representative resnet shapes.
"""
import sys, time
import os
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
import numpy as np
import jax
import jax.numpy as jnp

dev = jax.devices()[0]
print("device:", dev, flush=True)

# --- microbench: fused bn-apply+relu+add, XLA vs Pallas ---------------
from jax.experimental import pallas as pl

def xla_apply(x, scale, shift, res):
    return jax.nn.relu(x * scale + shift + res)

def pallas_apply(x, scale, shift, res, rows=256):
    M, C = x.shape
    def kernel(x_ref, s_ref, b_ref, r_ref, o_ref):
        o_ref[...] = jnp.maximum(
            x_ref[...] * s_ref[...] + b_ref[...] + r_ref[...], 0.0
        ).astype(o_ref.dtype)
    grid = (M // rows,)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((rows, C), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, C), x.dtype),
    )(x, scale, shift, res)

def best_of(fn, *args, iters=30, windows=3):
    f = jax.jit(fn)
    r = f(*args); r.block_until_ready()
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            r = f(*args)
        r.block_until_ready()
        best = min(best, (time.perf_counter() - t0) / iters)
    return best, r

rng = np.random.RandomState(0)
print("\n-- microbench: bn-apply+relu+residual (bf16) --", flush=True)
for (m, c) in [(256*56*56, 256), (256*28*28, 512), (256*14*14, 1024), (256*7*7, 2048)]:
    x = jax.device_put(jnp.asarray(rng.randn(m, c), jnp.bfloat16), dev)
    res = jax.device_put(jnp.asarray(rng.randn(m, c), jnp.bfloat16), dev)
    scale = jax.device_put(jnp.asarray(rng.rand(1, c) + 0.5, jnp.bfloat16), dev)
    shift = jax.device_put(jnp.asarray(rng.randn(1, c) * 0.1, jnp.bfloat16), dev)
    t_xla, r1 = best_of(xla_apply, x, scale, shift, res)
    t_pal, r2 = best_of(pallas_apply, x, scale, shift, res)
    ok = np.allclose(np.asarray(r1, np.float32), np.asarray(r2, np.float32), rtol=1e-2)
    bytes_min = (2 * m * c + m * c) * 2  # read x+res, write y, bf16
    bw = lambda t: bytes_min / t / 1e9
    print(f"[{m:9d} x {c:4d}] XLA {t_xla*1e6:7.1f}us ({bw(t_xla):5.0f} GB/s)  "
          f"Pallas {t_pal*1e6:7.1f}us ({bw(t_pal):5.0f} GB/s)  match={ok}", flush=True)

# --- whole-model: baseline vs eval-mode BN ----------------------------
print("\n-- whole model --", flush=True)
import bench
leg = bench.MANIFEST["legs"]["resnet50"]
sys.path.insert(0, os.path.join(_REPO, "examples", "python", "pytorch"))
from resnet50_search import ResNet50
from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.torch_frontend.model import PyTorchModel

def build_and_time(batch=leg["batch"], px=leg["px"]):
    cfg = FFConfig(batch_size=batch, num_devices=1, compute_dtype="bfloat16")
    ff = FFModel(cfg)
    x = ff.create_tensor([batch, 3, px, px], name="input")
    (out,) = PyTorchModel(ResNet50(classes=leg["classes"])).torch_to_ff(ff, [x])
    ff.softmax(out)
    ff.compile(optimizer=SGDOptimizer(lr=0.1),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=[dev])
    r = np.random.RandomState(0)
    xs = jax.device_put(r.randn(batch, 3, px, px).astype(np.float32),
                        ff.executor.input_shardings()["input"])
    ys = jax.device_put(r.randint(0, leg["classes"], batch).astype(np.int32),
                        ff.executor.label_sharding())
    for _ in range(3):
        m = ff.train_step({"input": xs}, ys)
    _ = float(m["loss"])
    dt = bench._steady_state(ff, {"input": xs}, ys, 40)
    return ff, dt, xs, ys

B = leg["batch"]
ff, dt, xs, ys = build_and_time()
print(f"baseline: {dt*1e3:.2f} ms/step ({B/dt:.0f} img/s)", flush=True)

# cost analysis of the train step: lower the executor's jitted step
# with the live argument pytrees (signature: weights, opt_state, state,
# inputs, labels, rng — model.train_step's call)
try:
    m = ff  # FFModel holds the live pytrees
    step = m.executor._step_fn
    import jax.random as jr
    lowered = step.lower(m._weights, m._opt_state, m._state,
                         {"input": xs}, ys, jr.key(0))
    an = lowered.compile().cost_analysis()
except Exception as e:
    an = None
    print("cost_analysis unavailable:", e, flush=True)
if an:
    ba = an.get("bytes accessed", None)
    fl = an.get("flops", None)
    print(f"bytes accessed/step: {ba}", flush=True)
    if ba:
        print(f"  = {ba/dt/1e9:.0f} GB/s effective (chip HBM ~819 GB/s)",
              flush=True)
    print(f"flops/step: {fl}", flush=True)

# no-BN ceiling: the native builder (models/resnet.py mirrors the
# reference resnet.cc, which has no BatchNorm)
from flexflow_tpu.models.resnet import build_resnet50
cfg = FFConfig(batch_size=B, num_devices=1, compute_dtype="bfloat16")
ff2 = FFModel(cfg)
build_resnet50(ff2, batch_size=B, image_size=leg["px"], num_classes=leg["classes"])
ff2.compile(optimizer=SGDOptimizer(lr=0.1),
            loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
            devices=[dev])
r = np.random.RandomState(0)
xs = jax.device_put(r.randn(B, 3, leg["px"], leg["px"]).astype(np.float32),
                    ff2.executor.input_shardings()["input"])
ys = jax.device_put(r.randint(0, leg["classes"], B).astype(np.int32),
                    ff2.executor.label_sharding())
for _ in range(3):
    m = ff2.train_step({"input": xs}, ys)
_ = float(m["loss"])
dt2 = bench._steady_state(ff2, {"input": xs}, ys, 40)
print(f"no-BN ceiling: {dt2*1e3:.2f} ms/step ({B/dt2:.0f} img/s); "
      f"BN/elementwise share = {(dt-dt2)/dt*100:.1f}%", flush=True)
