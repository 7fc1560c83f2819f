"""On-chip probe #3: is a 1x1 conv faster as lax.dot_general, and does
XLA fuse a BN-stats reduction into the dot's epilogue (it cannot fuse
into a conv custom-call)?  ResNet-50 b256 shapes, bf16, NHWC."""
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


def timeit(fn, *args, iters=20, windows=3):
    f = jax.jit(fn)
    r = jax.block_until_ready(f(*args))
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            r = f(*args)
        jax.block_until_ready(r)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best, r


def conv_cc(x, w):  # custom-call path, NHWC/OIHW
    return lax.conv_general_dilated(x, w, (1, 1), [(0, 0), (0, 0)],
                                    dimension_numbers=("NHWC", "OIHW", "NHWC"))


def conv_dot(x, w):
    wt = jnp.transpose(w.reshape(w.shape[0], w.shape[1]), (1, 0))
    return lax.dot_general(x, wt, (((3,), (0,)), ((), ())))


def with_stats(conv):
    def f(x, w):
        y = conv(x, w)
        m = jnp.mean(y, axis=(0, 1, 2), dtype=jnp.float32)
        m2 = jnp.mean(jnp.square(y.astype(jnp.float32)), axis=(0, 1, 2))
        return y, m, m2
    return f


def with_apply(conv):  # stats + apply + relu: the full BN train forward
    def f(x, w, res):
        y = conv(x, w)
        m = jnp.mean(y, axis=(0, 1, 2), dtype=jnp.float32)
        m2 = jnp.mean(jnp.square(y.astype(jnp.float32)), axis=(0, 1, 2))
        v = jnp.maximum(m2 - jnp.square(m), 0.0)
        s = lax.rsqrt(v + 1e-5)
        z = jax.nn.relu((y - m.astype(y.dtype)) * s.astype(y.dtype) + res)
        return z, m, m2
    return f


rng = np.random.RandomState(0)
# (B,H,W,Cin,Cout): resnet 1x1 shapes (stage1 conv3, stage2 conv1, stage3 conv1, stage4 conv3)
cases = [(256, 56, 56, 64, 256), (256, 56, 56, 256, 64),
         (256, 28, 28, 512, 128), (256, 14, 14, 1024, 256),
         (256, 7, 7, 512, 2048)]
for (b, h, w_, ci, co) in cases:
    x = jax.device_put(jnp.asarray(rng.randn(b, h, w_, ci), jnp.bfloat16), dev)
    wgt = jax.device_put(jnp.asarray(rng.randn(co, ci, 1, 1) * 0.05, jnp.bfloat16), dev)
    res = jax.device_put(jnp.asarray(rng.randn(b, h, w_, co), jnp.bfloat16), dev)
    t_cc, r1 = timeit(conv_cc, x, wgt)
    t_dot, r2 = timeit(conv_dot, x, wgt)
    ok = np.allclose(np.asarray(r1, np.float32), np.asarray(r2, np.float32),
                     rtol=5e-2, atol=1e-1)
    t_ccs, _ = timeit(with_stats(conv_cc), x, wgt)
    t_dots, _ = timeit(with_stats(conv_dot), x, wgt)
    t_cca, _ = timeit(with_apply(conv_cc), x, wgt, res)
    t_dota, _ = timeit(with_apply(conv_dot), x, wgt, res)
    print(f"[{b}x{h}x{w_} {ci:4d}->{co:4d}] conv {t_cc*1e6:7.1f}us  dot {t_dot*1e6:7.1f}us"
          f" | +stats: conv {t_ccs*1e6:7.1f}  dot {t_dots*1e6:7.1f}"
          f" | +bn+relu+res: conv {t_cca*1e6:7.1f}  dot {t_dota*1e6:7.1f}  match={ok}",
          flush=True)

# stride-2 1x1 (downsample): conv reads full x; slice-then-dot reads 1/4
def conv_cc_s2(x, w):
    return lax.conv_general_dilated(x, w, (2, 2), [(0, 0), (0, 0)],
                                    dimension_numbers=("NHWC", "OIHW", "NHWC"))


def conv_dot_s2(x, w):
    xs = x[:, ::2, ::2, :]
    wt = jnp.transpose(w.reshape(w.shape[0], w.shape[1]), (1, 0))
    return lax.dot_general(xs, wt, (((3,), (0,)), ((), ())))


print("\n-- stride-2 downsample 1x1 --", flush=True)
for (b, h, w_, ci, co) in [(256, 56, 56, 256, 512), (256, 28, 28, 512, 1024),
                           (256, 14, 14, 1024, 2048)]:
    x = jax.device_put(jnp.asarray(rng.randn(b, h, w_, ci), jnp.bfloat16), dev)
    wgt = jax.device_put(jnp.asarray(rng.randn(co, ci, 1, 1) * 0.05, jnp.bfloat16), dev)
    t_cc, r1 = timeit(conv_cc_s2, x, wgt)
    t_dot, r2 = timeit(conv_dot_s2, x, wgt)
    ok = np.allclose(np.asarray(r1, np.float32), np.asarray(r2, np.float32),
                     rtol=5e-2, atol=1e-1)
    print(f"[{b}x{h}x{w_} {ci:4d}->{co:4d}/2] conv {t_cc*1e6:7.1f}us  "
          f"dot {t_dot*1e6:7.1f}us  match={ok}", flush=True)
