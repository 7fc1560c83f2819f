"""On-chip probe #5: jax profiler trace of the resnet bench step; parse
the device trace for the top ops by self time (replaces byte-model
guesswork with measured per-op time)."""
import sys, glob, gzip, json, collections
import os
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
import numpy as np
import jax

dev = jax.devices()[0]
print("device:", dev, flush=True)

import bench
from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.torch_frontend.model import PyTorchModel

leg = bench.MANIFEST["legs"]["resnet50"]
sys.path.insert(0, os.path.join(_REPO, "examples", "python", "pytorch"))
from resnet50_search import ResNet50
B, px = leg["batch"], leg["px"]

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
for _ in range(5):
    m = ff.train_step({"input": xs}, ys)
print("warm, loss", float(m["loss"]), flush=True)

import shutil
shutil.rmtree("/tmp/restrace", ignore_errors=True)
with jax.profiler.trace("/tmp/restrace"):
    for _ in range(3):
        m = ff.train_step({"input": xs}, ys)
    _ = float(m["loss"])
print("trace captured", flush=True)

# parse the trace proto (xplane) via tensorflow-free reader if available,
# else the trace.json.gz event file
files = glob.glob("/tmp/restrace/**/*.trace.json.gz", recursive=True)
print("trace files:", files, flush=True)
if files:
    ev = json.load(gzip.open(files[0]))
    events = ev.get("traceEvents", [])
    # restrict to the device "XLA Ops" lane (thread_name metadata) —
    # summing every pid/tid would mix host TraceMe spans (which cover
    # whole steps) with device self-time and double-count derived lanes
    op_lanes = set()
    for e in events:
        if (e.get("ph") == "M" and e.get("name") == "thread_name"
                and (e.get("args") or {}).get("name") == "XLA Ops"):
            op_lanes.add((e.get("pid"), e.get("tid")))
    agg = collections.Counter()
    cnt = collections.Counter()
    for e in events:
        if e.get("ph") != "X" or (e.get("pid"), e.get("tid")) not in op_lanes:
            continue
        base = e.get("name", "").rstrip("0123456789").rstrip(".")
        agg[base] += e.get("dur", 0)  # us
        cnt[base] += 1
    tot = sum(agg.values())
    print(f"\ndevice op time: {tot/1e3:.1f} ms over 3 steps "
          f"= {tot/3e3:.2f} ms/step", flush=True)
    print("\n-- top device op groups (us over 3 steps) --", flush=True)
    for name, d in agg.most_common(40):
        print(f"{d:10.0f} us  n={cnt[name]:4d}  {name[:90]}", flush=True)
else:
    xp = glob.glob("/tmp/restrace/**/*.xplane.pb", recursive=True)
    print("no trace.json.gz; xplane files:", xp, flush=True)
