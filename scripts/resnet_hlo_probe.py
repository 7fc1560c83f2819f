"""On-chip probe #2: per-fusion byte accounting of the ResNet-50 bench
step.  Dumps the optimized HLO's largest fusions/ops by bytes-accessed
so the margin work targets the real HBM consumers (probe #1 showed the
step at 94.5% of HBM peak: only removing passes can help).
"""
import sys
import collections

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

import jax.random as jr
step = ff.executor._step_fn
lowered = step.lower(ff._weights, ff._opt_state, ff._state,
                     {"input": xs}, ys, jr.key(0))
compiled = lowered.compile()
an = compiled.cost_analysis()
print("total bytes accessed:", an.get("bytes accessed"), flush=True)
print("total flops:", an.get("flops"), flush=True)

# Optimized HLO: bucket instructions by opcode, estimate bytes from
# operand + output shapes (static shapes, so exact).
mod = compiled.runtime_executable().hlo_modules()[0]
txt = mod.to_string()
with open("/tmp/resnet_step_hlo.txt", "w") as f:
    f.write(txt)
print("HLO dumped to /tmp/resnet_step_hlo.txt,", len(txt), "chars", flush=True)

# crude per-opcode census of the entry computation's top-level ops
import re
DTYPE_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
               "pred": 1, "f16": 2, "s64": 8, "u64": 8, "f64": 8}


def shape_bytes(s):
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", s):
        dt, dims = m.group(1), m.group(2)
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


# find ENTRY computation block
entry = re.search(r"ENTRY [^{]+\{(.*)", txt, re.S)
body = entry.group(1) if entry else txt
body = body[: body.index("\n}")] if "\n}" in body else body
ops = collections.Counter()
byts = collections.Counter()
shapes = {}
rows = []
for line in body.splitlines():
    line = line.strip()
    # optimized HLO carries layout/tiling annotations and tuple result
    # types: "%name = (bf16[..]{..}, f32[..]{..}) fusion(%a, %b), ..."
    m = re.match(r"(%[\w.\-]+) = (\(?.*?\)?) ([\w\-]+)\((.*)", line)
    if not m:
        continue
    name, ty, opname, rest = m.groups()
    out_b = shape_bytes(ty)
    shapes[name] = out_b
    in_b = sum(
        shapes.get(o, 0)
        for o in re.findall(r"%[\w.\-]+",
                            rest.split(", calls=")[0].split(", metadata=")[0])
    )
    ops[opname] += 1
    byts[opname] += out_b + in_b
    rows.append((out_b + in_b, opname, name, line[:140]))

print("\n-- opcode census (entry, output bytes) --", flush=True)
for op, b in byts.most_common(15):
    print(f"{op:20s} n={ops[op]:4d}  out_bytes={b/1e9:8.3f} GB", flush=True)

print("\n-- top 25 single instructions by output bytes --", flush=True)
rows.sort(reverse=True)
for b, opname, name, line in rows[:25]:
    print(f"{b/1e9:7.3f} GB  {line}", flush=True)

# count transposes/copies — layout sanity
n_tr = len(re.findall(r" transpose\(", txt))
n_cp = len(re.findall(r" copy\(", txt))
print(f"\ntransposes in module: {n_tr}, copies: {n_cp}", flush=True)
