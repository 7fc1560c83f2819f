"""On-chip probe: KV-cache decoding throughput — O(T^2) re-forward vs
host-loop cached decode vs whole-generation-as-one-program lax.scan
(GPT-2-small shape).  The scan path also turns T host dispatches and
syncs into one."""
import sys, time
import os
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
import numpy as np
import jax

dev = jax.devices()[0]
print("device:", dev, flush=True)

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.decoding import (
    gpt_generate_cached, gpt_generate_scan, make_decoder,
)
from flexflow_tpu.models.transformer import build_gpt, gpt_generate

B, S, NEW = 8, 256, 128
ff = FFModel(FFConfig(batch_size=B, num_devices=1, compute_dtype="bfloat16"))
build_gpt(ff, batch_size=B, seq_length=S, hidden_size=768, num_layers=12,
          num_heads=12, intermediate_size=3072, vocab_size=50257)
ff.compile(optimizer=SGDOptimizer(lr=0.01),
           loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
           devices=[dev])
rng = np.random.RandomState(0)
prompt = rng.randint(1, 50257, size=(B, 64)).astype(np.int32)

print("building decoder twin...", flush=True)
ffd = make_decoder(ff, devices=[dev])

# warm each path once on a short run, then time one full generation
for name, fn in [
    ("full-O(T^2)", lambda n: gpt_generate(ff, prompt, n)),
    ("cached-host", lambda n: gpt_generate_cached(ffd, prompt, n)),
    ("cached-scan", lambda n: gpt_generate_scan(ffd, prompt, n)),
]:
    _ = fn(2)
    t0 = time.perf_counter()
    out = fn(NEW)
    dt = time.perf_counter() - t0
    tok = B * NEW / dt
    print(f"{name:12s}: {dt:7.2f}s for {NEW} new tokens x b{B} "
          f"({tok:8.0f} tok/s)  tail={out[0, -4:].tolist()}", flush=True)
