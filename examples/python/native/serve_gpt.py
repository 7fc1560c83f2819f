"""Generation serving end to end: train a tiny GPT, build its KV-cache
decode twin, serve /v2/generate over HTTP, and fire concurrent
requests (docs/SERVING.md; the scope the reference's triton/ prototype
never reached).

--serving-mode continuous (the default) runs the iteration-level
scheduler on the paged KV-cache pool (serving/scheduler.py);
--serving-mode static falls back to the whole-scan GenerationBatcher.
Continuous mode always serves through a ServingFront
(serving/front.py) — even --serving-replicas 1 gains the decode-step
watchdog (--serving-step-timeout) and budget-capped restart
supervision; N >= 2 adds queue handoff on replica death (requeues
onto survivors) and /v2/health per-replica liveness aggregation.

Run: python serve_gpt.py [-e STEPS] [-b BATCH]
                         [--serving-mode continuous|static]
                         [--kv-page-size N] [--serving-slots N]
                         [--serving-replicas N]
                         [--serving-step-timeout S]
                         [--serving-roles prefill=1,decode=1]
"""
import argparse
import json
import signal
import threading
import urllib.request

import numpy as np

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.models.transformer import build_gpt
from flexflow_tpu.serving import GenerationBatcher, GenerationEngine
from flexflow_tpu.serving.server import serve_http

V, S = 64, 24


def main():
    p = argparse.ArgumentParser()
    p.add_argument("-e", "--steps", type=int, default=30)
    p.add_argument("-b", "--batch-size", type=int, default=8)
    args, _ = p.parse_known_args()
    serving_cfg = FFConfig.from_args()  # --serving-mode/--kv-page-size/
    b = args.batch_size                 # --serving-slots/--kv-pool-blocks

    # --strategy-store/--compilation-cache flow into the replica's
    # compiles (docs/STORE.md "Replica cold start"): a second process
    # serving the same model restores instead of re-searching
    ff = FFModel(FFConfig(batch_size=b, num_devices=1,
                          strategy_store=serving_cfg.strategy_store,
                          compilation_cache=serving_cfg.compilation_cache))
    build_gpt(ff, batch_size=b, seq_length=S, hidden_size=32,
              num_layers=2, num_heads=4, intermediate_size=64,
              vocab_size=V)
    ff.compile(optimizer=SGDOptimizer(lr=0.5),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    rng = np.random.RandomState(0)
    seq = (rng.randint(0, V, (b, 1))
           + rng.randint(1, 5, (b, 1)) * np.arange(S + 1)) % V
    ids, labels = seq[:, :-1].astype(np.int32), seq[:, 1:].astype(np.int32)
    pos = np.broadcast_to(np.arange(S, dtype=np.int32), (b, S)).copy()
    for i in range(args.steps):
        m = ff.train_step({"input": ids, "positions": pos}, labels)
    print(f"trained {args.steps} steps, loss={float(m['loss']):.3f}")

    grace_displaced = {}
    if serving_cfg.serving_mode == "continuous":
        page = serving_cfg.kv_page_size
        if S % page:  # the demo model's position table is small
            page = 4
        # the front supervises even a SINGLE replica (watchdog +
        # budget-capped restarts — the config.py contract for
        # --serving-step-timeout at replicas=1), so continuous mode
        # always serves through it; --serving-roles upgrades it to a
        # disaggregated prefill/decode fleet (docs/SERVING.md
        # "Disaggregated fleet")
        from flexflow_tpu.serving import build_front

        ff.config.serving_replicas = serving_cfg.serving_replicas
        ff.config.serving_slots = serving_cfg.serving_slots
        ff.config.kv_page_size = page
        ff.config.kv_pool_blocks = serving_cfg.kv_pool_blocks
        # prefix cache + chunked prefill ride into every replica's
        # engine (--prefill-chunk / --no-prefix-cache)
        ff.config.prefill_chunk = serving_cfg.prefill_chunk
        ff.config.prefix_cache = serving_cfg.prefix_cache
        ff.config.serving_step_timeout = \
            serving_cfg.serving_step_timeout
        ff.config.serving_max_restarts = \
            serving_cfg.serving_max_restarts
        ff.config.request_retry_limit = \
            serving_cfg.request_retry_limit
        ff.config.serving_roles = serving_cfg.serving_roles
        ff.config.kv_transfer = serving_cfg.kv_transfer
        ff.config.migration_cost_cap = serving_cfg.migration_cost_cap
        batcher = build_front(ff, serving_cfg)
        # SIGTERM/SIGINT drain instead of kill for ANY front — the
        # grace machinery lives in ServingFront, not the autoscaler
        grace_displaced = batcher.install_grace_handlers(
            deadline_s=serving_cfg.serving_drain_timeout)
        if serving_cfg.serving_max_replicas > 0:
            # --serving-max-replicas N turns the fleet size into a
            # controlled variable (docs/SERVING.md "Autoscaling &
            # drain lifecycle"): scale-up on load, graceful drain
            # when calm
            from flexflow_tpu.serving import ServingAutoscaler

            ServingAutoscaler.from_config(
                batcher, serving_cfg).start()
    else:
        engine = GenerationEngine(ff, batch_size=b)
        batcher = GenerationBatcher(engine, flush_timeout_s=0.02)
    server = serve_http(generator=batcher, port=0, block=False)
    port = server.server_address[1]
    print(f"serving /v2/generate on :{port} "
          f"({serving_cfg.serving_mode} mode)")

    def client(i, out):
        payload = {"prompt": ids[i % b, :4].tolist(), "max_new_tokens": 8}
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v2/generate",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            out[i] = json.loads(r.read())["tokens"][0]

    results = {}
    threads = [threading.Thread(target=client, args=(i, results))
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert len(results) == 6 and all(len(v) == 12 for v in results.values())
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/v2/stats",
                                timeout=10) as r:
        stats = json.loads(r.read())
    print(f"6 concurrent generations OK; batches_run="
          f"{stats['batches_run']} p95={stats['latency']['p95_ms']}ms")
    server.shutdown()
    batcher.close()
    for signum, handler in grace_displaced.items():
        if handler is not None:  # Ctrl-C kills again post-close
            signal.signal(signum, handler)


if __name__ == "__main__":
    main()
