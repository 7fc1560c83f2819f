"""Generation serving (flexflow_tpu/serving/generation.py): the
KV-cache scan decoder behind the batcher/HTTP surface — the scope the
reference's triton/ backend never reached (triton/README.md:3-6,
forward-only).
"""
import json
import threading
import urllib.request

import numpy as np
import pytest
from _family import trained_gpt

from flexflow_tpu.decoding import gpt_generate_cached, make_decoder
from flexflow_tpu.models.transformer import gpt_generate
from flexflow_tpu.serving import GenerationBatcher, GenerationEngine
from flexflow_tpu.serving.server import serve_http

pytestmark = pytest.mark.slow  # search/train-heavy: full tier only

V, S, B = 32, 16, 4


@pytest.fixture(scope="module")
def trained(devices8):
    return trained_gpt(devices8, B, S, V)


@pytest.fixture(scope="module")
def gen_engine(trained, devices8):
    ff, _ = trained
    return GenerationEngine(ff, batch_size=B, devices=devices8[:1])


def test_engine_matches_reference_decode(trained, gen_engine):
    """Same-length prompts through the serving engine equal the
    host-loop KV decoder (and thus the full-forward path)."""
    ff, ids = trained
    prompts = [ids[i, :5].tolist() for i in range(B)]
    got = gen_engine.generate(prompts, max_new_tokens=6)
    ffd = make_decoder(ff, devices=None)
    want = gpt_generate_cached(ffd, ids[:, :5], max_new_tokens=6)
    for i in range(B):
        np.testing.assert_array_equal(got[i], want[i])


def test_engine_mixed_prompt_lengths(trained, gen_engine):
    """One scan serves different prompt lengths and per-request
    max_new_tokens; each row matches its own full-forward run."""
    ff, ids = trained
    prompts = [ids[0, :3].tolist(), ids[1, :7].tolist(), ids[2, :5].tolist()]
    mnts = [5, 3, 6]
    got = gen_engine.generate(prompts, mnts)
    for p, mnt, row in zip(prompts, mnts, got):
        # full-forward reference: duplicate the prompt across the batch
        full = gpt_generate(ff, np.tile(np.asarray(p, np.int32), (B, 1)),
                            max_new_tokens=mnt)
        assert row == full[0, :len(p) + mnt].tolist()
    # one program per total bucket: both calls below reuse total=16
    runs_before = gen_engine.generations_run
    gen_engine.generate([ids[3, :2].tolist()], 4)
    assert gen_engine.generations_run == runs_before + 1


def test_engine_eos_trimming(trained, devices8):
    ff, ids = trained
    ffd_ref = make_decoder(ff, devices=None)
    want = gpt_generate_cached(ffd_ref, ids[:, :4], max_new_tokens=8)
    eos = int(want[0, 6])  # force a hit inside row 0's continuation
    eng = GenerationEngine(ff, batch_size=B, devices=devices8[:1],
                           eos_id=eos)
    got = eng.generate([ids[i, :4].tolist() for i in range(B)], 8)
    row = got[0]
    assert row[-1] == eos and len(row) == 7  # trimmed at first eos
    np.testing.assert_array_equal(row, want[0, :7])


def test_batcher_coalesces_concurrent_generates(gen_engine):
    batcher = GenerationBatcher(gen_engine, flush_timeout_s=0.05)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, V, rng.randint(2, 7)).tolist()
               for _ in range(10)]
    direct = [gen_engine.generate([p], 5)[0] for p in prompts]
    results = [None] * len(prompts)

    def worker(i):
        results[i] = batcher.generate(prompts[i], 5)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    try:
        assert all(r is not None for r in results)
        for got, want in zip(results, direct):
            assert got == want
        assert batcher.requests_done == len(prompts)
        # coalescing happened: fewer scans than requests
        assert batcher.batches_run < len(prompts)
        stats = batcher.latency_stats()
        assert stats["n"] == len(prompts) and stats["p99_ms"] > 0
    finally:
        batcher.close()


def test_generate_http_endpoint(gen_engine):
    batcher = GenerationBatcher(gen_engine, flush_timeout_s=0.02)
    server = serve_http(generator=batcher, port=0, block=False)
    port = server.server_address[1]
    try:
        def post(payload):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v2/generate",
                data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=120) as r:
                return json.loads(r.read())

        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, V, 4).tolist() for _ in range(3)]
        out = post({"prompts": prompts, "max_new_tokens": 5})
        want = [gen_engine.generate([p], 5)[0] for p in prompts]
        assert out["tokens"] == want
        single = post({"prompt": prompts[0], "max_new_tokens": 5})
        assert single["tokens"] == [want[0]]

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v2/stats", timeout=10) as r:
            stats = json.loads(r.read())
        assert stats["requests_done"] >= 4
        assert stats["latency"]["n"] >= 4
    finally:
        server.shutdown()
        batcher.close()
