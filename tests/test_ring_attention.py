"""Sequence parallelism: ring attention + flash attention tests.

New TPU-native capability (SURVEY §5: the reference has no context
parallelism) — validated hermetically on the 8-device CPU mesh.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.models.transformer import bert_sp_strategy, build_bert
from flexflow_tpu.ops.pallas.flash_attention import _ref_attention, flash_attention


# ---------------------------------------------------------------------------
# flash attention (jnp fallback path on CPU; same custom_vjp as TPU)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(causal):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(4, 32, 16).astype(np.float32))
    k = jnp.asarray(rng.randn(4, 48, 16).astype(np.float32))
    v = jnp.asarray(rng.randn(4, 48, 16).astype(np.float32))
    scale = 0.25
    out = flash_attention(q, k, v, scale, causal)
    ref = _ref_attention(q, k, v, scale, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads_match(causal):
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(2, 16, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 16, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 16, 8).astype(np.float32))

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, 0.3, causal) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_ref_attention(q, k, v, 0.3, causal) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# ring attention end-to-end through the PCG
# ---------------------------------------------------------------------------

def _tiny_bert(causal=False, layers=1):
    ff = FFModel(FFConfig())
    build_bert(ff, batch_size=4, seq_length=32, hidden_size=32,
               num_layers=layers, num_heads=4, intermediate_size=64)
    return ff


def test_ring_attention_forward_matches_single(devices8):
    xs = np.random.RandomState(0).randn(4, 32, 32).astype(np.float32)
    ff1 = _tiny_bert()
    ff1.compile(devices=devices8[:1], seed=7)
    ref = np.asarray(ff1.forward({"input": xs}))

    ff_sp = _tiny_bert()
    ff_sp.compile(strategy=bert_sp_strategy(8, sp=4), devices=devices8, seed=7)
    out = np.asarray(ff_sp.forward({"input": xs}))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_ring_attention_causal_matches_vanilla(devices8):
    """Causal masking across ring steps (the subtle block-offset case)."""
    from flexflow_tpu.fftype import ActiMode

    def build(ff):
        x = ff.create_tensor([2, 32, 16], name="x")
        t = ff.multihead_attention(x, x, x, 16, 4, causal=True, name="attn")
        return ff.dense(t, 8, name="out")

    xs = np.random.RandomState(2).randn(2, 32, 16).astype(np.float32)
    ff1 = FFModel(FFConfig())
    build(ff1)
    ff1.compile(devices=devices8[:1], seed=3)
    ref = np.asarray(ff1.forward({"x": xs}))

    ff_sp = FFModel(FFConfig())
    build(ff_sp)
    ff_sp.compile(strategy=bert_sp_strategy(8, sp=8), devices=devices8, seed=3)
    out = np.asarray(ff_sp.forward({"x": xs}))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_ring_attention_training_step(devices8):
    """Gradients flow through shard_map + ppermute; loss decreases."""
    ff = _tiny_bert()
    ff.compile(
        optimizer=SGDOptimizer(lr=0.05),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        strategy=bert_sp_strategy(8, sp=4),
        devices=devices8,
        seed=0,
    )
    xs = np.random.RandomState(1).randn(4, 32, 32).astype(np.float32)
    ys = np.random.RandomState(2).randint(0, 2, 4).astype(np.int32)
    losses = [float(ff.train_step({"input": xs}, ys)["loss"]) for _ in range(8)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_flash_path_through_model_layer(devices8):
    """flash_min_seq=0 forces the Pallas/flash branch in
    MultiHeadAttention._attend through the full model path (coverage
    guard: the default threshold routes short seqs to plain XLA)."""
    import numpy as np

    def build(flash_min):
        ff = FFModel(FFConfig(batch_size=4, num_devices=1,
                              flash_min_seq=flash_min))
        build_bert(ff, batch_size=4, seq_length=32, hidden_size=32,
                   num_layers=1, num_heads=4, intermediate_size=64)
        ff.compile(devices=devices8[:1], seed=11)
        return ff

    xs = np.random.RandomState(0).randn(4, 32, 32).astype(np.float32)
    out_flash = np.asarray(build(0).forward({"input": xs}))
    out_plain = np.asarray(build(10_000).forward({"input": xs}))
    np.testing.assert_allclose(out_flash, out_plain, rtol=2e-4, atol=2e-4)
