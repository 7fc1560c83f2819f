"""Pallas flash kernel parity on CPU via pallas_call(interpret=True).

The TPU kernels never execute in the CPU-pinned suite, so without this
file a tiling or math bug in the forward/backward kernels would pass
every test and surface on hardware as silently wrong gradients.
Interpret mode runs the same kernel jaxprs through the evaluator,
checking block index maps, masks, and the dq/dkv math against the jnp
reference implementation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu.ops.pallas.flash_attention as fa

if not fa._HAVE_PALLAS:  # pragma: no cover
    pytest.skip("pallas unavailable", allow_module_level=True)


def _mk(bh, s, d, seed=0):
    rng = np.random.RandomState(seed)
    return [
        jnp.asarray(rng.randn(bh, s, d) * 0.4, jnp.float32)
        for _ in range(3)
    ]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_q,block_k", [(128, 128), (128, 256)])
def test_fwd_kernel_parity(causal, block_q, block_k):
    q, k, v = _mk(2, 256, 64)
    scale = 0.125
    out, lse = fa._flash_fwd_pallas(
        q, k, v, scale, causal, block_q, block_k, interpret=True
    )
    ref = fa._ref_attention(q, k, v, scale, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    # lse parity against the jnp forward's residual
    _, lse_ref = fa._flash_fwd(q, k, v, scale, causal)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_q,block_k", [(128, 128), (256, 128)])
def test_bwd_kernel_parity(causal, block_q, block_k):
    q, k, v = _mk(2, 256, 64, seed=1)
    scale = 0.125
    out, lse = fa._flash_fwd(q, k, v, scale, causal)
    rng = np.random.RandomState(2)
    dout = jnp.asarray(rng.randn(*out.shape) * 0.3, jnp.float32)
    got = fa._flash_bwd_pallas(
        q, k, v, out, lse, dout, scale, causal, block_q, block_k,
        interpret=True,
    )
    want = fa._flash_vjp_bwd(scale, causal, (q, k, v, out, lse), dout)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4,
            err_msg=f"d{name} mismatch (causal={causal})",
        )


# -- one-tile kernels (PR 33): key rows that fit one VMEM tile ----------

def _mk_bshd(b, s, h, d, dtype, seed):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(b, s, h, d) * 0.5, dtype)


def _to_bh(x):  # [b, s, h, d] -> the long-row kernels' [b*h, s, d]
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _flat(x):  # [b, s, h, d] -> the one-tile kernels' [b, s, h*d]
    return x.reshape(x.shape[0], x.shape[1], -1)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk,d", [(512, 512, 64), (256, 256, 64),
                                     (128, 128, 64), (1024, 1024, 128),
                                     (512, 512, 128)])
def test_one_tile_kernel_parity(sq, sk, d, causal, dtype):
    """The one-tile forward against `_ref_attention` and the fused
    backward against `_flash_vjp_bwd`'s jnp twin, on the same
    (rounded) inputs; two heads of 64 share a 128-lane block, and
    1024 x 1024 runs four query blocks that accumulate dk and dv."""
    h = 2 if d == 64 else 1
    scale = 1.0 / np.sqrt(d)  # a power of two at 64 (folds into q), not at 128
    q, k, v, do = (_mk_bshd(1, s, h, d, dtype, seed)
                   for seed, s in enumerate((sq, sk, sk, sq)))
    out, lse = fa._one_tile_fwd(_flat(q), _flat(k), _flat(v), d=d,
                                scale=scale, causal=causal, interpret=True)
    f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    ref = fa._ref_attention(_to_bh(q), _to_bh(k), _to_bh(v), scale, causal)
    np.testing.assert_allclose(f32(_to_bh(out.reshape(q.shape))), f32(ref),
                               rtol=tol, atol=tol)
    _, lse_ref = fa._flash_fwd(_to_bh(q), _to_bh(k), _to_bh(v), scale, causal)
    np.testing.assert_allclose(f32(lse).reshape(h, sq), f32(lse_ref),
                               rtol=1e-4, atol=2e-3)
    got = fa._one_tile_bwd(_flat(q), _flat(k), _flat(v), out, lse, _flat(do),
                           d=d, scale=scale, causal=causal, interpret=True)
    want = fa._flash_vjp_bwd(
        scale, causal, (_to_bh(q), _to_bh(k), _to_bh(v),
                        _to_bh(out.reshape(q.shape)), lse_ref), _to_bh(do))
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(
            f32(_to_bh(a.reshape(1, -1, h, d))), f32(b), rtol=tol, atol=tol,
            err_msg=f"d{name} mismatch (causal={causal})")


def test_tiling_is_a_pure_function_of_length_width_backend(monkeypatch):
    assert fa.pick_tiling(512, 64, "tpu") == "one_tile"
    assert fa.pick_tiling(128, 128, "tpu") == "one_tile"
    assert fa.pick_tiling(fa.ONE_TILE_MAX_KV, 64, "tpu") == "one_tile"
    assert fa.pick_tiling(2048, 64, "tpu") == "online"
    assert fa.pick_tiling(8192, 128, "tpu") == "online"
    assert fa.pick_tiling(520, 64, "tpu") == "online"  # no 128-lane row
    assert fa.pick_tiling(512, 80, "tpu") == "jnp"  # no tiling at all
    assert fa.pick_tiling(512, 64, "cpu") == "jnp"
    assert fa.pick_tiling(512, 64) == "jnp"  # this suite's backend
    # at 2,048 keys `flash_mha` still hands the long-row kernels the
    # call; at 512 they never see it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seen = []
    monkeypatch.setattr(fa, "mha_flash", lambda *a: seen.append(a[0].shape))
    monkeypatch.setattr(fa, "one_tile_attention",
                        lambda q, *a: seen.append("one_tile") or q)
    x = jax.ShapeDtypeStruct((2, 2048, 4, 64), jnp.bfloat16)
    fa.flash_mha(x, x, x, 0.125, False)
    y = jnp.zeros((2, 512, 4, 64), jnp.bfloat16)
    fa.flash_mha(y, y, y, 0.125, False)
    odd_heads = jnp.zeros((2, 512, 3, 64), jnp.bfloat16)  # no lane pair
    fa.flash_mha(odd_heads, odd_heads, odd_heads, 0.125, False)
    assert seen == [(2, 2048, 4, 64), "one_tile", (2, 512, 3, 64)]


def test_one_tile_custom_vjp_matches_twin_gradients(monkeypatch):
    """`flash_mha` end to end as the attention op calls it (custom_vjp,
    the [b, s, h, d] reshapes), kernels interpreted, against the
    gradients of the jnp twin."""
    from jax.experimental.pallas import tpu as pltpu

    q, k, v = (_mk_bshd(2, 128, 4, 64, jnp.float32, s) for s in range(3))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, 0.125, True) ** 2)

    want = jax.grad(loss(fa.mha_flash), argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        got = jax.grad(loss(fa.flash_mha), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


@pytest.fixture(scope="module")
def encoder4():
    """A 4-layer BERT-shaped encoder at seq 512, compiled on the CPU."""
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType
    from flexflow_tpu.models.transformer import build_bert

    ff = FFModel(FFConfig(batch_size=2, num_devices=1,
                          compute_dtype="bfloat16"))
    build_bert(ff, batch_size=2, seq_length=512, hidden_size=128,
               num_layers=4, num_heads=2, intermediate_size=256,
               vocab_size=64, num_classes=2, from_token_ids=True)
    ff.compile(optimizer=AdamOptimizer(alpha=1e-4),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=jax.devices()[:1])
    return ff


def test_step_lowers_one_kernel_body_a_direction(encoder4, monkeypatch):
    """24 layers must not pay 24 Mosaic lowerings a direction: the
    pallas_calls sit in jitted functions, so the step's module holds
    one forward and one backward kernel, each called once a layer."""
    ff = encoder4
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (ff._weights, ff._opt_state, ff._state,
         {"input": jnp.zeros((2, 512), jnp.int32)},
         jnp.zeros((2,), jnp.int32)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = ff.executor.build_step().trace(
        *shapes, jax.random.key(0)).lower(
            lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2
    assert text.count("call @_one_tile_fwd") == 4
    assert text.count("call @_one_tile_bwd") == 4
    assert "512x512" not in text  # no [b, h, s, s] tensor anywhere


def test_build_step_fns_span_says_which_core_engaged(encoder4, monkeypatch):
    from flexflow_tpu.obs import trace as obs_trace

    args = [r.args for r in obs_trace.spans()
            if r.name == "build_step_fns"][-1]
    # off-TPU the flash branch runs its jnp twin: no kernel
    # (no `remat`: no segment of the step is checkpointed)
    assert args == {"attn_kernel_ops": 0, "attn_dense_ops": 4,
                    "attn_tile": "", "remat_segments": 0}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert encoder4._attention_core_counts() == {
        "attn_kernel_ops": 4, "attn_dense_ops": 0, "attn_tile": "one_tile",
        "remat_segments": 0}
    for op in encoder4.operators.topo_order():
        op._flash_min_seq = 1024  # FFConfig.flash_min_seq, as compile sets it
    assert encoder4._attention_core_counts() == {
        "attn_kernel_ops": 0, "attn_dense_ops": 4, "attn_tile": "",
        "remat_segments": 0}
    for op in encoder4.operators.topo_order():
        op._flash_min_seq = 512
