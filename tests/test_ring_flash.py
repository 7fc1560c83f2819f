"""The ring whose steps run the Pallas flash kernels a block
(interpret mode on the CPU) against the ring of dense blocks, forward
and through the manual ring backward; the ring through the PCG and the
flash unit tests: tests/test_ring_attention.py.  Each side is one
compiled program: run eagerly, `shard_map` hands every primitive of the
ring's body to XLA as a program of its own.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from flexflow_tpu.ops.pallas.flash_attention import _ref_attention
from flexflow_tpu.parallel.ring_attention import ring_attention

SP = 4


def ring_case(devices8, seed, b=2, s=128 * SP, h=2, d=64):
    """(q, k, v [b, s, h, d], the mesh of SP shards, the scale):
    >=128-wide shards, lane-friendly d."""
    rng = np.random.RandomState(seed)
    qkv = [jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
           for _ in range(3)]
    return qkv, Mesh(np.array(devices8[:SP]), ("seq",)), 1.0 / np.sqrt(d)


def test_ring_flash_blocks_match_dense(devices8):
    """Non-causal ring steps can run the Pallas flash kernel per block
    (interpret mode on CPU): the (out, lse) log-sum-exp merge must
    reproduce the dense block path exactly."""
    (qh, kh, vh), mesh, scale = ring_case(devices8, 5)
    b, s, h, d = qh.shape

    def ring(impl):
        return jax.jit(lambda q, k, v: ring_attention(
            q, k, v, mesh, "seq", scale=scale, block_impl=impl))

    dense = ring("dense")(qh, kh, vh)
    flash = ring("flash")(qh, kh, vh)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)
    # and both agree with plain single-device attention
    ref = jax.jit(lambda q, k, v: _ref_attention(
        q.transpose(0, 2, 1, 3).reshape(b * h, s, d),
        k.transpose(0, 2, 1, 3).reshape(b * h, s, d),
        v.transpose(0, 2, 1, 3).reshape(b * h, s, d), scale, False,
    ).reshape(b, h, s, d).transpose(0, 2, 1, 3))(qh, kh, vh)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    # forced flash refuses shapes the kernel cannot tile rather than
    # silently running dense
    rng = np.random.RandomState(5)
    tiny = jnp.asarray(rng.randn(2, 4 * SP, 2, 8).astype(np.float32))
    with pytest.raises(ValueError, match="unsupported"):
        ring("flash")(tiny, tiny, tiny)
    # the support check must see SHARD shapes: global 128*sp-divisible
    # but shard 96-long has no >=128 tile -> refuse, not crash
    odd = jnp.asarray(rng.randn(2, 96 * SP, 2, 64).astype(np.float32))
    with pytest.raises(ValueError, match="unsupported"):
        ring("flash")(odd, odd, odd)


def value_and_gradients(devices8, seed, impl, causal=False):
    """(the ring's output, the gradients by q, k, v of the sum of its
    squares), one program."""
    qkv, mesh, scale = ring_case(devices8, seed)

    def f(q, k, v):
        o = ring_attention(q, k, v, mesh, "seq", scale=scale, causal=causal,
                           block_impl=impl)
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    (_, o), grads = jax.jit(jax.value_and_grad(
        f, argnums=(0, 1, 2), has_aux=True))(*qkv)
    return o, grads


def test_ring_flash_gradients_match_dense(devices8):
    """The flash ring is fully differentiable: the manual ring backward
    (rotating dk/dv partial sums, Pallas bwd kernels per block against
    the global lse) must reproduce the dense ring's autodiff gradients."""
    _, g_dense = value_and_gradients(devices8, 7, "dense")
    _, g_flash = value_and_gradients(devices8, 7, "flash")
    for gd, gf in zip(g_dense, g_flash):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_causal_matches_dense(devices8, causal):
    """Causal flash rings: the diagonal step uses the kernel's static
    causal mask, off-diagonal steps gate a traced visibility bit — both
    forward and the manual backward must match the dense causal ring."""
    o_dense, g_dense = value_and_gradients(devices8, 11, "dense", causal)
    o_flash, g_flash = value_and_gradients(devices8, 11, "flash", causal)
    np.testing.assert_allclose(np.asarray(o_flash), np.asarray(o_dense),
                               rtol=2e-4, atol=2e-4)
    for gd, gf in zip(g_dense, g_flash):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=3e-4, atol=3e-4)
