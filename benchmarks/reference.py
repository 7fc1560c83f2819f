"""Plain transformer pieces the families' references are built from.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks, nothing imported from the program.  Weights use the
layout the families document: attention ``wq/wk/wv [e, h, d]`` and
``wo [h, d, e]`` (no biases), dense ``kernel [in, out]`` + ``bias``,
LayerNorm ``gamma/beta`` with eps 1e-5, GELU in the tanh form
(``jax.nn.gelu``'s default, which is what the program calls).

``precision`` names how every matrix product rounds its operands:

* ``float32``: nothing is rounded; products run at "highest".
* ``bfloat16``: both operands rounded to bfloat16, float32 accumulate.
* ``float8_e4m3fn``: both operands scaled per tensor to the format's
  range (448 / max|x|, the usual fp8 recipe), rounded to e4m3, scaled
  back; float32 accumulate.

The lower two exist for the *control*: the reference put in the
program's place one precision below the one the configuration states
(``CONTROL_BELOW``).  The comparison has to call such a run incorrect.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# the nearest precision below the stated one (the builder's contract)
CONTROL_BELOW = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}

LN_EPS = 1e-5


def control_precisions(stated: str) -> dict:
    """{precision: name in a sweep's row}: the control, and the
    reference at the stated precision itself (for scale) where that is
    not float32."""
    names = {CONTROL_BELOW[stated]: "control"}
    if stated != "float32":
        names[stated] = "reference_at_stated"
    return names


def rounder(precision: str):
    """Operand rounding for one matrix product (see module docstring)."""
    if precision == "float32":
        return lambda x: x
    if precision == "bfloat16":
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "float8_e4m3fn":
        def q(x):
            top = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
            s = 448.0 / jax.lax.stop_gradient(top)
            return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
        return q
    raise ValueError(f"no such reference precision: {precision!r}")


def layer_norm(x, gamma, beta):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * gamma + beta


def attention(x, w, q, causal: bool):
    """Multi-head self-attention over x [b, s, e]; w = {wq, wk, wv, wo}."""
    qh = jnp.einsum("bse,ehd->bshd", q(x), q(w["wq"]))
    kh = jnp.einsum("bse,ehd->bshd", q(x), q(w["wk"]))
    vh = jnp.einsum("bse,ehd->bshd", q(x), q(w["wv"]))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q(qh), q(kh))
    scores = scores / jnp.sqrt(jnp.float32(qh.shape[-1]))
    if causal:
        s = x.shape[1]
        keep = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", q(probs), q(vh))
    return jnp.einsum("bqhd,hde->bqe", q(ctx), q(w["wo"]))


def ffn(x, w1, w2, q):
    h = jax.nn.gelu(jnp.matmul(q(x), q(w1["kernel"])) + w1["bias"])
    return jnp.matmul(q(h), q(w2["kernel"])) + w2["bias"]


def normal_tree(key, shapes, std: float = 0.02):
    """Seeded weights for a nested {name: ... {leaf: shape}} tree: the
    published initialisation of both families (normal, std 0.02), with
    two departures so that no leaf is a constant the comparison cannot
    see: ``gamma`` is 1 + N(0, std) and every bias/beta is N(0, std)
    instead of 1 and 0.  One key per leaf, folded in from its position
    in the sorted tree, so a seed always gives the same weights."""
    leaves, treedef = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        v = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        out.append(v + 1.0 if path[-1].key == "gamma" else v)
    return jax.tree.unflatten(treedef, out)


def unstack_layers(stacked, num_layers: int):
    """Reference layout -> the program's per-op layout: the block's
    weights live stacked under ``"layers"`` as {name: {leaf: [L, ...]}}
    (so the block is one ``lax.scan``: 24 float32 layers unrolled
    compile for minutes, scanned for seconds) and the program names
    them ``<name>_<i>``."""
    out = {k: v for k, v in stacked.items() if k != "layers"}
    for name, leaves in stacked["layers"].items():
        for i in range(num_layers):
            out[f"{name}_{i}"] = {k: v[i] for k, v in leaves.items()}
    return out


def stack_layers(per_op, like, num_layers: int):
    """The inverse of ``unstack_layers``, shaped like ``like``."""
    out = {k: per_op[k] for k in like if k != "layers"}
    out["layers"] = {
        name: {leaf: jnp.stack([per_op[f"{name}_{i}"][leaf]
                                for i in range(num_layers)])
               for leaf in leaves}
        for name, leaves in like["layers"].items()
    }
    return out


def make_weights(shapes, num_layers: int, seed: int, layout: str):
    """The seed's weights on the device, from one jitted call, in the
    ``"program"`` (per-op) or the ``"reference"`` (stacked) layout: the
    same numbers either way.  Made anew where needed rather than kept,
    so that a second copy does not sit beside the program's own."""
    def make(key):
        stacked = normal_tree(key, shapes)
        return (stacked if layout == "reference"
                else unstack_layers(stacked, num_layers))

    return jax.jit(make)(seed_key(seed))


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (the driver's pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
