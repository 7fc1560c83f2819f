"""`longcat_flash` (LongCat-Flash-Omni's language model) behind the
serving front: how to build it in the program, its seeded weights, its
plain reference.

The program side is `models.longcat_flash.build_longcat_flash` ->
`FFModel.compile(defer_weights=True)` -> `set_weights` ->
`serving.build_front`.  What that graph is, and so what the reference
computes (`RMS(v) = v / sqrt(mean(v^2) + eps) * g`, no biases; `A_i`,
`F_i` one layer's two latent attentions and two dense MLPs, `E` its
routed experts):

    x = tok_embed[id]
    every layer:
        a   = x + A_0(RMS_0(x))
        u   = RMS_1(a)
        m   = E(u)                        the shortcut: read here ...
        b   = a + F_0(u)
        c   = b + A_1(RMS_2(b))
        out = c + F_1(RMS_3(c)) + m       ... joined here
    F(h) = (silu(h W_g) * (h W_u)) W_d
    A(h), a head:
        c_q = RMS(h W_qa);  q = (c_q W_qb) * s_q -> [q_nope | q_rope]
        [c_kv | k_r] = h W_kva;  c_kv <- RMS(c_kv) * s_kv
        [k_nope | v]_head = c_kv W_kvb      the reference EXPANDS
        RoPE (plain, theta) on q_rope per head and on the ONE k_r
        p = causal softmax((q_nope . k_nope + q_rope . k_r) (dn + dr)^-1/2)
        A = concat_heads(p v) W_o
        s_q = sqrt(hidden / q_lora_rank), s_kv = sqrt(hidden / kv_lora_rank)
    E(u), in float32:
        s = softmax(u W_r) over total + zero outputs
        chosen = top k of s + bias;  w_j = scaling * s[chosen_j]   (no
        renormalisation; the bias only chooses, and is zero here)
        E(u) = sum_{chosen j < total AND held} w_j MLP_j(u)
               + (sum_{chosen j >= total} w_j) u       identity experts
    logits = RMS(x) W_head                  over the rows held

The reference is given THE SAME SHARE as the program: the experts held
here (`n_routed_experts` of `deployment.n_routed_experts_published`,
from `deployment.first_held_expert`) and the slice of the vocabulary.
Experts on other chips add nothing, in the program and here alike; the
identity experts are every chip's own and are counted ONCE when shares
are added up (`experts(..)` returns them apart).

At the published widths this share is 5.17 B parameters: 20.7 GB in
float32, and 11 GB of the chip are resident while the check runs.  So
every leaf has a key of its own, `fold_in`ed from the seed by (kind of
op, leaf, index, expert); `make_weights(.., "program")` makes the
program's copy one op (one expert matrix stack) at a time, rounded to
the stated precision AS IT IS MADE (the router stays float32), and the
reference regenerates its float32 weights A SUBLAYER AT A TIME: one
attention (0.36 GB), one dense MLP (0.91 GB), one expert (0.15 GB),
each inside the jitted function that uses it.

A serving family offers `build_server`, `make_weights` and
`position_regrets` (`drivers/serve.py` calls them).  Imports nothing of
`flexflow_tpu/ops`.
"""
from __future__ import annotations

import functools
import json
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check
from benchmarks import reference as ref

STD = 0.02

#: the published config's keys `build_longcat_flash` takes as they are
KEYS = ("hidden_size", "num_layers", "num_attention_heads", "q_lora_rank",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "mla_scale_q_lora", "mla_scale_kv_lora",
        "ffn_hidden_size", "expert_ffn_hidden_size", "n_routed_experts",
        "zero_expert_num", "zero_expert_type", "moe_topk",
        "routed_scaling_factor", "vocab_size", "rms_norm_eps", "rope_theta")


# -- sizes ------------------------------------------------------------------
def published(cfg) -> dict:
    """The keyword arguments of `build_longcat_flash`, under the
    published config's own keys (plus the share: experts held, their
    first)."""
    dep = cfg["deployment"]
    kw = {k: cfg[k] for k in KEYS}
    kw["max_position_embeddings"] = cfg["n_positions"]
    kw["n_routed_experts_total"] = dep["n_routed_experts_published"]
    kw["first_held_expert"] = dep["first_held_expert"]
    return kw


@functools.lru_cache(maxsize=8)
def _dims(frozen: str):
    return Dims(json.loads(frozen))


def dims(cfg) -> "Dims":
    return _dims(json.dumps(published(cfg), sort_keys=True))


class Dims:
    """The sizes the reference and the counting functions read, hashable
    by identity (one per configuration: `dims`)."""

    def __init__(self, kw):
        self.e = kw["hidden_size"]
        self.L = kw["num_layers"]
        self.h = kw["num_attention_heads"]
        self.rq, self.rk = kw["q_lora_rank"], kw["kv_lora_rank"]
        self.dn, self.dr = kw["qk_nope_head_dim"], kw["qk_rope_head_dim"]
        self.dv = kw["v_head_dim"]
        self.s_q = math.sqrt(self.e / self.rq) \
            if kw["mla_scale_q_lora"] else 1.0
        self.s_kv = math.sqrt(self.e / self.rk) \
            if kw["mla_scale_kv_lora"] else 1.0
        self.f_dense = kw["ffn_hidden_size"]
        self.f = kw["expert_ffn_hidden_size"]
        self.held = kw["n_routed_experts"]
        self.total = kw["n_routed_experts_total"]
        self.first_held = kw["first_held_expert"]
        self.zero = kw["zero_expert_num"]
        self.width = self.total + self.zero      # the router's outputs
        self.k = kw["moe_topk"]
        self.scaling = float(kw["routed_scaling_factor"])
        self.v = kw["vocab_size"]
        self.p = kw["max_position_embeddings"]
        self.eps = float(kw["rms_norm_eps"])
        self.theta = float(kw["rope_theta"])


# -- the program --------------------------------------------------------------
def build_server(cfg, devices):
    """A model that is only ever served: no weight drawn, none held in
    float32; `set_weights` brings them in the stated precision.  Only
    sizes leave their defaults: slots and the pool."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.longcat_flash import build_longcat_flash

    dep = cfg["deployment"]
    ff = FFModel(FFConfig(batch_size=1, num_devices=1,
                          compute_dtype=cfg["precision"],
                          serving_slots=dep["serving_slots"],
                          kv_page_size=dep["kv_page_size"],
                          kv_pool_blocks=dep["kv_pool_blocks"]))
    build_longcat_flash(ff, batch_size=1, seq_length=cfg["n_positions"],
                        **published(cfg))
    ff.compile(devices=list(devices), defer_weights=True)
    return ff


# -- weights, from the seed -----------------------------------------------------
def leaf_shapes(d: Dims, kind: str) -> dict:
    """{leaf: shape} of one op of a kind, in the program's layout; the
    routed experts' three matrices are per expert (`expert`)."""
    e = d.e
    return {
        "tok_embed": {"weight": (d.v, e)},
        "norm": {"gamma": (e,)},
        "attn": {"wq_a": (e, d.rq), "q_norm": (d.rq,),
                 "wq_b": (d.rq, d.h, d.dn + d.dr),
                 "wkv_a": (e, d.rk + d.dr), "kv_norm": (d.rk,),
                 "wkv_b": (d.rk, d.h, d.dn + d.dv), "wo": (d.h, d.dv, e)},
        "mlp": {"w_gate": (e, d.f_dense), "w_up": (e, d.f_dense),
                "w_down": (d.f_dense, e)},
        "moe": {"router": (e, d.width), "router_bias": (d.width,)},
        "expert": {"w_gate": (e, d.f), "w_up": (e, d.f), "w_down": (d.f, e)},
        "lm_head": {"kernel": (e, d.v)},
    }[kind]


GAINS = ("gamma", "q_norm", "kv_norm")   # 1 + N(0, STD)
ZEROS = ("router_bias",)                 # the choosing bias: not trained
FLOAT32_LEAVES = ("router", "router_bias")


def leaf(key, kind: str, name: str, shape, index=0, expert=0):
    """One leaf in float32: normal, std 0.02 (a gain: 1 + that; the
    choosing bias: zero), from a key of its own: the seed's, folded with
    the kind of op and the leaf's name (a fixed hash), the op's index
    among its kind and the expert's index among ALL the router's real
    experts (so every share makes the same expert)."""
    if name in ZEROS:
        return jnp.zeros(shape, jnp.float32)
    k = jax.random.fold_in(key, zlib.crc32(f"{kind}/{name}".encode())
                           & 0x7FFFFFFF)
    k = jax.random.fold_in(jax.random.fold_in(k, index), expert)
    v = STD * jax.random.normal(k, shape, jnp.float32)
    return v + 1.0 if name in GAINS else v


def make_leaves(key, d: Dims, kind: str, index, expert=0):
    return {name: leaf(key, kind, name, shape, index, expert)
            for name, shape in leaf_shapes(d, kind).items()}


@functools.partial(jax.jit, static_argnames=("d", "kind", "dtype"))
def make_op(key, index, *, d: Dims, kind: str, dtype):
    """One op's weights in the program's layout and precision, each
    leaf rounded as it is made (a `moe` op: its router and bias; its
    experts come a matrix stack at a time, `make_expert_stack`)."""
    return {name: v if name in FLOAT32_LEAVES else v.astype(dtype)
            for name, v in make_leaves(key, d, kind, index).items()}


@functools.partial(jax.jit, static_argnames=("d", "name", "dtype"))
def make_expert_stack(key, layer, *, d: Dims, name: str, dtype):
    """[held, ...]: one of the three matrices of every held expert."""
    shape = leaf_shapes(d, "expert")[name]
    return jnp.stack([
        leaf(key, "expert", name, shape, layer, d.first_held + x
             ).astype(dtype) for x in range(d.held)])


def program_ops(d: Dims):
    """[(op name, kind, index among its kind)] of every op of the
    program that has weights, in graph order.  A layer has four norms
    (index 4 l + 0..3), two attentions and two MLPs (2 l + 0..1)."""
    ops = [("tok_embed", "tok_embed", 0)]
    for i in range(d.L):
        ops += [(f"attn_{i}_0_norm", "norm", 4 * i),
                (f"attn_{i}_0", "attn", 2 * i),
                (f"mlp_{i}_0_norm", "norm", 4 * i + 1),
                (f"moe_{i}", "moe", i),
                (f"mlp_{i}_0", "mlp", 2 * i),
                (f"attn_{i}_1_norm", "norm", 4 * i + 2),
                (f"attn_{i}_1", "attn", 2 * i + 1),
                (f"mlp_{i}_1_norm", "norm", 4 * i + 3),
                (f"mlp_{i}_1", "mlp", 2 * i + 1)]
    return ops + [("final_norm", "norm", 4 * d.L), ("lm_head", "lm_head", 0)]


def make_program_op(key, d: Dims, kind: str, index, dtype) -> dict:
    """One op's weights as `set_weights` takes them."""
    w = make_op(key, index, d=d, kind=kind, dtype=dtype)
    if kind == "moe":
        for name in leaf_shapes(d, "expert"):
            w[name] = make_expert_stack(key, index, d=d, name=name,
                                        dtype=dtype)
    return w


class ReferenceWeights:
    """What the reference is handed: the seed.  Every float32 leaf is
    made where it is used (`leaf`), a sublayer at a time."""

    def __init__(self, cfg, seed: int):
        self.d, self.key = dims(cfg), ref.seed_key(seed)


def make_weights(cfg, seed: int, layout: str):
    if layout == "reference":
        return ReferenceWeights(cfg, seed)
    d, key = dims(cfg), ref.seed_key(seed)
    dtype = jnp.dtype(cfg["precision"])
    return {name: make_program_op(key, d, kind, index, dtype)
            for name, kind, index in program_ops(d)}


# -- the plain reference --------------------------------------------------------
def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * gain


def rotate(x, d: Dims):
    """Plain RoPE on x [s, ..., dr], positions 0..s-1: adjacent pairs
    `(2i, 2i + 1)` turned by `position * theta^(-2i / dr)`."""
    freq = d.theta ** (-np.arange(0, d.dr, 2, dtype=np.float64) / d.dr)
    angle = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
             * jnp.asarray(freq, jnp.float32))
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


HEADS_AT_ONCE = 8  # [8, s, s] scores at a time, not [h, s, s]


def attention(x, w, d: Dims, q):
    """x [s, e] (already normed) -> [s, e]: expanded keys and values,
    `[s, s]` causal scores, a few heads at a time."""
    s = x.shape[0]
    cq = rms(jnp.matmul(q(x), q(w["wq_a"])), w["q_norm"], d.eps)
    qh = jnp.einsum("sr,rhd->shd", q(cq), q(w["wq_b"])) * d.s_q
    q_nope, q_rope = qh[..., :d.dn], rotate(qh[..., d.dn:], d)
    kv = jnp.matmul(q(x), q(w["wkv_a"]))
    c = rms(kv[:, :d.rk], w["kv_norm"], d.eps) * d.s_kv
    k_rope = rotate(kv[:, d.rk:], d)
    kvh = jnp.einsum("sc,chd->shd", q(c), q(w["wkv_b"]))
    k_nope, v = kvh[..., :d.dn], kvh[..., d.dn:]
    keep = jnp.tril(jnp.ones((s, s), bool))
    scale = (d.dn + d.dr) ** -0.5

    def some_heads(args):
        qn, qr, kn, vv = args  # [g, s, .]
        scores = (jnp.einsum("gqd,gkd->gqk", q(qn), q(kn))
                  + jnp.einsum("gqd,kd->gqk", q(qr), q(k_rope)))
        probs = jax.nn.softmax(jnp.where(keep, scores * scale, -jnp.inf),
                               axis=-1)
        return jnp.einsum("gqk,gkd->gqd", q(probs), q(vv))

    def groups(t):  # [s, h, .] -> [h / g, g, s, .]
        g = math.gcd(d.h, HEADS_AT_ONCE)
        return jnp.swapaxes(t, 0, 1).reshape(d.h // g, g, s, -1)

    ctx = jax.lax.map(some_heads, tuple(
        groups(t) for t in (q_nope, q_rope, k_nope, v)))
    ctx = jnp.swapaxes(ctx.reshape(d.h, s, d.dv), 0, 1)
    return jnp.einsum("shd,hde->se", q(ctx), q(w["wo"]))


def gated(x, wg, wu, wd, q):
    return jnp.matmul(q(jax.nn.silu(jnp.matmul(q(x), q(wg)))
                        * jnp.matmul(q(x), q(wu))), q(wd))


def routing(h, router, bias, d: Dims):
    """h [s, e] -> routing weights [s, total + zero]: `scaling` times
    the softmax score where an output was chosen, zero elsewhere;
    float32, whatever the precision under test."""
    scores = jax.nn.softmax(jnp.matmul(h, router), axis=-1)
    _, chosen = jax.lax.top_k(scores + bias, d.k)
    w = jnp.take_along_axis(scores, chosen, axis=-1) * d.scaling
    return jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(w)


def experts(h, key, layer, d: Dims, q, held=None):
    """One layer's `E(h)` in two parts, h [s, e] -> ([s, e], [s, e]):
    the real experts in `held` ((first, count); default the
    configuration's share), one expert's weights at a time over EVERY
    row with its routing weights, and the identity experts' term, which
    every chip computes alike."""
    first, count = held if held is not None else (d.first_held, d.held)
    w = make_leaves(key, d, "moe", layer)
    weights = routing(h, w["router"], w["router_bias"], d)

    def one(acc, x):
        ew = make_leaves(key, d, "expert", layer, x)
        y = gated(h, ew["w_gate"], ew["w_up"], ew["w_down"], q)
        return acc + jnp.take(weights, x, axis=1)[:, None] * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             first + jnp.arange(count))
    identity = jnp.sum(weights[:, d.total:], axis=-1, keepdims=True) * h
    return routed, identity


def norm_gain(key, d: Dims, index):
    return leaf(key, "norm", "gamma", (d.e,), index)


# one jitted function a sublayer: its float32 weights exist inside it
@functools.partial(jax.jit, static_argnames=("d", "precision"))
def attention_fn(key, index, norm_index, x, *, d: Dims, precision: str):
    """x + A(RMS(x)) for attention `index` (2 l + 0..1)."""
    return x + attention(rms(x, norm_gain(key, d, norm_index), d.eps),
                         make_leaves(key, d, "attn", index), d,
                         ref.rounder(precision))


@functools.partial(jax.jit, static_argnames=("d", "precision"))
def mlp_fn(key, index, h, *, d: Dims, precision: str):
    w = make_leaves(key, d, "mlp", index)
    return gated(h, w["w_gate"], w["w_up"], w["w_down"],
                 ref.rounder(precision))


@functools.partial(jax.jit, static_argnames=("d", "precision"))
def experts_fn(key, layer, u, *, d: Dims, precision: str):
    return sum(experts(u, key, layer, d, ref.rounder(precision)))


@functools.partial(jax.jit, static_argnames=("d",))
def norm_fn(key, index, x, *, d: Dims):
    return rms(x, norm_gain(key, d, index), d.eps)


def layer_fn(key, layer, x, *, d: Dims, precision: str,
             join_early: bool = False):
    """One double layer, as the equations at the top write it.
    `join_early` is the tests' control: the shortcut joined one
    sublayer early (before `F_1` reads the stream), which is ANOTHER
    model."""
    kw = dict(d=d, precision=precision)
    a = attention_fn(key, 2 * layer, 4 * layer, x, **kw)
    u = norm_fn(key, 4 * layer + 1, a, d=d)
    m = experts_fn(key, layer, u, **kw)
    b = a + mlp_fn(key, 2 * layer, u, **kw)
    c = attention_fn(key, 2 * layer + 1, 4 * layer + 2, b, **kw)
    if join_early:
        c = c + m
    out = c + mlp_fn(key, 2 * layer + 1,
                     norm_fn(key, 4 * layer + 3, c, d=d), **kw)
    return out if join_early else out + m


@functools.partial(jax.jit, static_argnames=("d",))
def embed_fn(key, ids, *, d: Dims):
    return jnp.take(leaf(key, "tok_embed", "weight", (d.v, d.e)), ids, axis=0)


@functools.partial(jax.jit, static_argnames=("d", "precision"))
def head_fn(key, x, *, d: Dims, precision: str):
    q = ref.rounder(precision)
    x = rms(x, norm_gain(key, d, 4 * d.L), d.eps)
    return jnp.matmul(q(x), q(leaf(key, "lm_head", "kernel", (d.e, d.v))))


def logits_fn(w: ReferenceWeights, ids, precision: str,
              join_early: bool = False):
    """ids [s] -> logits [s, vocab]: one full causal forward, a
    sublayer at a time."""
    d = w.d
    with jax.default_matmul_precision("highest"):
        x = embed_fn(w.key, ids, d=d)
        for i in range(d.L):
            x = layer_fn(w.key, i, x, d=d, precision=precision,
                         join_early=join_early)
        return head_fn(w.key, x, d=d, precision=precision)


def position_regrets(w: ReferenceWeights, ids, chooser=None):
    """ids [s] (a served sequence, right-padded) -> regret [s - 1] of the
    token at position p + 1 under the float32 reference's logits at p.
    With ``chooser`` (a lower precision) the tokens judged are the ones
    the reference at that precision would pick, teacher-forced on the
    same context: the control."""
    want = logits_fn(w, ids, "float32")[:-1]
    chosen = (ids[1:] if chooser is None else
              jnp.argmax(logits_fn(w, ids, chooser)[:-1], axis=-1))
    return check.position_regret(want, chosen)


# -- what a pass has to move and to compute ---------------------------------------
def parameter_counts(d: Dims) -> dict:
    """Parameters by where a pass finds them (this chip's share)."""
    n = lambda kind: sum(int(np.prod(s))  # noqa: E731
                         for s in leaf_shapes(d, kind).values())
    return {
        "one_attention": n("attn"), "one_mlp": n("mlp"),
        "attention": 2 * d.L * n("attn"), "norms": (4 * d.L + 1) * d.e,
        "dense_mlp": 2 * d.L * n("mlp"),
        "router": d.L * (d.e * d.width + d.width),
        "one_expert": n("expert"), "held_experts": d.L * d.held,
        "table": d.v * d.e, "head": d.e * d.v,
    }


def total_parameters(d: Dims) -> int:
    c = parameter_counts(d)
    return (c["attention"] + c["norms"] + c["dense_mlp"] + c["router"]
            + c["held_experts"] * c["one_expert"] + c["table"] + c["head"])


def latent_block_bytes(cfg) -> int:
    """Bytes of one physical block of the latent pools, all of them:
    TWO planes a layer (one an attention)."""
    d = dims(cfg)
    return (2 * d.L * cfg["deployment"]["kv_page_size"] * (d.rk + d.dr)
            * jnp.dtype(cfg["precision"]).itemsize)


def decode_pass_bytes(cfg, rows: int, experts_hit: float,
                      kv_blocks_live: float, kv_block_bytes: int) -> float:
    """Bytes one seq-1 pass over `rows` slots cannot avoid reading:
    every weight outside the routed experts once (the router in
    float32, the rest in the stated precision; of the table only the
    rows' own lines), the held experts that received a row
    (`experts_hit`, summed over layers), and the live pages of the
    latent pools (`kv_blocks_live` blocks of `kv_block_bytes`, all
    planes).  Activations, logits and cache writes are left out: the
    floor stays a floor."""
    d, c = dims(cfg), parameter_counts(dims(cfg))
    b = jnp.dtype(cfg["precision"]).itemsize
    return (b * (c["attention"] + c["norms"] + c["dense_mlp"] + c["head"]
                 + rows * d.e + experts_hit * c["one_expert"])
            + 4 * c["router"] + kv_blocks_live * kv_block_bytes)


def _prefill_sublayers(d: Dims) -> dict:
    """Sublayers whose products a prefill pass cannot avoid: it returns
    no logits, so what only the last layer's output needs is not asked
    of it: the last layer's experts, its second MLP, and its second
    attention past the latent it writes (`wkv_a` alone); no head."""
    return {"attention": 2 * d.L - 1, "latent_only": 1, "mlp": 2 * d.L - 1,
            "expert_layers": d.L - 1, "routers": d.L}


def prefill_pass_flops(cfg, tokens: float, context: float) -> float:
    """Operations of one chunked-prefill pass that advances `tokens`
    real prompt tokens, each attending `context` cached positions on
    average, AS ROUTING ASKS FOR THEM: the (token, expert) pairs an even
    router lands on held experts (`tokens x k x held / width` a layer),
    never every held expert over every row, and no identity pick (it
    multiplies nothing); attention counted at the expanded form's cost a
    (query, key) pair with nothing for expanding.  A floor: rows the
    pass computes for riders and pads are not in `tokens`."""
    d, c, n = dims(cfg), parameter_counts(dims(cfg)), _prefill_sublayers(
        dims(cfg))
    pairs = tokens * d.k * d.held / d.width
    products = (tokens * (n["attention"] * c["one_attention"]
                          + n["latent_only"] * d.e * (d.rk + d.dr)
                          + n["mlp"] * c["one_mlp"]
                          + n["routers"] * d.e * d.width)
                + n["expert_layers"] * pairs * c["one_expert"])
    attend = (n["attention"] * tokens * d.h * context
              * (d.dn + d.dr + d.dv))
    return 2.0 * (products + attend)


def prefill_pass_bytes(cfg, tokens: float) -> float:
    """Bytes such a pass cannot avoid reading: every weight it needs
    once (the held experts that an even router's `tokens x k` picks
    reach, in expectation; the routers in float32; of the table the
    tokens' own lines).  The pools' pages, activations and writes are
    left out."""
    d, c, n = dims(cfg), parameter_counts(dims(cfg)), _prefill_sublayers(
        dims(cfg))
    b = jnp.dtype(cfg["precision"]).itemsize
    hit = d.held * (1.0 - (1.0 - d.k / d.width) ** tokens)
    return (b * (n["attention"] * c["one_attention"]
                 + n["latent_only"] * d.e * (d.rk + d.dr)
                 + n["mlp"] * c["one_mlp"] + 4 * d.L * d.e + tokens * d.e
                 + n["expert_layers"] * hit * c["one_expert"])
            + 4 * n["routers"] * (d.e * d.width + d.width))
