"""`kimi_k2` (Kimi-K2 / K2.5's language model) behind the serving front:
how to build it in the program, its seeded weights, its plain reference.

The program side is `models.kimi_k2.build_kimi_k2` -> `FFModel.compile(
defer_weights=True)` -> `set_weights` -> `serving.build_front`.  What
that graph is, and so what the reference computes, for one token `x`
(`RMS(v) = v / sqrt(mean(v^2) + eps) * g`, no biases anywhere):

    x = tok_embed[id]
    every layer:
        h = RMS(x)
        c_q = RMS(h W_qa);  q = c_q W_qb -> heads of [q_nope | q_rope]
        [c_kv | k_r] = h W_kva;  c_kv <- RMS(c_kv)
        RoPE (YaRN frequencies) on q_rope per head and on the ONE k_r
        [k_nope | v]_head = c_kv W_kvb          the reference EXPANDS
        score = (q_nope . k_nope + q_rope . k_r) * s, causal softmax
        x = x + concat_heads(sum p v) W_o
      layer < first_k_dense_replace:
        x = x + W_d (silu(W_g RMS(x)) * (W_u RMS(x)))
      else, in float32:
        s = sigmoid(RMS(x) W_r); the top k of s + b are chosen
        w_e = s_e / (sum of the k chosen s + 1e-20) * scaling
        x = x + sum_{e chosen AND held} w_e E_e(RMS(x)) + E_shared(RMS(x))
    logits = RMS(x) W_head                     over the rows held

The reference is given THE SAME SHARE as the program: the experts held
here (`n_routed_experts` of `deployment.n_routed_experts_published`,
from `deployment.first_held_expert`) and the slice of the vocabulary.
Experts that live on other chips add nothing, in the program and here
alike; the router keeps its published width, and the normaliser runs
over all k chosen.

At the published widths the model is 3.5 B parameters: 14 GB in
float32, which neither fits beside the server nor can come from one
jitted call (`benchmarks/reference.make_weights`).  So every leaf has a
key of its own, `fold_in`ed from the seed by (kind of op, leaf, layer,
expert); `make_weights(.., "program")` makes the program's copy one op
at a time, rounded to the stated precision AS IT IS MADE (the router
stays float32), and `make_weights(.., "reference")` returns only the
seed: `position_regrets` walks the layers and makes each one's float32
weights when it gets there, an expert at a time.

A serving family offers `build_server`, `make_weights` and
`position_regrets` (`drivers/serve.py` calls them).
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


# -- sizes ------------------------------------------------------------------
def published(cfg) -> dict:
    """The keyword arguments of `build_kimi_k2`, under the published
    config's own keys (plus the share: experts held, their first)."""
    dep = cfg["deployment"]
    keys = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "intermediate_size",
            "moe_intermediate_size", "first_k_dense_replace",
            "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob", "vocab_size",
            "rms_norm_eps", "rope_theta", "rope_scaling")
    kw = {k: cfg[k] for k in keys}
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
        self.L = kw["num_hidden_layers"]
        self.h = kw["num_attention_heads"]
        self.rq, self.rk = kw["q_lora_rank"], kw["kv_lora_rank"]
        self.dn, self.dr = kw["qk_nope_head_dim"], kw["qk_rope_head_dim"]
        self.dv = kw["v_head_dim"]
        self.f_dense = kw["intermediate_size"]
        self.f = kw["moe_intermediate_size"]
        self.first_dense = kw["first_k_dense_replace"]
        self.held = kw["n_routed_experts"]
        self.total = kw["n_routed_experts_total"]
        self.first_held = kw["first_held_expert"]
        self.f_shared = kw["n_shared_experts"] * self.f
        self.k = kw["num_experts_per_tok"]
        self.scaling = float(kw["routed_scaling_factor"])
        self.norm_topk = bool(kw["norm_topk_prob"])
        self.v = kw["vocab_size"]
        self.p = kw["max_position_embeddings"]
        self.eps = float(kw["rms_norm_eps"])
        self.theta = float(kw["rope_theta"])
        self.rope = dict(kw["rope_scaling"] or {})

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_dense


# -- the program --------------------------------------------------------------
def build_server(cfg, devices):
    """A model that is only ever served: no weight drawn, none held in
    float32; `set_weights` brings them in the stated precision.  Only
    sizes leave their defaults: slots and the pool."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.kimi_k2 import build_kimi_k2

    dep = cfg["deployment"]
    ff = FFModel(FFConfig(batch_size=1, num_devices=1,
                          compute_dtype=cfg["precision"],
                          serving_slots=dep["serving_slots"],
                          kv_page_size=dep["kv_page_size"],
                          kv_pool_blocks=dep["kv_pool_blocks"]))
    build_kimi_k2(ff, batch_size=1, seq_length=cfg["n_positions"],
                  **published(cfg))
    ff.compile(devices=list(devices), defer_weights=True)
    return ff


# -- weights, from the seed -----------------------------------------------------
def leaf_shapes(d: Dims, kind: str) -> dict:
    """{leaf: shape} of one op of a kind, in the program's layout; the
    routed experts' three matrices are per expert (`expert_*`)."""
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
        "moe": {"router": (e, d.total), "router_bias": (d.total,),
                "shared_gate": (e, d.f_shared), "shared_up": (e, d.f_shared),
                "shared_down": (d.f_shared, e)},
        "expert": {"w_gate": (e, d.f), "w_up": (e, d.f), "w_down": (d.f, e)},
        "lm_head": {"kernel": (e, d.v)},
    }[kind]


GAINS = ("gamma", "q_norm", "kv_norm")   # 1 + N(0, STD)
FLOAT32_LEAVES = ("router", "router_bias")


def leaf(key, kind: str, name: str, shape, layer=0, expert=0):
    """One leaf in float32: normal, std 0.02 (a gain: 1 + that), from a
    key of its own: the seed's, folded with the kind of op and the
    leaf's name (a fixed hash), the layer and the expert's index among
    ALL the router's experts (so every share makes the same expert)."""
    k = jax.random.fold_in(key, zlib.crc32(f"{kind}/{name}".encode())
                           & 0x7FFFFFFF)
    k = jax.random.fold_in(jax.random.fold_in(k, layer), expert)
    v = STD * jax.random.normal(k, shape, jnp.float32)
    return v + 1.0 if name in GAINS else v


@functools.partial(jax.jit, static_argnames=("d", "kind", "dtype"))
def make_op(key, layer, *, d: Dims, kind: str, dtype):
    """One op's weights in the program's layout and precision, each
    leaf rounded as it is made."""
    def put(name, v):
        return v if name in FLOAT32_LEAVES else v.astype(dtype)

    out = {name: put(name, leaf(key, kind, name, shape, layer))
           for name, shape in leaf_shapes(d, kind).items()}
    if kind == "moe":
        for name, shape in leaf_shapes(d, "expert").items():
            out[name] = jnp.stack([
                leaf(key, "expert", name, shape, layer,
                     d.first_held + x).astype(dtype)
                for x in range(d.held)])
    return out


def program_ops(d: Dims):
    """[(op name, kind, layer)] of every op of the program that has
    weights, in graph order."""
    ops = [("tok_embed", "tok_embed", 0)]
    for i in range(d.L):
        ops += [(f"attn_norm_{i}", "norm", 2 * i), (f"attn_{i}", "attn", i),
                (f"ffn_norm_{i}", "norm", 2 * i + 1),
                (f"mlp_{i}", "mlp", i) if d.is_dense(i)
                else (f"moe_{i}", "moe", i)]
    return ops + [("final_norm", "norm", 2 * d.L), ("lm_head", "lm_head", 0)]


class ReferenceWeights:
    """What the reference is handed: the seed.  Every float32 leaf is
    made where it is used (`leaf`), so that 14 GB never sit beside the
    server."""

    def __init__(self, cfg, seed: int):
        self.d, self.key = dims(cfg), ref.seed_key(seed)


def make_weights(cfg, seed: int, layout: str):
    if layout == "reference":
        return ReferenceWeights(cfg, seed)
    d, key = dims(cfg), ref.seed_key(seed)
    dtype = jnp.dtype(cfg["precision"])
    return {name: make_op(key, layer, d=d, kind=kind, dtype=dtype)
            for name, kind, layer in program_ops(d)}


# -- the plain reference --------------------------------------------------------
def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * gain


def yarn_frequencies(d: Dims):
    """[dr / 2] rotations per position (the closed form of ISSUE 29)."""
    dr, rs = d.dr, d.rope
    extra = d.theta ** (-np.arange(0, dr, 2, dtype=np.float64) / dr)
    factor = float(rs.get("factor", 1.0))
    if factor <= 1:
        return extra
    orig = rs["original_max_position_embeddings"]

    def pair_of(turns):
        return dr * math.log(orig / (2 * math.pi * turns)) \
            / (2 * math.log(d.theta))

    low = max(math.floor(pair_of(rs["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rs["beta_slow"])), dr - 1)
    ramp = np.clip((np.arange(dr // 2) - low) / max(high - low, 1e-3), 0, 1)
    return extra / factor * ramp + extra * (1 - ramp)


def mscale(d: Dims, which: str) -> float:
    factor = float(d.rope.get("factor", 1.0))
    return 1.0 if factor <= 1 else \
        0.1 * float(d.rope.get(which, 0.0)) * math.log(factor) + 1.0


def softmax_scale(d: Dims) -> float:
    return (d.dn + d.dr) ** -0.5 * mscale(d, "mscale_all_dim") ** 2


def rotate(x, d: Dims):
    """RoPE on x [s, ..., dr], positions 0..s-1: adjacent pairs."""
    ratio = mscale(d, "mscale") / mscale(d, "mscale_all_dim")
    angle = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
             * jnp.asarray(yarn_frequencies(d), jnp.float32))
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(angle) * ratio, jnp.sin(angle) * ratio
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


HEADS_AT_ONCE = 8  # [8, s, s] scores at a time, not [h, s, s]


def attention(x, w, d: Dims, q):
    """x [s, e] (already normed) -> [s, e]: expanded keys and values,
    full causal softmax, a few heads at a time."""
    s = x.shape[0]
    cq = rms(jnp.matmul(q(x), q(w["wq_a"])), w["q_norm"], d.eps)
    qh = jnp.einsum("sr,rhd->shd", q(cq), q(w["wq_b"]))
    q_nope, q_rope = qh[..., :d.dn], rotate(qh[..., d.dn:], d)
    kv = jnp.matmul(q(x), q(w["wkv_a"]))
    c = rms(kv[:, :d.rk], w["kv_norm"], d.eps)
    k_rope = rotate(kv[:, d.rk:], d)
    kvh = jnp.einsum("sc,chd->shd", q(c), q(w["wkv_b"]))
    k_nope, v = kvh[..., :d.dn], kvh[..., d.dn:]
    keep = jnp.tril(jnp.ones((s, s), bool))

    def some_heads(args):
        qn, qr, kn, vv = args  # [g, s, .]
        scores = (jnp.einsum("gqd,gkd->gqk", q(qn), q(kn))
                  + jnp.einsum("gqd,kd->gqk", q(qr), q(k_rope)))
        scores = jnp.where(keep, scores * softmax_scale(d), -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
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
    """h [s, e] -> combine weights [s, total]: zero where an expert was
    not chosen; float32, whatever the precision under test."""
    scores = jax.nn.sigmoid(jnp.matmul(h, router))
    _, chosen = jax.lax.top_k(scores + bias, d.k)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if d.norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(w * d.scaling)


def make_leaves(key, d: Dims, kind: str, layer, expert=0):
    return {name: leaf(key, kind, name, shape, layer, expert)
            for name, shape in leaf_shapes(d, kind).items()}


def experts(h, key, layer, d: Dims, q, held=None):
    """The routed part of one layer over the experts in `held`
    ((first, count); default the configuration's share) plus, counted
    once, the shared expert: h [s, e] -> [s, e].  One expert's weights
    exist at a time."""
    first, count = held if held is not None else (d.first_held, d.held)
    w = make_leaves(key, d, "moe", layer)
    combine = routing(h, w["router"], w["router_bias"], d)

    def one(acc, x):
        ew = make_leaves(key, d, "expert", layer, x)
        y = gated(h, ew["w_gate"], ew["w_up"], ew["w_down"], q)
        return acc + jnp.take(combine, x, axis=1)[:, None] * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             first + jnp.arange(count))
    return routed, gated(h, w["shared_gate"], w["shared_up"],
                         w["shared_down"], q)


@functools.partial(jax.jit, static_argnames=("d", "precision", "dense"))
def layer_fn(key, layer, x, *, d: Dims, precision: str, dense: bool):
    q = ref.rounder(precision)
    norm = lambda which: leaf(key, "norm", "gamma", (d.e,),  # noqa: E731
                              2 * layer + which)
    x = x + attention(rms(x, norm(0), d.eps),
                      make_leaves(key, d, "attn", layer), d, q)
    h = rms(x, norm(1), d.eps)
    if dense:
        w = make_leaves(key, d, "mlp", layer)
        return x + gated(h, w["w_gate"], w["w_up"], w["w_down"], q)
    routed, shared = experts(h, key, layer, d, q)
    return x + routed + shared


@functools.partial(jax.jit, static_argnames=("d",))
def embed_fn(key, ids, *, d: Dims):
    return jnp.take(leaf(key, "tok_embed", "weight", (d.v, d.e)), ids, axis=0)


@functools.partial(jax.jit, static_argnames=("d", "precision"))
def head_fn(key, x, *, d: Dims, precision: str):
    q = ref.rounder(precision)
    x = rms(x, leaf(key, "norm", "gamma", (d.e,), 2 * d.L), d.eps)
    return jnp.matmul(q(x), q(leaf(key, "lm_head", "kernel", (d.e, d.v))))


def logits_fn(w: ReferenceWeights, ids, precision: str):
    """ids [s] -> logits [s, vocab]: one full causal forward, a layer
    at a time."""
    d = w.d
    with jax.default_matmul_precision("highest"):
        x = embed_fn(w.key, ids, d=d)
        for i in range(d.L):
            x = layer_fn(w.key, i, x, d=d, precision=precision,
                         dense=d.is_dense(i))
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


# -- what a decode pass has to move and to compute -------------------------------
def parameter_counts(d: Dims) -> dict:
    """Parameters by where a decode pass finds them."""
    n = lambda kind: sum(int(np.prod(s))  # noqa: E731
                         for s in leaf_shapes(d, kind).values())
    moe_layers = d.L - d.first_dense
    return {
        "attention": d.L * n("attn"), "norms": (2 * d.L + 1) * d.e,
        "dense_mlp": d.first_dense * n("mlp"),
        "router": moe_layers * (d.e * d.total + d.total),
        "shared": moe_layers * 3 * d.e * d.f_shared,
        "one_expert": n("expert"), "held_experts": moe_layers * d.held,
        "table": d.v * d.e, "head": d.e * d.v,
    }


def latent_block_bytes(cfg) -> int:
    """Bytes of one physical block of the latent pool, all layers."""
    d = dims(cfg)
    return (d.L * cfg["deployment"]["kv_page_size"] * (d.rk + d.dr)
            * jnp.dtype(cfg["precision"]).itemsize)


def decode_pass_bytes(cfg, rows: int, experts_hit: float,
                      kv_blocks_live: float, kv_block_bytes: int) -> float:
    """Bytes one seq-1 pass over `rows` slots cannot avoid reading:
    every weight outside the routed experts once (the router in
    float32, the rest in the stated precision; of the table only the
    rows' own lines), the held experts that received a row
    (`experts_hit`, summed over layers), and the live pages of the
    latent pool (`kv_blocks_live` blocks of `kv_block_bytes`, all
    layers).  Activations, logits and cache writes are left out: the
    floor stays a floor."""
    d, c = dims(cfg), parameter_counts(dims(cfg))
    b = jnp.dtype(cfg["precision"]).itemsize
    return (b * (c["attention"] + c["norms"] + c["dense_mlp"] + c["shared"]
                 + c["head"] + rows * d.e + experts_hit * c["one_expert"])
            + 4 * c["router"] + kv_blocks_live * kv_block_bytes)


def decode_pass_flops(cfg, rows: int, context: float) -> float:
    """Operations of one seq-1 pass over `rows` slots with `context`
    cached tokens a row on average, as the program computes it: every
    held expert applied to every row, attention absorbed."""
    d, c = dims(cfg), parameter_counts(dims(cfg))
    dense = (c["attention"] + c["dense_mlp"] + c["router"] + c["shared"]
             + c["head"] + c["held_experts"] * c["one_expert"])
    attend = d.L * d.h * context * (2 * d.rk + d.dr)
    return 2.0 * rows * (dense + attend)
