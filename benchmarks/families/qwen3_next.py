"""`qwen3_next` (Qwen3-Next-80B-A3B's language model) behind the serving
front: how to build it in the program, its seeded weights, its plain
reference.

The program side is `models.qwen3_next.build_qwen3_next` ->
`FFModel.compile(defer_weights=True)` -> `set_weights` ->
`serving.build_front`.  What that graph is, and so what the reference
computes, for a sequence of tokens (no bias anywhere;
`RMS(v; w) = v * rsqrt(mean(v^2) + eps) * (1 + w)`, the gain stored
around zero):

    x = tok_embed[ids]
    layer i:  x = x + Mixer_i(RMS(x; w_in));  x = x + MoE(RMS(x; w_post))
    logits = RMS(x; w_f) W_head                 over the rows held

    Mixer_i, (i + 1) % full_attention_interval == 0: gated attention
        [q | gate] = h W_q per head; k = h W_k, v = h W_v (kv heads)
        q = RMS_head(q; w_qn), k = RMS_head(k; w_kn)
        rotary on the first head_dim * partial_rotary_factor channels,
        first half against second half
        causal softmax(q k^T / sqrt(head_dim)), a kv head shared by
        heads / kv_heads query heads; out = (attn * sigmoid(gate)) W_o
    else: Gated DeltaNet
        [q, k, v, z] = h W_qkvz; [b, a] = h W_ba
        [q, k, v] = silu(causal depthwise conv, kernel K, over time)
        q, k repeated to the value heads; q = l2norm(q) / sqrt(d_k),
        k = l2norm(k); beta = sigmoid(b)
        g = -exp(A_log) softplus(a + dt_bias)
        S = 0; every position in order, per head:
            S = exp(g_t) S; d = beta_t (v_t - S^T k_t); S += k_t d^T
            o_t = S^T q_t
        y = w_n rmsnorm(o) silu(z) per head; out = y W_out
    MoE, in float32:
        p = softmax(h W_r) over all experts; the top k; their p divided
        by their sum; sum_{e chosen AND held} p_e E_e(h)
        + sigmoid(w_sg . h) E_shared(h)

The reference is given THE SAME SHARE as the program: the experts held
here (`num_experts` of `deployment.n_routed_experts_published`, from
`deployment.first_held_expert`) and the slice of the vocabulary.
Experts that live on other chips add nothing, in the program and here
alike.  It keeps no cache and no state between calls: one forward over
the whole sequence, the delta rule position by position in a
`lax.scan`, attention a block of queries at a time.

Every leaf has a key of its own, `fold_in`ed from the seed by (kind of
op, leaf, layer, expert); `make_weights(.., "program")` makes the
program's copy one op at a time, rounded to the stated precision AS IT
IS MADE (the router, `A_log` and `dt_bias` stay float32), and
`make_weights(.., "reference")` returns only the seed: `logits_fn`
walks the layers and makes each one's float32 weights when it gets
there, an expert at a time.

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
    """The keyword arguments of `build_qwen3_next`, under the published
    config's own keys (plus the share: experts held, their first)."""
    dep = cfg["deployment"]
    keys = ("hidden_size", "num_hidden_layers", "full_attention_interval",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "partial_rotary_factor", "rope_theta", "rope_scaling",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "moe_intermediate_size",
            "shared_expert_intermediate_size", "num_experts",
            "num_experts_per_tok", "norm_topk_prob", "decoder_sparse_step",
            "mlp_only_layers", "vocab_size", "rms_norm_eps")
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
        self.interval = kw["full_attention_interval"]
        self.h, self.kvh = kw["num_attention_heads"], kw["num_key_value_heads"]
        self.hd = kw["head_dim"]
        self.rot = int(self.hd * kw["partial_rotary_factor"])
        self.theta = float(kw["rope_theta"])
        self.hk, self.hv = (kw["linear_num_key_heads"],
                            kw["linear_num_value_heads"])
        self.dk, self.dv = (kw["linear_key_head_dim"],
                            kw["linear_value_head_dim"])
        self.K = kw["linear_conv_kernel_dim"]
        self.key_dim, self.value_dim = self.hk * self.dk, self.hv * self.dv
        self.conv_dim = 2 * self.key_dim + self.value_dim
        self.f = kw["moe_intermediate_size"]
        self.f_shared = kw["shared_expert_intermediate_size"]
        self.held = kw["num_experts"]
        self.total = kw["n_routed_experts_total"]
        self.first_held = kw["first_held_expert"]
        self.k = kw["num_experts_per_tok"]
        self.norm_topk = bool(kw["norm_topk_prob"])
        self.v = kw["vocab_size"]
        self.p = kw["max_position_embeddings"]
        self.eps = float(kw["rms_norm_eps"])

    def is_full(self, layer: int) -> bool:
        return (layer + 1) % self.interval == 0

    @property
    def full_layers(self) -> int:
        return sum(self.is_full(i) for i in range(self.L))


# -- the program --------------------------------------------------------------
def build_server(cfg, devices):
    """A model that is only ever served: no weight drawn, none held in
    float32; `set_weights` brings them in the stated precision.  Sizes
    leave their defaults (slots, the pool), and `prefix_cache`: the
    family does not carry it (a page hit without the recurrent state at
    that position is wrong), and FFConfig's default asks for it."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.qwen3_next import build_qwen3_next

    dep = cfg["deployment"]
    ff = FFModel(FFConfig(batch_size=1, num_devices=1,
                          compute_dtype=cfg["precision"],
                          serving_slots=dep["serving_slots"],
                          kv_page_size=dep["kv_page_size"],
                          kv_pool_blocks=dep["kv_pool_blocks"],
                          prefix_cache=False))
    build_qwen3_next(ff, batch_size=1, seq_length=cfg["n_positions"],
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
        "attn": {"wq": (e, d.h, 2 * d.hd), "wk": (e, d.kvh, d.hd),
                 "wv": (e, d.kvh, d.hd), "wo": (d.h, d.hd, e),
                 "q_norm": (d.hd,), "k_norm": (d.hd,)},
        "gdn": {"in_proj_qkvz": (e, d.conv_dim + d.value_dim),
                "in_proj_ba": (e, 2 * d.hv), "conv1d": (d.conv_dim, d.K),
                "dt_bias": (d.hv,), "A_log": (d.hv,), "norm": (d.dv,),
                "out_proj": (d.value_dim, e)},
        "moe": {"router": (e, d.total), "router_bias": (d.total,),
                "shared_gate": (e, d.f_shared), "shared_up": (e, d.f_shared),
                "shared_down": (d.f_shared, e), "shared_expert_gate": (e,)},
        "expert": {"w_gate": (e, d.f), "w_up": (e, d.f), "w_down": (d.f, e)},
        "lm_head": {"kernel": (e, d.v)},
    }[kind]


#: gains applied as they are stored: 1 + N(0, STD).  The zero-centred
#: ones (`gamma`, `q_norm`, `k_norm`) are N(0, STD) like any matrix
ONE_CENTRED = ("gdn/norm", "gdn/dt_bias")
FLOAT32_LEAVES = ("router", "router_bias", "A_log", "dt_bias")


def leaf(key, kind: str, name: str, shape, layer=0, expert=0):
    """One leaf in float32, from a key of its own: the seed's, folded
    with the kind of op and the leaf's name (a fixed hash), the layer
    and the expert's index among ALL the router's experts (so every
    share makes the same expert).  Normal, std 0.02 (a gain: around its
    identity); `A_log = log U(0, 16)` and `dt_bias` around 1 as the
    published initialiser; the router has no bias (zeros: the op's
    bias only chooses)."""
    which = f"{kind}/{name}"
    k = jax.random.fold_in(key, zlib.crc32(which.encode()) & 0x7FFFFFFF)
    k = jax.random.fold_in(jax.random.fold_in(k, layer), expert)
    if name == "router_bias":
        return jnp.zeros(shape, jnp.float32)
    if name == "A_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 0.0, 16.0))
    v = STD * jax.random.normal(k, shape, jnp.float32)
    return v + 1.0 if which in ONE_CENTRED else v


@functools.partial(jax.jit, static_argnames=("d", "kind", "dtype"))
def make_op(key, layer, *, d: Dims, kind: str, dtype):
    """One op's weights in the program's layout and precision, each
    leaf rounded as it is made."""
    def put(name, v):
        return v if name in FLOAT32_LEAVES else v.astype(dtype)

    out = {name: put(name, leaf(key, kind, name, shape, layer))
           for name, shape in leaf_shapes(d, kind).items()}
    if kind == "moe":
        held = d.first_held + jnp.arange(d.held)
        for name, shape in leaf_shapes(d, "expert").items():
            out[name] = jax.lax.map(lambda x: leaf(  # noqa: B023
                key, "expert", name, shape, layer, x).astype(dtype), held)
    return out


def program_ops(d: Dims):
    """[(op name, kind, layer)] of every op of the program that has
    weights, in graph order."""
    ops = [("tok_embed", "tok_embed", 0)]
    for i in range(d.L):
        ops += [(f"input_norm_{i}", "norm", 2 * i),
                (f"attn_{i}", "attn", i) if d.is_full(i)
                else (f"gdn_{i}", "gdn", i),
                (f"post_norm_{i}", "norm", 2 * i + 1),
                (f"moe_{i}", "moe", i)]
    return ops + [("final_norm", "norm", 2 * d.L), ("lm_head", "lm_head", 0)]


class ReferenceWeights:
    """What the reference is handed: the seed.  Every float32 leaf is
    made where it is used (`leaf`), so that 15 GB never sit beside the
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
    """The family's norm: the gain is stored around zero."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * (1.0 + gain)


def rotate(x, d: Dims):
    """Rotary embedding on the first `d.rot` channels of x [s, heads,
    hd], positions 0..s-1: first half against second half."""
    half = d.rot // 2
    freq = d.theta ** (-np.arange(0, d.rot, 2, dtype=np.float64) / d.rot)
    angle = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None]
             * jnp.asarray(freq, jnp.float32))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:d.rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., d.rot:]], axis=-1)


QUERIES_AT_ONCE = 512  # [h, 512, s] scores at a time, not [h, s, s]


def attention(x, w, d: Dims, q):
    """x [s, e] (already normed) -> [s, e]: gated grouped-query
    attention, full causal softmax, a block of queries at a time."""
    s = x.shape[0]
    qg = jnp.einsum("se,ehd->shd", q(x), q(w["wq"]))
    qh, gate = qg[..., :d.hd], qg[..., d.hd:]
    kh = jnp.einsum("se,ehd->shd", q(x), q(w["wk"]))
    vh = jnp.einsum("se,ehd->shd", q(x), q(w["wv"]))
    qh = rotate(rms(qh, w["q_norm"], d.eps), d)
    kh = rotate(rms(kh, w["k_norm"], d.eps), d)
    kh = jnp.repeat(kh, d.h // d.kvh, axis=1)  # every query head its copy
    vh = jnp.repeat(vh, d.h // d.kvh, axis=1)
    block = math.gcd(s, QUERIES_AT_ONCE)
    key_pos = jnp.arange(s)

    def some_queries(args):
        qb, start = args  # [block, h, hd]
        scores = jnp.einsum("qhd,khd->hqk", q(qb), q(kh)) / math.sqrt(d.hd)
        keep = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", q(probs), q(vh))

    ctx = jax.lax.map(some_queries, (
        qh.reshape(s // block, block, d.h, d.hd),
        jnp.arange(0, s, block))).reshape(s, d.h, d.hd)
    return jnp.einsum("shd,hde->se", q(ctx * jax.nn.sigmoid(gate)),
                      q(w["wo"]))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


def delta_net(x, w, d: Dims, q):
    """x [s, e] (already normed) -> [s, e]: the Gated DeltaNet mixer,
    the state starting at zero, one position after another."""
    s = x.shape[0]
    mixed = jnp.matmul(q(x), q(w["in_proj_qkvz"]))
    qkv, z = mixed[:, :d.conv_dim], mixed[:, d.conv_dim:]
    ba = jnp.matmul(q(x), q(w["in_proj_ba"]))
    padded = jnp.concatenate([jnp.zeros((d.K - 1, d.conv_dim)), qkv])
    conv = jax.nn.silu(sum(padded[i:i + s] * w["conv1d"][:, i]
                           for i in range(d.K)))
    rep = d.hv // d.hk
    qh = jnp.repeat(l2norm(conv[:, :d.key_dim].reshape(s, d.hk, d.dk))
                    / math.sqrt(d.dk), rep, axis=1)
    kh = jnp.repeat(l2norm(conv[:, d.key_dim:2 * d.key_dim]
                           .reshape(s, d.hk, d.dk)), rep, axis=1)
    vh = conv[:, 2 * d.key_dim:].reshape(s, d.hv, d.dv)
    beta = jax.nn.sigmoid(ba[:, :d.hv])
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[:, d.hv:] + w["dt_bias"])

    def position(S, xs):  # S [hv, dk, dv]
        q_t, k_t, v_t, g_t, beta_t = xs
        S = S * jnp.exp(g_t)[:, None, None]
        delta = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * delta[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(position, jnp.zeros((d.hv, d.dk, d.dv)),
                        (qh, kh, vh, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + d.eps)
    y = o * w["norm"] * jax.nn.silu(z.reshape(s, d.hv, d.dv))
    return jnp.matmul(q(y.reshape(s, d.value_dim)), q(w["out_proj"]))


def gated(x, wg, wu, wd, q):
    return jnp.matmul(q(jax.nn.silu(jnp.matmul(q(x), q(wg)))
                        * jnp.matmul(q(x), q(wu))), q(wd))


def routing(h, router, d: Dims):
    """h [s, e] -> combine weights [s, total]: zero where an expert was
    not chosen; float32, whatever the precision under test."""
    p = jax.nn.softmax(jnp.matmul(h, router), axis=-1)
    w, chosen = jax.lax.top_k(p, d.k)
    if d.norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return jnp.zeros_like(p).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(w)


def make_leaves(key, d: Dims, kind: str, layer, expert=0):
    return {name: leaf(key, kind, name, shape, layer, expert)
            for name, shape in leaf_shapes(d, kind).items()}


def experts(h, key, layer, d: Dims, q, held=None):
    """The routed part of one layer over the experts in `held`
    ((first, count); default the configuration's share) and, counted
    once, the gated shared expert: h [s, e] -> ([s, e], [s, e]).  One
    expert's weights exist at a time."""
    first, count = held if held is not None else (d.first_held, d.held)
    w = make_leaves(key, d, "moe", layer)
    combine = routing(h, w["router"], d)

    def one(acc, x):
        ew = make_leaves(key, d, "expert", layer, x)
        y = gated(h, ew["w_gate"], ew["w_up"], ew["w_down"], q)
        return acc + jnp.take(combine, x, axis=1)[:, None] * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             first + jnp.arange(count))
    gate = jax.nn.sigmoid(jnp.matmul(q(h), q(w["shared_expert_gate"])))
    return routed, gate[:, None] * gated(
        h, w["shared_gate"], w["shared_up"], w["shared_down"], q)


@functools.partial(jax.jit, static_argnames=("d", "precision", "full"))
def layer_fn(key, layer, x, *, d: Dims, precision: str, full: bool):
    q = ref.rounder(precision)
    norm = lambda which: leaf(key, "norm", "gamma", (d.e,),  # noqa: E731
                              2 * layer + which)
    h = rms(x, norm(0), d.eps)
    if full:
        x = x + attention(h, make_leaves(key, d, "attn", layer), d, q)
    else:
        x = x + delta_net(h, make_leaves(key, d, "gdn", layer), d, q)
    routed, shared = experts(rms(x, norm(1), d.eps), key, layer, d, q)
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
                         full=d.is_full(i))
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


# -- what a decode pass has to move ---------------------------------------------
def parameter_counts(d: Dims) -> dict:
    """Parameters by where a decode pass finds them."""
    n = lambda kind: sum(int(np.prod(s))  # noqa: E731
                         for s in leaf_shapes(d, kind).values())
    shared = 3 * d.e * d.f_shared + d.e
    return {
        "attention": d.full_layers * n("attn"),
        "delta_net": (d.L - d.full_layers) * n("gdn"),
        "norms": (2 * d.L + 1) * d.e,
        "router": d.L * d.e * d.total, "shared": d.L * shared,
        "one_expert": n("expert"), "held_experts": d.L * d.held,
        "table": d.v * d.e, "head": d.e * d.v,
    }


def latent_block_bytes(cfg) -> int:
    """Bytes of one physical block of the paged pools, all layers (the
    name `readers/decode.hbm_roofline_share.py` asks for; here the
    full-attention layers' keys and values, `kv_heads x head_dim` each)."""
    d = dims(cfg)
    return (d.full_layers * cfg["deployment"]["kv_page_size"]
            * 2 * d.kvh * d.hd * jnp.dtype(cfg["precision"]).itemsize)


def rstate_row_bytes(cfg) -> int:
    """Bytes of ONE slot's recurrent state, all linear layers: the
    delta-rule matrix of every value head in float32 and the conv's
    tail in the stated precision."""
    d = dims(cfg)
    return (d.L - d.full_layers) * (
        4 * d.hv * d.dk * d.dv
        + (d.K - 1) * d.conv_dim * jnp.dtype(cfg["precision"]).itemsize)


def decode_pass_bytes(cfg, rows: int, experts_hit: float,
                      kv_blocks_live: float, kv_block_bytes: int,
                      rstate_rows_live=None) -> float:
    """Bytes one seq-1 pass over `rows` slots cannot avoid moving: every
    weight outside the routed experts once (the router in float32, the
    rest in the stated precision; of the table only the rows' own
    lines), the held experts that received a row (`experts_hit`, summed
    over layers), the live pages of the k/v pool, and the recurrent
    state of the live rows READ AND WRITTEN (`rstate_rows_live`; where
    the caller has no such count, every one of `rows`: the accepted
    roofline reader passes the slots, which in a cell above capacity
    are all live).  Activations, logits and k/v writes are left out:
    the floor stays a floor."""
    d, c = dims(cfg), parameter_counts(dims(cfg))
    b = jnp.dtype(cfg["precision"]).itemsize
    live = rows if rstate_rows_live is None else rstate_rows_live
    return (b * (c["attention"] + c["delta_net"] + c["norms"] + c["shared"]
                 + c["head"] + rows * d.e + experts_hit * c["one_expert"])
            + 4 * c["router"] + kv_blocks_live * kv_block_bytes
            + 2 * live * rstate_row_bytes(cfg))
