"""`glm_dsa` (GLM-5.2's language model, `model_type: glm_moe_dsa`) behind
the serving front: how to build it in the program, its seeded weights,
its plain reference.

The program side is `models.glm_dsa.build_glm_dsa` -> `FFModel.compile(
defer_weights=True)` -> `set_weights` -> `serving.build_front`.  What
that graph is, and so what the reference computes, for a sequence of
tokens (h the layer's normed input at position t; `RMS(v; g) = v *
rsqrt(mean(v^2) + eps) * g`; no bias but the index key's LayerNorm):

    x = tok_embed[ids]
    every layer:
      h = RMS(x; g1);  c_q = RMS(h W_qa; g_q)
      q = c_q W_qb -> heads of [q_nope | q_rope];  [c_kv | k_r] = h W_kva
      c_kv <- RMS(c_kv; g_kv);  RoPE on q_rope (a head) and on the ONE k_r
      [k_nope | v]_head = c_kv W_kvb               the reference EXPANDS
      a `full` layer picks (64 of an index head's 128 channels rotate):
        q^I_j = RoPE(c_q W^I_q)_j          j = 1..index_n_heads
        k^I_s = RoPE(LayerNorm(h_s W^I_k))
        w_j   = (h W^I_w)_j * index_n_heads^-0.5 * index_head_dim^-0.5
        I(t, s) = sum_j w_j ReLU(q^I_j . k^I_s),  s <= t
        S_t = the min(t + 1, index_topk) keys s <= t of the largest I
      a `shared` layer takes S_t from the nearest `full` layer below
      score = (q_nope . k_nope + q_rope . k_r) (nope + rope)^-0.5 over
      s in S_t ONLY, softmax over S_t, x = x + concat_heads(sum p v) W_o
      dense:   x = x + W_d (silu(W_g RMS(x; g2)) * (W_u RMS(x; g2)))
      sparse, in float32:  s = sigmoid(RMS(x; g2) W_r); top k of s + b;
        w_e = scaling s_e / (sum of the k chosen s + 1e-20)
        x = x + sum_{e chosen AND held} w_e E_e(.) + E_shared(.)
    logits = RMS(x; g) W_head                  over the rows held

The layers are `deployment.first_layer` on of the published model's
(`indexer_types`, `mlp_layer_types`, both copied whole into the
configuration), `num_hidden_layers` of them.  S_t is found by a SORT:
a row's causal keys in a stable order of falling score, the first
`index_topk` kept (equal scores, which a ReLU's zeros make possible,
go to the earlier key, as `lax.top_k`, which the program takes, breaks
them).

The reference is given THE SAME SHARE as the program: the experts held
here and the slice of the vocabulary.  It keeps no cache and no state:
one forward over the whole sequence a layer at a time, `QUERIES_AT_ONCE`
queries at a time against the keys before the next multiple of
`KEYS_STEP` past them (every causal key is among those) under a
`[queries, keys]` mask, walked as far as the sequence's last block of
queries by loops whose trip count is data (one compiled layer for every
length), its MLPs `ROWS_AT_ONCE` rows at a time likewise and a routed
expert over the rows that chose it, `EXPERT_ROWS` at a time; a layer's
float32 weights are regenerated from the seed when it gets there, a
routed expert at a time.  (The blocks are how 12,800 positions fit
beside the server and how forty served sequences are judged inside a
run's time: the numbers are those of one block over everything,
`tests/test_glm_dsa.py`.)

Two CONTROLS OF THE MECHANISM are other references, never another
program (`position_regrets(.., selection=)`): `"dense"` attends every
causal key (selection off), `"above"` hands each `shared` layer the
picks of the nearest `full` layer ABOVE it (as the sound forward found
them) instead of below.  A program that selects as the equations say
must read far from both past position `index_topk`.

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
from benchmarks.families.kimi_k2 import gated, rms, routing

STD = 0.02
FULL = "full"


# -- sizes ------------------------------------------------------------------
def published(cfg) -> dict:
    """The keyword arguments of `build_glm_dsa`, under the published
    config's own keys (plus the share: experts held, their first; and
    the stretch of the published layers that is built)."""
    dep = cfg["deployment"]
    keys = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "index_n_heads", "index_head_dim", "index_topk",
            "intermediate_size", "moe_intermediate_size", "n_routed_experts",
            "n_shared_experts", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob", "vocab_size",
            "rms_norm_eps")
    kw = {k: cfg[k] for k in keys}
    first = dep["first_layer"]
    stretch = slice(first, first + cfg["num_hidden_layers"])
    kw["indexer_types"] = cfg["indexer_types"][stretch]
    kw["mlp_layer_types"] = cfg["mlp_layer_types"][stretch]
    kw["rope_theta"] = cfg["rope_parameters"]["rope_theta"]
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
    by identity (one per configuration: `dims`).  The attribute names
    `families/kimi_k2.py`'s `routing` reads are kept."""

    def __init__(self, kw):
        self.e = kw["hidden_size"]
        self.roles = tuple(kw["indexer_types"])
        self.mlps = tuple(kw["mlp_layer_types"])
        self.L = len(self.roles)
        self.h = kw["num_attention_heads"]
        self.rq, self.rk = kw["q_lora_rank"], kw["kv_lora_rank"]
        self.dn, self.dr = kw["qk_nope_head_dim"], kw["qk_rope_head_dim"]
        self.dv = kw["v_head_dim"]
        self.hi, self.di = kw["index_n_heads"], kw["index_head_dim"]
        self.topk = kw["index_topk"]
        self.f_dense = kw["intermediate_size"]
        self.f = kw["moe_intermediate_size"]
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

    def is_full(self, layer: int) -> bool:
        return self.roles[layer] == FULL

    def is_dense(self, layer: int) -> bool:
        return self.mlps[layer] == "dense"

    @property
    def full_layers(self) -> int:
        return sum(r == FULL for r in self.roles)

    @property
    def selects(self) -> bool:
        """Whether any query of a served sequence can have more keys in
        reach than it reads (else selection is the identity and the
        program builds neither index pool nor picks)."""
        return self.topk < self.p


# -- the program --------------------------------------------------------------
def build_server(cfg, devices):
    """A model that is only ever served: no weight drawn, none held in
    float32; `set_weights` brings them in the stated precision.  Only
    sizes leave their defaults: slots, the pool, the prefill chunk."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.glm_dsa import build_glm_dsa

    dep = cfg["deployment"]
    ff = FFModel(FFConfig(batch_size=1, num_devices=1,
                          compute_dtype=cfg["precision"],
                          serving_slots=dep["serving_slots"],
                          kv_page_size=dep["kv_page_size"],
                          kv_pool_blocks=dep["kv_pool_blocks"],
                          prefill_chunk=dep["prefill_chunk"]))
    build_glm_dsa(ff, batch_size=1, seq_length=cfg["n_positions"],
                  **published(cfg))
    ff.compile(devices=list(devices), defer_weights=True)
    return ff


# -- weights, from the seed -----------------------------------------------------
def leaf_shapes(d: Dims, kind: str) -> dict:
    """{leaf: shape} of one op of a kind, in the program's layout (a
    `full` layer's attention: `attn_full`, the indexer's five leaves
    behind the block's); the routed experts' three matrices are per
    expert (`expert`)."""
    e = d.e
    attn = {"wq_a": (e, d.rq), "q_norm": (d.rq,),
            "wq_b": (d.rq, d.h, d.dn + d.dr),
            "wkv_a": (e, d.rk + d.dr), "kv_norm": (d.rk,),
            "wkv_b": (d.rk, d.h, d.dn + d.dv), "wo": (d.h, d.dv, e)}
    return {
        "tok_embed": {"weight": (d.v, e)},
        "norm": {"gamma": (e,)},
        "attn": attn,
        "attn_full": dict(attn, wq_idx=(d.rq, d.hi, d.di), wk_idx=(e, d.di),
                          k_idx_norm=(d.di,), k_idx_bias=(d.di,),
                          w_idx=(e, d.hi)),
        "mlp": {"w_gate": (e, d.f_dense), "w_up": (e, d.f_dense),
                "w_down": (d.f_dense, e)},
        "moe": {"router": (e, d.total), "router_bias": (d.total,),
                "shared_gate": (e, d.f_shared), "shared_up": (e, d.f_shared),
                "shared_down": (d.f_shared, e)},
        "expert": {"w_gate": (e, d.f), "w_up": (e, d.f), "w_down": (d.f, e)},
        "lm_head": {"kernel": (e, d.v)},
    }[kind]


GAINS = ("gamma", "q_norm", "kv_norm", "k_idx_norm")   # 1 + N(0, STD)
FLOAT32_LEAVES = ("router", "router_bias")


def leaf(key, kind: str, name: str, shape, layer=0, expert=0):
    """One leaf in float32: normal, std 0.02 (a gain: 1 + that), from a
    key of its own: the seed's, folded with the leaf's name under its
    op's kind (a fixed hash; a `full` layer's attention leaves hash as
    a `shared` one's), the layer and the expert's index among ALL the
    router's experts (so every share makes the same expert)."""
    kind = "attn" if kind == "attn_full" else kind
    k = jax.random.fold_in(key, zlib.crc32(f"{kind}/{name}".encode())
                           & 0x7FFFFFFF)
    k = jax.random.fold_in(jax.random.fold_in(k, layer), expert)
    v = STD * jax.random.normal(k, shape, jnp.float32)
    return v + 1.0 if name in GAINS else v


def make_leaves(key, d: Dims, kind: str, layer, expert=0):
    return {name: leaf(key, kind, name, shape, layer, expert)
            for name, shape in leaf_shapes(d, kind).items()}


@functools.partial(jax.jit, static_argnames=("d", "kind", "dtype"))
def make_op(key, layer, *, d: Dims, kind: str, dtype):
    """One op's weights in the program's layout and precision, each
    leaf rounded as it is made."""
    def put(name, v):
        return v if name in FLOAT32_LEAVES else v.astype(dtype)

    out = {name: put(name, v)
           for name, v in make_leaves(key, d, kind, layer).items()}
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
        ops += [(f"attn_norm_{i}", "norm", 2 * i),
                (f"attn_{i}", "attn_full" if d.is_full(i) else "attn", i),
                (f"ffn_norm_{i}", "norm", 2 * i + 1),
                (f"mlp_{i}", "mlp", i) if d.is_dense(i)
                else (f"moe_{i}", "moe", i)]
    return ops + [("final_norm", "norm", 2 * d.L), ("lm_head", "lm_head", 0)]


class ReferenceWeights:
    """What the reference is handed: the seed.  Every float32 leaf is
    made where it is used (`leaf`), so that 15.5 GB never sit beside
    the server."""

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
def rotate(x, positions, d: Dims):
    """RoPE on x [s, ..., dr] at `positions [s]`: adjacent pairs, plain
    frequencies `theta^(-2i / dr)`."""
    freq = d.theta ** (-np.arange(0, d.dr, 2, dtype=np.float64) / d.dr)
    angle = (positions.astype(jnp.float32)[:, None]
             * jnp.asarray(freq, jnp.float32))
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def rotate_head(x, positions, d: Dims):
    """An index head: its first `dr` channels rotate, the rest pass."""
    return jnp.concatenate([rotate(x[..., :d.dr], positions, d),
                            x[..., d.dr:]], axis=-1)


def softmax_scale(d: Dims) -> float:
    return (d.dn + d.dr) ** -0.5


QUERIES_AT_ONCE = 64  # [heads, 64, s] scores at a time, not [heads, s, s]
ROWS_AT_ONCE = 512     # rows an MLP takes at a time
EXPERT_ROWS = 128      # of the rows that chose it, an expert's at a time
KEYS_STEP = 2048       # the keys a block of queries meets grow by this


def block_of(d: Dims) -> int:
    return math.gcd(d.p, QUERIES_AT_ONCE)


def rows_of(d: Dims) -> int:
    return math.gcd(d.p, ROWS_AT_ONCE)


def index_keys(h, positions, w, d: Dims, q):
    """k^I of h [s, e] -> [s, di]: LayerNorm with gain and bias (eps the
    model's), then RoPE on the first dr channels."""
    k = jnp.matmul(q(h), q(w["wk_idx"]))
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(jnp.square(k), axis=-1, keepdims=True)
                          + d.eps)
    return rotate_head(k * w["k_idx_norm"] + w["k_idx_bias"], positions, d)


def index_scores(hb, cq, at, ki, w, d: Dims, q):
    """I(t, s) of the queries hb [block, e] (their c_q, their positions
    `at`) against the keys ki [s, di]: [block, s] float32."""
    qi = rotate_head(jnp.einsum("sr,rjd->sjd", q(cq), q(w["wq_idx"])), at, d)
    wj = jnp.matmul(q(hb), q(w["w_idx"])) * (d.hi ** -0.5 * d.di ** -0.5)
    each = jax.nn.relu(jnp.einsum("qjd,kd->qjk", q(qi), q(ki)))
    return jnp.einsum("qjk,qj->qk", each, wj)


def picked(scores, causal, d: Dims):
    """[block, s] bool: S_t of each query, its `min(t + 1, index_topk)`
    causal keys of the largest score: a stable sort by falling score
    (of equal scores the earlier key first, as `lax.top_k` takes them),
    the first `index_topk` places kept."""
    if scores.shape[1] <= d.topk:
        return causal
    order = jnp.argsort(jnp.where(causal, -scores, jnp.inf), axis=-1,
                        stable=True)
    place = jnp.argsort(order, axis=-1)  # a key's place in that order
    return causal & (place < d.topk)


def attend(cq, at, keep, k_nope, k_rope, v, w, d: Dims, q):
    """The queries of c_q [block, rq] at `at` over the keys `keep
    [block, s]` allows, keys and values EXPANDED: [block, e]."""
    qh = jnp.einsum("sr,rhd->shd", q(cq), q(w["wq_b"]))
    q_nope, q_rope = qh[..., :d.dn], rotate(qh[..., d.dn:], at, d)
    scores = (jnp.einsum("qhd,khd->hqk", q(q_nope), q(k_nope))
              + jnp.einsum("qhd,kd->hqk", q(q_rope), q(k_rope)))
    probs = jax.nn.softmax(
        jnp.where(keep, scores * softmax_scale(d), -jnp.inf), axis=-1)
    ctx = jnp.einsum("hqk,khd->qhd", q(probs), q(v))
    return jnp.einsum("shd,hde->se", q(ctx), q(w["wo"]))


def over_rows(fn, h, used, d: Dims):
    """`fn(rows [n, e])` -> [n, e] over h [s, e], `rows_of(d)` rows at a
    time as far as the `used`-th (data); the rows past them stay
    zero."""
    n = rows_of(d)

    def body(i, out):
        return jax.lax.dynamic_update_slice_in_dim(
            out, fn(jax.lax.dynamic_slice_in_dim(h, i * n, n)), i * n,
            axis=0)

    return jax.lax.fori_loop(0, -(-used // n), body, jnp.zeros_like(h))


def key_extents(d: Dims) -> tuple:
    """Where the walk over a sequence's blocks of queries widens its
    keys: a block of queries that ends at or before an extent meets the
    keys before that extent only (the rest are not causal for it)."""
    return tuple(range(KEYS_STEP, d.p, KEYS_STEP)) + (d.p,)


def attention(h, used, keep, w, d: Dims, q, full: bool,
              selection: str = "dsa"):
    """h [s, e] (normed) -> (the attention's output [s, e], the picks
    `[s, s]` bool this layer read by: a `full` layer's own, a `shared`
    one's `keep` as handed), right for the first `used` (data)
    positions.  Three walks, each as far as `used`: the key side
    `rows_of(d)` positions at a time; in a `full` layer the picks of a
    block of queries past `index_topk` against every key (before it a
    query picks all its causal keys); the attention of a block of
    queries over the keys up to the next of `key_extents` past its own
    end, which holds every key that is causal for it."""
    s, block, n = h.shape[0], block_of(d), rows_of(d)
    pos = jnp.arange(s)

    def keys_of(i, made):
        """[c_q, k_nope, v, k_rope (, k^I)] of n more positions."""
        at = i * n + jnp.arange(n)
        hb = jax.lax.dynamic_slice_in_dim(h, i * n, n)
        kv = jnp.matmul(q(hb), q(w["wkv_a"]))
        c = rms(kv[:, :d.rk], w["kv_norm"], d.eps)
        kvh = jnp.einsum("sc,chd->shd", q(c), q(w["wkv_b"]))
        new = [rms(jnp.matmul(q(hb), q(w["wq_a"])), w["q_norm"], d.eps),
               kvh[..., :d.dn], kvh[..., d.dn:], rotate(kv[:, d.rk:], at, d)]
        if full:
            new.append(index_keys(hb, at, w, d, q))
        return [jax.lax.dynamic_update_slice_in_dim(m, x, i * n, 0)
                for m, x in zip(made, new)]

    widths = [(d.rq,), (d.h, d.dn), (d.h, d.dv), (d.dr,)] + [(d.di,)] * full
    cq, k_nope, v, k_rope, *ki = jax.lax.fori_loop(
        0, -(-used // n), keys_of,
        [jnp.zeros((s,) + width, h.dtype) for width in widths])
    blocks = -(-used // block)

    def queries(x, i):
        return jax.lax.dynamic_slice_in_dim(x, i * block, block)

    causal = pos[None, :] <= pos[:, None]
    if selection == "dense":
        keep = causal
    elif full:
        def some_picks(i, keep):
            at = i * block + jnp.arange(block)
            scores = index_scores(queries(h, i), queries(cq, i), at, ki[0],
                                  w, d, q)
            return jax.lax.dynamic_update_slice_in_dim(
                keep, picked(scores, queries(causal, i), d), i * block, 0)

        whole = d.topk // block  # blocks of queries that pick every key
        keep = jax.lax.fori_loop(
            jnp.minimum(whole, blocks), blocks, some_picks,
            causal & (pos[:, None] < whole * block))

    def blocks_under(m):
        """The walk over the blocks of queries whose keys are the first
        m."""
        def some_queries(i, out):
            at = i * block + jnp.arange(block)
            o = attend(queries(cq, i), at, queries(keep, i)[:, :m],
                       k_nope[:m], k_rope[:m], v[:m], w, d, q)
            return jax.lax.dynamic_update_slice_in_dim(out, o, i * block, 0)
        return some_queries

    out, done = jnp.zeros_like(h), 0
    for m in key_extents(d):
        out = jax.lax.fori_loop(jnp.minimum(done, blocks),
                                jnp.minimum(m // block, blocks),
                                blocks_under(m), out)
        done = m // block
    return out, keep


def experts(h, used, key, layer, d: Dims, q, held=None):
    """The routed part of one layer over the experts in `held` ((first,
    count); default the configuration's share) and, counted once, the
    shared expert, over the first `used` (data) rows of h [s, e]: ([s,
    e], [s, e]).  One expert's weights exist at a time, and an expert is
    applied to the rows that chose it alone, `EXPERT_ROWS` at a time as
    far as there are any (data)."""
    first, count = held if held is not None else (d.first_held, d.held)
    w = make_leaves(key, d, "moe", layer)
    live = jnp.arange(h.shape[0]) < used
    combine = jnp.where(live[:, None],
                        routing(h, w["router"], w["router_bias"], d), 0.0)
    n = math.gcd(d.p, EXPERT_ROWS)

    def one(acc, x):
        ew = make_leaves(key, d, "expert", layer, x)
        weight = jnp.take(combine, x, axis=1)
        chose = weight != 0
        # the rows that chose x, in order; row 0 behind the last of them
        order, = jnp.nonzero(chose, size=h.shape[0], fill_value=0)

        def some_rows(i, acc):
            rows = jax.lax.dynamic_slice_in_dim(order, i * n, n)
            real = i * n + jnp.arange(n) < jnp.sum(chose)
            y = gated(jnp.take(h, rows, axis=0), ew["w_gate"], ew["w_up"],
                      ew["w_down"], q)
            return acc.at[rows].add(
                jnp.where(real, jnp.take(weight, rows), 0.0)[:, None] * y)

        return jax.lax.fori_loop(0, -(-jnp.sum(chose) // n), some_rows,
                                 acc), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             first + jnp.arange(count))
    return routed, over_rows(
        lambda rows: gated(rows, w["shared_gate"], w["shared_up"],
                           w["shared_down"], q), h, used, d)


def norm_gain(key, d: Dims, index):
    return leaf(key, "norm", "gamma", (d.e,), index)


@functools.partial(jax.jit, static_argnames=(
    "d", "precision", "full", "dense", "selection"))
def layer_fn(key, layer, x, used, keep, *, d: Dims, precision: str,
             full: bool, dense: bool, selection: str = "dsa"):
    """One layer over x [s, e], right for its first `used` (data)
    positions: (x, the picks it read by)."""
    q = ref.rounder(precision)
    w = make_leaves(key, d, "attn_full" if full else "attn", layer)
    a, keep = attention(rms(x, norm_gain(key, d, 2 * layer), d.eps), used,
                        keep, w, d, q, full, selection)
    x = x + a
    h = rms(x, norm_gain(key, d, 2 * layer + 1), d.eps)
    if dense:
        m = make_leaves(key, d, "mlp", layer)
        return x + over_rows(
            lambda rows: gated(rows, m["w_gate"], m["w_up"], m["w_down"],
                               q), h, used, d), keep
    routed, shared = experts(h, used, key, layer, d, q)
    return x + routed + shared, keep


@functools.partial(jax.jit, static_argnames=("d",))
def embed_fn(key, ids, *, d: Dims):
    return jnp.take(leaf(key, "tok_embed", "weight", (d.v, d.e)), ids, axis=0)


@functools.partial(jax.jit, static_argnames=("d", "precision"))
def head_fn(key, x, *, d: Dims, precision: str):
    q = ref.rounder(precision)
    x = rms(x, norm_gain(key, d, 2 * d.L), d.eps)
    return jnp.matmul(q(x), q(leaf(key, "lm_head", "kernel", (d.e, d.v))))


def walk(w: "ReferenceWeights", ids, used: int, precision: str,
         selection: str = "dsa", handed=None):
    """ids [d.p] -> (the last layer's x [d.p, e], right for the first
    `used` positions; {full layer: the picks it made}).  `handed` maps a
    shared layer to the picks it reads instead of the last full
    layer's."""
    d = w.d
    used = jnp.int32(used)
    x = embed_fn(w.key, ids, d=d)
    keep, made = jnp.zeros((d.p, d.p), bool), {}
    for i in range(d.L):
        mine = (handed or {}).get(i, keep)
        x, mine = layer_fn(w.key, i, x, used, mine, d=d,
                           precision=precision, full=d.is_full(i),
                           dense=d.is_dense(i), selection=selection)
        if d.is_full(i):
            keep = made[i] = mine
    return x, made


def hidden_fn(w: "ReferenceWeights", ids, used: int, precision: str,
              selection: str = "dsa"):
    """The last layer's x under `selection`: "dsa" (the equations),
    "dense" (every causal key), "above" (each shared layer reads the
    picks the SOUND forward's nearest full layer above it made, where
    there is one)."""
    if selection != "above":
        return walk(w, ids, used, precision, selection)[0]
    d = w.d
    made = walk(w, ids, used, precision)[1]
    above = {i: made[min(j for j in made if j > i)]
             for i in range(d.L)
             if not d.is_full(i) and any(j > i for j in made)}
    return walk(w, ids, used, precision, handed=above)[0]


def logits_fn(w: ReferenceWeights, ids, precision: str,
              selection: str = "dsa"):
    """ids [s <= d.p] -> logits [s, vocab]: one full causal forward, a
    layer at a time (the tests' sizes: the whole head at once)."""
    d, s = w.d, len(ids)
    padded = jnp.zeros((d.p,), jnp.int32).at[:s].set(jnp.asarray(ids))
    with jax.default_matmul_precision("highest"):
        x = hidden_fn(w, padded, s, precision, selection)
        return head_fn(w.key, x, d=d, precision=precision)[:s]


HEAD_ROWS = 1024  # positions whose logits exist at a time


def position_regrets(w: ReferenceWeights, ids, chooser=None,
                     selection: str = "dsa"):
    """ids [d.p] (a served sequence, right-padded with zeros) -> regret
    [d.p - 1] of the token at position p + 1 under the float32
    reference's logits at p.  The forward runs over the sequence up to
    its last non-zero token (positions past that read regret 0), the
    head `HEAD_ROWS` positions at a time.  With ``chooser`` (a lower
    precision) the tokens judged are the ones the reference at that
    precision would pick, teacher-forced on the same context: the
    control.  ``selection`` puts another reference in the judge's seat
    (module docstring: the controls of the mechanism)."""
    d = w.d
    host = np.asarray(ids)
    used = int(np.flatnonzero(host)[-1]) + 1 if host.any() else 1
    rows = math.gcd(d.p, HEAD_ROWS)
    ids = jnp.asarray(host, jnp.int32)
    nxt = jnp.concatenate([ids[1:], ids[:1]])
    out = np.zeros(len(host) - 1, np.float32)
    with jax.default_matmul_precision("highest"):
        x = hidden_fn(w, ids, used, "float32", selection)
        xc = x if chooser is None else hidden_fn(w, ids, used, chooser)
        for a in range(0, used - 1, rows):
            want = head_fn(w.key, x[a:a + rows], d=d, precision="float32")
            chosen = (nxt[a:a + rows] if chooser is None else jnp.argmax(
                head_fn(w.key, xc[a:a + rows], d=d, precision=chooser),
                axis=-1))
            upto = min(a + rows, used - 1)
            out[a:upto] = np.asarray(
                check.position_regret(want, chosen))[:upto - a]
    return jnp.asarray(out)


# -- what a dispatch has to move and to compute ----------------------------------
def parameter_counts(d: Dims) -> dict:
    """Parameters by where a pass finds them."""
    n = lambda kind: sum(int(np.prod(s))  # noqa: E731
                         for s in leaf_shapes(d, kind).values())
    sparse = sum(not d.is_dense(i) for i in range(d.L))
    return {
        "attention": d.L * n("attn"),
        "indexer": d.full_layers * (n("attn_full") - n("attn")),
        "norms": (2 * d.L + 1) * d.e,
        "dense_mlp": (d.L - sparse) * n("mlp"),
        "router": sparse * (d.e * d.total + d.total),
        "shared": sparse * 3 * d.e * d.f_shared,
        "one_expert": n("expert"), "held_experts": sparse * d.held,
        "table": d.v * d.e, "head": d.e * d.v,
    }


def total_parameters(d: Dims) -> int:
    c = parameter_counts(d)
    return (sum(c[k] for k in ("attention", "indexer", "norms", "dense_mlp",
                               "router", "shared", "table", "head"))
            + c["held_experts"] * c["one_expert"])


def latent_block_bytes(cfg) -> int:
    """Bytes of one physical block of the pools, all layers: the
    latents of every layer and, where selection can bite, the index
    keys of the full ones, the latent rows then padded to whole 128-lane
    tiles (the program's `MLAttention.pool_width`: 640 for 576)."""
    d = dims(cfg)
    row = d.rk + d.dr
    if d.selects:
        row = -(-row // 128) * 128
    return (cfg["deployment"]["kv_page_size"]
            * (d.L * row + d.selects * d.full_layers * d.di)
            * jnp.dtype(cfg["precision"]).itemsize)


def index_scores_flops(cfg, keys_scored: float) -> float:
    """Operations of the index scores of `keys_scored` (query, live key)
    pairs, summed over the full layers (`dsa_keys_scored`): a product a
    head; the ReLU and the heads' weighted sum are left out."""
    d = dims(cfg)
    return 2.0 * keys_scored * d.hi * d.di


def index_read_bytes(cfg, index_blocks_live: float) -> float:
    """Bytes of `index_blocks_live` live blocks of index keys (summed
    over the full layers), each read once."""
    d = dims(cfg)
    return (index_blocks_live * cfg["deployment"]["kv_page_size"] * d.di
            * jnp.dtype(cfg["precision"]).itemsize)


def selected_read_bytes(cfg, keys_selected: float) -> float:
    """Bytes of the picked latents of `keys_selected` (query, picked
    key) pairs a layer (`dsa_keys_selected`), read once by EVERY
    layer."""
    d = dims(cfg)
    return (keys_selected * d.L * (d.rk + d.dr)
            * jnp.dtype(cfg["precision"]).itemsize)


def pass_flops(cfg, tokens: float, sampled: float, keys_selected: float,
               keys_scored: float) -> float:
    """Operations of one pass over `tokens` real tokens, `sampled` of
    which the head multiplies: the projections (the indexer's in the
    full layers), the router, the experts a token is routed to AND
    finds here (`k held / total` of them, an even router), the shared
    expert and the dense MLP a token; absorbed attention over the
    picked keys alone (a score over rank + rope and a value product
    over rank, a head, a layer); the index scores."""
    d, c = dims(cfg), parameter_counts(dims(cfg))
    sparse = c["held_experts"] // max(d.held, 1)
    per_token = 2.0 * (
        c["attention"] + c["indexer"] + c["dense_mlp"] + c["router"]
        + c["shared"] + sparse * d.k * d.held / d.total * c["one_expert"])
    return (tokens * per_token + 2.0 * sampled * c["head"]
            + 2.0 * keys_selected * d.L * d.h * (2 * d.rk + d.dr)
            + index_scores_flops(cfg, keys_scored))


def pass_bytes(cfg, tokens: float, keys_selected: float,
               index_blocks_live: float, experts_hit=None) -> float:
    """Bytes one pass over `tokens` real tokens cannot avoid moving:
    every weight outside the routed experts once (the router in
    float32, of the table only the tokens' own lines), the held experts
    that received a row (`experts_hit`, summed over layers; where the
    span lacks the count, the experts an even router would hit), the
    picked latents once a layer and the live index keys.  Activations,
    logits and the step's own writes are left out: the floor stays a
    floor."""
    d, c = dims(cfg), parameter_counts(dims(cfg))
    b = jnp.dtype(cfg["precision"]).itemsize
    if experts_hit is None:
        experts_hit = c["held_experts"] * (
            1.0 - (1.0 - d.k / d.total) ** max(tokens, 0.0))
    return (b * (c["attention"] + c["indexer"] + c["norms"] + c["dense_mlp"]
                 + c["shared"] + c["head"] + tokens * d.e
                 + experts_hit * c["one_expert"])
            + 4 * c["router"] + selected_read_bytes(cfg, keys_selected)
            + index_read_bytes(cfg, index_blocks_live))


def dispatch_least_s(cfg, peak, program: str, args: dict):
    """The least seconds the chip could take for ONE dispatch of
    `program` ("decode" or "prefill") whose span carries `args`
    (`readers/serve.mfu_share.py`): the larger of its operations over
    the bf16 peak and its bytes over the bandwidth, both over REAL
    tokens, PICKED keys and LIVE index keys only.  None where the span
    lacks the selection's counters."""
    need = ("dsa_keys_selected", "dsa_keys_scored", "index_blocks_live")
    if any(k not in args for k in need):
        return None
    if program == "decode":
        tokens = sampled = args["rows"] + args.get("feeding", 0)
    else:
        tokens, sampled = args["tokens"], args.get("decode_rows", 0)
    return max(
        pass_flops(cfg, tokens, sampled, args["dsa_keys_selected"],
                   args["dsa_keys_scored"]) / peak["bf16_flops_per_s"],
        pass_bytes(cfg, tokens, args["dsa_keys_selected"],
                   args["index_blocks_live"], args.get("moe_hit"))
        / peak["hbm_bytes_per_s"])
