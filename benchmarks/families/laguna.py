"""`laguna` (poolside Laguna-XS.2) behind the serving front: how to build
it in the program, its seeded weights, its plain reference.

The program side is `models.laguna.build_laguna` ->
`FFModel.compile(defer_weights=True)` -> `set_weights` ->
`serving.build_front`.  What that graph is, and so what the reference
computes, for a sequence of tokens (no bias anywhere; `RMS(v; g) = v *
rsqrt(mean(v^2) + eps) * g`; layer `i` has `t_i = layer_types[i]`,
`n_i = num_attention_heads_per_layer[i]`, `G_i = n_i / kv heads`):

    x = tok_embed[ids]
    a = RMS(x; g1)
    q = a Wq  [n_i x d]   k = a Wk  [kv x d]   v = a Wv  [kv x d]
    gate = sigmoid(a Wg)  [n_i]
    full:     RoPE on the first partial_rotary_factor of each head of q
              and k (channel i against channel i + rot / 2), YaRN
              frequencies, cos and sin times attention_factor
    sliding:  RoPE on all d channels, its own theta, no scaling
    score[h, s, j] = q[h, s] . k[h // G_i, j] / sqrt(d)
    visible:  full  j <= s ;  sliding  s - sliding_window < j <= s
    o[h, s] = sum_j softmax_j(score)[h, s, j] v[h // G_i, j]
    x = x + concat_h(gate[h] o[h]) Wo
    b = RMS(x; g2)
    dense:   x = x + (silu(b Wgate) * (b Wup)) Wdown
    sparse:  s = sigmoid(b Wr) in float32; top k of s;
             w = moe_routed_scaling_factor s_e / sum_chosen s
             x = x + sum over e chosen AND held of w_e E_e(b) + E_shared(b)
    logits = RMS(x; g) W_head

The reference is given THE SAME SHARE as the program: the experts held
here (`num_experts` of `deployment.n_routed_experts_published`, from
`deployment.first_held_expert`).  Experts that live on other chips add
nothing, in the program and here alike.  It keeps no cache, no ring and
no state between calls: one forward over the whole sequence, a layer at
a time, `QUERIES_AT_ONCE` queries at a time against every key under a
`[queries, keys]` mask (a window layer's keys cut to the stretch its
mask can reach).  A sequence is walked as far as its last block of
queries, by a loop whose trip count is data, so one compiled layer
serves every length.

Every leaf has a key of its own, `fold_in`ed from the seed by (kind of
op, leaf, layer, expert); `make_weights(.., "program")` makes the
program's copy one op at a time, rounded to the stated precision AS IT
IS MADE (the router stays float32), and `make_weights(..,
"reference")` returns only the seed: `hidden_fn` walks the layers and
makes each one's float32 weights when it gets there.

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
FULL, SLIDING = "full_attention", "sliding_attention"


# -- sizes ------------------------------------------------------------------
def published(cfg) -> dict:
    """The keyword arguments of `build_laguna`, under the published
    config's own keys (plus the share: experts held, their first).  The
    three per-layer lists are the source's, whole; the layers kept are
    their first `num_hidden_layers` entries."""
    dep = cfg["deployment"]
    keys = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "sliding_window",
            "rope_parameters", "gating", "intermediate_size",
            "moe_intermediate_size", "shared_expert_intermediate_size",
            "num_experts", "num_experts_per_tok",
            "moe_routed_scaling_factor", "vocab_size", "rms_norm_eps")
    kw = {k: cfg[k] for k in keys}
    n = cfg["num_hidden_layers"]
    for k in ("layer_types", "mlp_layer_types",
              "num_attention_heads_per_layer"):
        kw[k] = list(cfg[k][:n])
    kw["max_position_embeddings"] = cfg["n_positions"]
    kw["n_routed_experts_total"] = dep["n_routed_experts_published"]
    kw["first_held_expert"] = dep["first_held_expert"]
    kw["prefill_chunk"] = dep["prefill_chunk"]
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
        self.kinds = tuple(kw["layer_types"])
        self.mlps = tuple(kw["mlp_layer_types"])
        self.heads = tuple(kw["num_attention_heads_per_layer"])
        self.kvh, self.hd = kw["num_key_value_heads"], kw["head_dim"]
        self.window = kw["sliding_window"]
        self.rope = {k: dict(kw["rope_parameters"][k])
                     for k in (FULL, SLIDING)}
        self.gating = bool(kw["gating"])
        self.f_dense = kw["intermediate_size"]
        self.f = kw["moe_intermediate_size"]
        self.f_shared = kw["shared_expert_intermediate_size"]
        self.held = kw["num_experts"]
        self.total = kw["n_routed_experts_total"]
        self.first_held = kw["first_held_expert"]
        self.k = kw["num_experts_per_tok"]
        self.scaling = float(kw["moe_routed_scaling_factor"])
        self.v = kw["vocab_size"]
        self.p = kw["max_position_embeddings"]
        self.eps = float(kw["rms_norm_eps"])
        self.chunk = kw["prefill_chunk"]

    def is_full(self, layer: int) -> bool:
        return self.kinds[layer] == FULL

    def is_dense(self, layer: int) -> bool:
        return self.mlps[layer] == "dense"

    @property
    def full_layers(self) -> int:
        return sum(k == FULL for k in self.kinds)

    @property
    def window_layers(self) -> int:
        return self.L - self.full_layers


# -- the program --------------------------------------------------------------
def build_server(cfg, devices):
    """A model that is only ever served: no weight drawn, none held in
    float32; `set_weights` brings them in the stated precision.  Sizes
    leave their defaults (slots, the pool, the prefill chunk, which also
    sizes the window layers' rings), and `prefix_cache`: the family does
    not carry it (a page hit without the ring at that position is
    wrong), and FFConfig's default asks for it."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.laguna import build_laguna

    dep = cfg["deployment"]
    ff = FFModel(FFConfig(batch_size=1, num_devices=1,
                          compute_dtype=cfg["precision"],
                          serving_slots=dep["serving_slots"],
                          kv_page_size=dep["kv_page_size"],
                          kv_pool_blocks=dep["kv_pool_blocks"],
                          prefill_chunk=dep["prefill_chunk"],
                          prefix_cache=False))
    build_laguna(ff, batch_size=1, seq_length=cfg["n_positions"],
                 **published(cfg))
    ff.compile(devices=list(devices), defer_weights=True)
    return ff


# -- weights, from the seed -----------------------------------------------------
def leaf_shapes(d: Dims, kind: str, heads: int = 0) -> dict:
    """{leaf: shape} of one op of a kind, in the program's layout (an
    attention op's by its layer's `heads`); the routed experts' three
    matrices are per expert (`expert`)."""
    e = d.e
    if kind == "attn":
        return {"wq": (e, heads, d.hd), "wk": (e, d.kvh, d.hd),
                "wv": (e, d.kvh, d.hd), "wo": (heads, d.hd, e),
                **({"wg": (e, heads)} if d.gating else {})}
    return {
        "tok_embed": {"weight": (d.v, e)},
        "norm": {"gamma": (e,)},
        "mlp": {"w_gate": (e, d.f_dense), "w_up": (e, d.f_dense),
                "w_down": (d.f_dense, e)},
        "moe": {"router": (e, d.total), "router_bias": (d.total,),
                "shared_gate": (e, d.f_shared), "shared_up": (e, d.f_shared),
                "shared_down": (d.f_shared, e)},
        "expert": {"w_gate": (e, d.f), "w_up": (e, d.f), "w_down": (d.f, e)},
        "lm_head": {"kernel": (e, d.v)},
    }[kind]


FLOAT32_LEAVES = ("router", "router_bias")


def leaf(key, kind: str, name: str, shape, layer=0, expert=0):
    """One leaf in float32: normal, std 0.02 (a gain: 1 + that; the
    router's bias: zeros, the published router has none), from a key of
    its own: the seed's, folded with the kind of op and the leaf's name
    (a fixed hash), the layer and the expert's index among ALL the
    router's experts (so every share makes the same expert)."""
    if name == "router_bias":
        return jnp.zeros(shape, jnp.float32)
    k = jax.random.fold_in(key, zlib.crc32(f"{kind}/{name}".encode())
                           & 0x7FFFFFFF)
    k = jax.random.fold_in(jax.random.fold_in(k, layer), expert)
    v = STD * jax.random.normal(k, shape, jnp.float32)
    return v + 1.0 if name == "gamma" else v


def make_leaves(key, d: Dims, kind: str, layer, heads: int = 0):
    return {name: leaf(key, kind, name, shape, layer)
            for name, shape in leaf_shapes(d, kind, heads).items()}


def held_experts(key, d: Dims, layer, held=None):
    """{leaf: [count, ...]} float32: the experts in `held` ((first,
    count); default the configuration's share) of one layer."""
    first, count = held if held is not None else (d.first_held, d.held)
    return {name: jax.lax.map(lambda x: leaf(  # noqa: B023
        key, "expert", name, shape, layer, x), first + jnp.arange(count))
        for name, shape in leaf_shapes(d, "expert").items()}


@functools.partial(jax.jit, static_argnames=("d", "kind", "heads", "dtype"))
def make_op(key, layer, *, d: Dims, kind: str, heads: int, dtype):
    """One op's weights in the program's layout and precision, each
    leaf rounded as it is made."""
    def put(name, v):
        return v if name in FLOAT32_LEAVES else v.astype(dtype)

    out = {name: put(name, v)
           for name, v in make_leaves(key, d, kind, layer, heads).items()}
    if kind == "moe":
        out.update({name: v.astype(dtype)
                    for name, v in held_experts(key, d, layer).items()})
    return out


def program_ops(d: Dims):
    """[(op name, kind, layer, heads)] of every op of the program that
    has weights, in graph order."""
    ops = [("tok_embed", "tok_embed", 0, 0)]
    for i in range(d.L):
        ops += [(f"input_norm_{i}", "norm", 2 * i, 0),
                (f"attn_{i}", "attn", i, d.heads[i]),
                (f"post_norm_{i}", "norm", 2 * i + 1, 0),
                (f"mlp_{i}", "mlp", i, 0) if d.is_dense(i)
                else (f"moe_{i}", "moe", i, 0)]
    return ops + [("final_norm", "norm", 2 * d.L, 0),
                  ("lm_head", "lm_head", 0, 0)]


class ReferenceWeights:
    """What the reference is handed: the seed.  Every float32 leaf is
    made where it is used (`leaf`), so that 8.7 GB never sit beside the
    server."""

    def __init__(self, cfg, seed: int):
        self.d, self.key = dims(cfg), ref.seed_key(seed)


def make_weights(cfg, seed: int, layout: str):
    if layout == "reference":
        return ReferenceWeights(cfg, seed)
    d, key = dims(cfg), ref.seed_key(seed)
    dtype = jnp.dtype(cfg["precision"])
    return {name: make_op(key, layer, d=d, kind=kind, heads=heads,
                          dtype=dtype)
            for name, kind, layer, heads in program_ops(d)}


# -- the plain reference --------------------------------------------------------
def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * gain


def rope_of(d: Dims, full: bool):
    """(rotary channels, their frequencies [rot / 2] float64, the factor
    on cos and sin) of a layer type: plain `theta^(-2i / rot)`, or the
    YaRN blend of those and those over `factor`, over a linear ramp
    between the pairs that turn `beta_fast` and `beta_slow` times in
    the original context."""
    r = d.rope[FULL if full else SLIDING]
    rot = int(d.hd * r.get("partial_rotary_factor", 1.0))
    theta = float(r["rope_theta"])
    extra = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    if r.get("rope_type", "default") != "yarn":
        return rot, extra, 1.0
    factor, original = float(r["factor"]), r["original_max_position_embeddings"]

    def pair_of(turns):
        return (rot * math.log(original / (2 * math.pi * turns))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(r["beta_fast"])), 0)
    high = min(math.ceil(pair_of(r["beta_slow"])), rot - 1)
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (rot, extra / factor * ramp + extra * (1.0 - ramp),
            float(r.get("attention_factor", 1.0)))


def rotate(x, positions, rope):
    """Rotary embedding on the first `rot` channels of x [s, heads, hd]
    at `positions` [s]: first half against second half."""
    rot, freq, factor = rope
    half = rot // 2
    angle = (positions.astype(jnp.float32)[:, None, None]
             * jnp.asarray(freq, jnp.float32))
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


QUERIES_AT_ONCE = 128  # [heads, 128, s] scores at a time, not [heads, s, s]


def block_of(d: Dims) -> int:
    return math.gcd(d.p, QUERIES_AT_ONCE)


def gated(x, wg, wu, wd, q):
    return jnp.matmul(q(jax.nn.silu(jnp.matmul(q(x), q(wg)))
                        * jnp.matmul(q(x), q(wu))), q(wd))


def routing(h, router, d: Dims):
    """h [s, e] -> combine weights [s, total]: zero where an expert was
    not chosen; float32, whatever the precision under test."""
    p = jax.nn.sigmoid(jnp.matmul(h, router))
    w, chosen = jax.lax.top_k(p, d.k)
    w = d.scaling * w / jnp.sum(w, axis=-1, keepdims=True)
    return jnp.zeros_like(p).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(w)


def experts(h, w, ew, d: Dims, q, first=None):
    """The routed part of one layer over the experts `ew` holds (from
    `first`; default the configuration's share) and, counted once, the
    shared expert: h [s, e] -> ([s, e], [s, e])."""
    first = d.first_held if first is None else first
    count = ew["w_gate"].shape[0]
    combine = jax.lax.dynamic_slice_in_dim(
        routing(h, w["router"], d), first, count, axis=1)
    mid = (jax.nn.silu(jnp.einsum("se,nef->nsf", q(h), q(ew["w_gate"])))
           * jnp.einsum("se,nef->nsf", q(h), q(ew["w_up"])))
    each = jnp.einsum("nsf,nfe->nse", q(mid), q(ew["w_down"]))
    return (jnp.einsum("sn,nse->se", combine, each),
            gated(h, w["shared_gate"], w["shared_up"], w["shared_down"], q))


def attention_block(hb, start, kh, vh, w, d: Dims, q, full: bool, rope):
    """hb [block, e] (normed, positions `start` on) against the whole
    sequence's keys and values kh, vh [s, kv, hd] -> [block, e]."""
    block, s = hb.shape[0], kh.shape[0]
    heads = w["wq"].shape[1]
    at = start + jnp.arange(block)
    qh = rotate(jnp.einsum("se,ehd->shd", q(hb), q(w["wq"])), at, rope)
    if full:
        first = 0
    else:  # the stretch of keys a window layer's mask can reach
        stretch = min(s, -(-(d.window + block) // block) * block)
        first = jnp.clip(start + block - stretch, 0, s - stretch)
        kh = jax.lax.dynamic_slice_in_dim(kh, first, stretch)
        vh = jax.lax.dynamic_slice_in_dim(vh, first, stretch)
    key_pos = first + jnp.arange(kh.shape[0])
    keep = key_pos[None, :] <= at[:, None]
    if not full:
        keep &= key_pos[None, :] > at[:, None] - d.window
    qg = qh.reshape(block, d.kvh, heads // d.kvh, d.hd)
    scores = jnp.einsum("qkgd,nkd->kgqn", q(qg), q(kh)) / math.sqrt(d.hd)
    probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("kgqn,nkd->qkgd", q(probs), q(vh)).reshape(
        block, heads, d.hd)
    if d.gating:
        ctx = ctx * jax.nn.sigmoid(jnp.matmul(q(hb), q(w["wg"])))[..., None]
    return jnp.einsum("shd,hde->se", q(ctx), q(w["wo"]))


@functools.partial(jax.jit, static_argnames=(
    "d", "precision", "full", "dense", "heads"))
def layer_fn(key, layer, x, blocks, *, d: Dims, precision: str, full: bool,
             dense: bool, heads: int):
    """One layer over x [s, e], the first `blocks` (data) blocks of
    `block_of(d)` queries; the rows past them pass as they are."""
    q = ref.rounder(precision)
    block = block_of(d)
    rope = rope_of(d, full)
    norm = lambda which: leaf(key, "norm", "gamma", (d.e,),  # noqa: E731
                              2 * layer + which)
    g1, g2 = norm(0), norm(1)
    w = make_leaves(key, d, "attn", layer, heads)
    h = rms(x, g1, d.eps)
    at = jnp.arange(x.shape[0])
    kh = rotate(jnp.einsum("se,ehd->shd", q(h), q(w["wk"])), at, rope)
    vh = jnp.einsum("se,ehd->shd", q(h), q(w["wv"]))
    if dense:
        m = make_leaves(key, d, "mlp", layer)
    else:
        m, ew = make_leaves(key, d, "moe", layer), held_experts(key, d, layer)

    def some_queries(i, x):
        start = i * block
        xb = jax.lax.dynamic_slice_in_dim(x, start, block)
        xb = xb + attention_block(rms(xb, g1, d.eps), start, kh, vh, w, d,
                                  q, full, rope)
        b = rms(xb, g2, d.eps)
        if dense:
            xb = xb + gated(b, m["w_gate"], m["w_up"], m["w_down"], q)
        else:
            routed, shared = experts(b, m, ew, d, q)
            xb = xb + routed + shared
        return jax.lax.dynamic_update_slice_in_dim(x, xb, start, axis=0)

    return jax.lax.fori_loop(0, blocks, some_queries, x)


@functools.partial(jax.jit, static_argnames=("d",))
def embed_fn(key, ids, *, d: Dims):
    return jnp.take(leaf(key, "tok_embed", "weight", (d.v, d.e)), ids, axis=0)


@functools.partial(jax.jit, static_argnames=("d", "precision"))
def head_fn(key, x, *, d: Dims, precision: str):
    q = ref.rounder(precision)
    x = rms(x, leaf(key, "norm", "gamma", (d.e,), 2 * d.L), d.eps)
    return jnp.matmul(q(x), q(leaf(key, "lm_head", "kernel", (d.e, d.v))))


def hidden_fn(w: ReferenceWeights, ids, used: int, precision: str):
    """ids [d.p] -> the last layer's x [d.p, e], right for the first
    `used` positions (whole blocks of queries are walked)."""
    d = w.d
    blocks = jnp.int32(-(-used // block_of(d)))
    x = embed_fn(w.key, ids, d=d)
    for i in range(d.L):
        x = layer_fn(w.key, i, x, blocks, d=d, precision=precision,
                     full=d.is_full(i), dense=d.is_dense(i),
                     heads=d.heads[i])
    return x


def logits_fn(w: ReferenceWeights, ids, precision: str):
    """ids [s <= d.p] -> logits [s, vocab]: one full causal forward, a
    layer at a time (the tests' sizes: the whole head at once)."""
    d, s = w.d, len(ids)
    padded = jnp.zeros((d.p,), jnp.int32).at[:s].set(jnp.asarray(ids))
    with jax.default_matmul_precision("highest"):
        x = hidden_fn(w, padded, s, precision)
        return head_fn(w.key, x, d=d, precision=precision)[:s]


HEAD_ROWS = 1024  # positions whose logits exist at a time


def position_regrets(w: ReferenceWeights, ids, chooser=None):
    """ids [d.p] (a served sequence, right-padded with zeros) -> regret
    [d.p - 1] of the token at position p + 1 under the float32
    reference's logits at p.  The forward runs over the sequence up to
    its last non-zero token (the attention is causal; positions past
    that read regret 0: a served sequence that ENDS in token 0 would
    lose those few positions from the statistic), the head `HEAD_ROWS`
    positions at a time.  With ``chooser`` (a lower precision) the
    tokens judged are the ones the reference at that precision would
    pick, teacher-forced on the same context: the control."""
    d = w.d
    host = np.asarray(ids)
    used = int(np.flatnonzero(host)[-1]) + 1 if host.any() else 1
    rows = math.gcd(d.p, HEAD_ROWS)
    ids = jnp.asarray(host, jnp.int32)
    nxt = jnp.concatenate([ids[1:], ids[:1]])
    out = np.zeros(len(host) - 1, np.float32)
    with jax.default_matmul_precision("highest"):
        x = hidden_fn(w, ids, used, "float32")
        xc = (x if chooser is None else hidden_fn(w, ids, used, chooser))
        for a in range(0, used - 1, rows):
            want = head_fn(w.key, x[a:a + rows], d=d, precision="float32")
            chosen = (nxt[a:a + rows] if chooser is None else jnp.argmax(
                head_fn(w.key, xc[a:a + rows], d=d, precision=chooser),
                axis=-1))
            upto = min(a + rows, used - 1)
            out[a:upto] = np.asarray(
                check.position_regret(want, chosen))[:upto - a]
    return jnp.asarray(out)


# -- what a dispatch has to move ------------------------------------------------
def parameter_counts(d: Dims) -> dict:
    """Parameters by where a pass finds them."""
    n = lambda kind, heads=0: sum(  # noqa: E731
        int(np.prod(s)) for s in leaf_shapes(d, kind, heads).values())
    sparse = sum(not d.is_dense(i) for i in range(d.L))
    return {
        "attention": sum(n("attn", h) for h in d.heads),
        "dense_mlp": (d.L - sparse) * n("mlp"),
        "norms": (2 * d.L + 1) * d.e,
        "router": sparse * d.e * d.total,
        "shared": sparse * 3 * d.e * d.f_shared,
        "one_expert": n("expert"), "held_experts": sparse * d.held,
        "table": d.v * d.e, "head": d.e * d.v,
    }


def kv_row_bytes(cfg) -> int:
    """Bytes of one position's keys and values in ONE layer."""
    d = dims(cfg)
    return 2 * d.kvh * d.hd * jnp.dtype(cfg["precision"]).itemsize


def latent_block_bytes(cfg) -> int:
    """Bytes of one physical block of the paged pools: the FULL layers'
    keys and values alone (the window layers hold no pages)."""
    return (dims(cfg).full_layers * cfg["deployment"]["kv_page_size"]
            * kv_row_bytes(cfg))


def paged_read_bytes(cfg, kv_blocks_live: float) -> float:
    """Bytes of `kv_blocks_live` blocks of the sequences' tables, read
    once by every full layer."""
    return kv_blocks_live * latent_block_bytes(cfg)


def swa_read_bytes(cfg, rows: float) -> float:
    """Bytes of `rows` ring rows (as the dispatch spans count them:
    `swa_rows_live` / `swa_rows_read`, summed over the window layers),
    each read once."""
    return rows * kv_row_bytes(cfg)


def swa_state_bytes(cfg, ring: int) -> int:
    """Bytes of every slot's rings of `ring` rows, all window layers."""
    return (dims(cfg).window_layers * cfg["deployment"]["serving_slots"]
            * ring * kv_row_bytes(cfg))


def pass_bytes(cfg, rows: float, kv_blocks_live: float,
               swa_rows_live: float, experts_hit=None) -> float:
    """Bytes one pass over `rows` real tokens cannot avoid moving: every
    weight outside the routed experts once (the router in float32, of
    the table only the rows' own lines), the held experts that received
    a row (`experts_hit`, summed over layers; where the span lacks the
    count, the experts an even router would hit: each of a layer's held
    ones with probability 1 - (1 - k / total)^rows), the full layers'
    live pages and the window layers' LIVE ring rows.  Activations,
    logits and the step's own writes are left out: the floor stays a
    floor."""
    d, c = dims(cfg), parameter_counts(dims(cfg))
    b = jnp.dtype(cfg["precision"]).itemsize
    if experts_hit is None:
        experts_hit = c["held_experts"] * (
            1.0 - (1.0 - d.k / d.total) ** max(rows, 0.0))
    return (b * (c["attention"] + c["dense_mlp"] + c["norms"] + c["shared"]
                 + c["head"] + rows * d.e + experts_hit * c["one_expert"])
            + 4 * c["router"] + paged_read_bytes(cfg, kv_blocks_live)
            + swa_read_bytes(cfg, swa_rows_live))


def pass_flops(cfg, tokens: float, sampled: float, full_keys: float,
               swa_rows_live: float) -> float:
    """Operations of one pass over `tokens` real tokens, `sampled` of
    which the head multiplies: the projections, the router, the experts
    a token is routed to AND finds here (`k held / total` of them, an
    even router), the shared expert and the dense MLP a token; both
    attentions' two products a visible key: `full_keys` keys a full
    layer's queries see, summed over the queries, and the window
    layers' `swa_rows_live` ring rows (summed over the layers; each row
    counted for ONE of the queries that see it: from below)."""
    d, c = dims(cfg), parameter_counts(dims(cfg))
    sparse = c["held_experts"] // max(d.held, 1)
    per_token = 2.0 * (
        c["attention"] + c["dense_mlp"] + c["router"] + c["shared"]
        + sparse * d.k * d.held / d.total * c["one_expert"])
    per_key = 4.0 * d.hd  # a score and a value product, a head
    full_heads = sum(h for i, h in enumerate(d.heads) if d.is_full(i))
    window_heads = (sum(d.heads) - full_heads) / max(d.window_layers, 1)
    return (tokens * per_token + 2.0 * sampled * c["head"]
            + per_key * (full_keys * full_heads
                         + swa_rows_live * window_heads))


def dispatch_least_s(cfg, peak, program: str, args: dict):
    """The least seconds the chip could take for ONE dispatch of
    `program` ("decode" or "prefill") whose span carries `args`
    (`readers/serve.mfu_share.py`): the larger of its operations over
    the bf16 peak and its bytes over the bandwidth, both over REAL
    tokens and LIVE state only.  The keys a full layer's queries see
    are counted from `kv_blocks_live` from below: a row's last page may
    hold one token, and of a chunk's queries only each row's first is
    counted, against the pages before the chunk (the spans say how many
    pages a row reads, not which query reads which).  None where the
    span lacks the counts."""
    if "swa_rows_live" not in args or "kv_blocks_live" not in args:
        return None
    page = cfg["deployment"]["kv_page_size"]
    if program == "decode":
        rows = tokens = sampled = args["rows"] + args.get("feeding", 0)
        own = 1  # a row's last page
    else:
        rows, tokens = args["rows"], args["tokens"]
        sampled = args.get("decode_rows", 0)
        own = 1 + -(-dims(cfg).chunk // page)  # and the chunk's pages
    full_keys = page * max(0, args["kv_blocks_live"] - own * rows)
    return max(
        pass_flops(cfg, tokens, sampled, full_keys, args["swa_rows_live"])
        / peak["bf16_flops_per_s"],
        pass_bytes(cfg, tokens, args["kv_blocks_live"],
                   args["swa_rows_live"], args.get("moe_hit"))
        / peak["hbm_bytes_per_s"])
