"""`evabyte` (EvaByte 6.5B, a byte-level decoder on EVA chunked
attention) behind the serving front: how to build it in the program,
its seeded weights, its plain reference, and what its dispatches cannot
avoid moving.

The program side is `models.evabyte.build_evabyte` ->
`FFModel.compile(defer_weights=True)` -> `set_weights` ->
`serving.build_front`.  What that graph is, and so what the reference
computes, for a sequence of byte ids (no bias anywhere;
`N(v; g) = v * rsqrt(mean(v^2) + eps) * (1 + g)`, the gain stored
around zero):

    x = tok_embed[ids]
    layer:  h = x + Attn(N(x; g1));  x = h + W_down(silu(W_gate n) * W_up n),
            n = N(h; g2)
    logits = N(x; g_f) W_head      [.., num_pred_heads x vocab]: head p,
                                   columns p x vocab on, predicts the
                                   byte p + 1 positions ahead

    Attn, per head, c = chunk_size, w = window_size, s = head_dim^-0.5:
        q_i, k_i, v_i = the head's d columns of x W_q, x W_k, x W_v
        (each [e, heads x d]); rotary embedding on q and k at
        the absolute position i (first half against second half)
        chunk m = positions c m .. c m + c - 1, learned phi, mu:
            a_j = softmax_{j in chunk m}(s <k_j, phi>)
            K_m = sum_j a_j k_j + mu;  V_m = sum_j a_j v_j
        query i, W = i // w:
            own keys   {j : j // w == W, j <= i}
            summaries  {m : m < (w / c) W}
            p = softmax over both of s <q_i, k_j>, s <q_i, K_m>
            o_i = sum p_ij v_j + sum p_im V_m;  out = W_o o_i

The reference keeps no cache and shares no code with
`flexflow_tpu/ops/eva_attention.py`: it walks the sequence a WINDOW at
a time (the chunks of the windows before it are all a window needs of
the past), one jitted function for whatever length, a layer's float32
weights at a time, made from the seed where they are used.

Every leaf has a key of its own, `fold_in`ed from the seed by (kind of
op, leaf, layer); `make_weights(.., "program")` makes the program's copy
one op at a time, rounded to the stated precision AS IT IS MADE, and
`make_weights(.., "reference")` returns only the seed.

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


# -- sizes ------------------------------------------------------------------
def published(cfg) -> dict:
    """The keyword arguments of `build_evabyte`, under the published
    config's own keys."""
    keys = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "intermediate_size", "vocab_size",
            "num_pred_heads", "window_size", "chunk_size", "rope_theta",
            "rms_norm_eps", "norm_add_unit_offset")
    kw = {k: cfg[k] for k in keys}
    kw["max_position_embeddings"] = cfg["n_positions"]
    return kw


@functools.lru_cache(maxsize=8)
def _dims(frozen: str):
    return Dims(json.loads(frozen))


def dims(cfg) -> "Dims":
    kw = dict(published(cfg), init_std=cfg["init_std"],
              adaptive_init_std=cfg.get("adaptive_init_std",
                                        cfg["init_std"]))
    return _dims(json.dumps(kw, sort_keys=True))


class Dims:
    """The sizes the reference and the counting functions read, hashable
    by identity (one per configuration: `dims`)."""

    def __init__(self, kw):
        self.e = kw["hidden_size"]
        self.L = kw["num_hidden_layers"]
        self.h = kw["num_attention_heads"]
        self.hd = self.e // self.h
        self.f = kw["intermediate_size"]
        self.v = kw["vocab_size"]
        self.heads_out = kw["num_pred_heads"]
        self.w, self.c = kw["window_size"], kw["chunk_size"]
        self.theta = float(kw["rope_theta"])
        self.eps = float(kw["rms_norm_eps"])
        self.p = kw["max_position_embeddings"]
        self.std = float(kw["init_std"])
        self.phi_std = float(kw["adaptive_init_std"])
        if not kw["norm_add_unit_offset"]:
            raise ValueError("evabyte: the reference is written for "
                             "norm_add_unit_offset, as published")


# -- the program --------------------------------------------------------------
def build_server(cfg, devices):
    """A model that is only ever served: no weight drawn, none held in
    float32; `set_weights` brings them in the stated precision.  The
    family keeps no paged pool (a "page" of the host's table is one
    window, and the twin sizes the table so it never refuses a slot's
    sequence) and does not carry `prefix_cache`, which FFConfig's
    default asks for."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.evabyte import build_evabyte

    dep = cfg["deployment"]
    ff = FFModel(FFConfig(batch_size=1, num_devices=1,
                          compute_dtype=cfg["precision"],
                          serving_slots=dep["serving_slots"],
                          kv_page_size=cfg["window_size"],
                          prefill_chunk=dep["prefill_chunk"],
                          prefix_cache=False))
    build_evabyte(ff, batch_size=1, seq_length=cfg["n_positions"],
                  **published(cfg))
    ff.compile(devices=list(devices), defer_weights=True)
    return ff


# -- weights, from the seed -----------------------------------------------------
def leaf_shapes(d: Dims, kind: str) -> dict:
    """{leaf: shape} of one op of a kind, in the program's layout."""
    e = d.e
    return {
        "tok_embed": {"weight": (d.v, e)},
        "norm": {"gamma": (e,)},
        "attn": {"wq": (e, e), "wk": (e, e), "wv": (e, e), "wo": (e, e),
                 "adaptive_phi": (d.h, d.hd), "adaptive_mu_k": (d.h, d.hd)},
        "mlp": {"w_gate": (e, d.f), "w_up": (e, d.f), "w_down": (d.f, e)},
        "lm_head": {"kernel": (e, d.v * d.heads_out)},
    }[kind]


def leaf(key, d: Dims, kind: str, name: str, shape, layer=0):
    """One leaf in float32, from a key of its own: the seed's, folded
    with the kind of op and the leaf's name (a fixed hash) and the
    layer.  Normal, std `init_std`; a norm's gain likewise, around the
    zero its unit offset makes the identity; `adaptive_phi` and
    `adaptive_mu_k` as the source's initialiser draws them: N(0, 1)
    clipped to [-1, 1], times `init_std`."""
    k = jax.random.fold_in(key, zlib.crc32(f"{kind}/{name}".encode())
                           & 0x7FFFFFFF)
    draw = jax.random.normal(jax.random.fold_in(k, layer), shape,
                             jnp.float32)
    if name.startswith("adaptive_"):
        return d.phi_std * jnp.clip(draw, -1.0, 1.0)
    return d.std * draw


def make_leaves(key, d: Dims, kind: str, layer=0):
    return {name: leaf(key, d, kind, name, shape, layer)
            for name, shape in leaf_shapes(d, kind).items()}


@functools.partial(jax.jit, static_argnames=("d", "kind", "dtype"))
def make_op(key, layer, *, d: Dims, kind: str, dtype):
    """One op's weights in the program's layout and precision, each
    leaf rounded as it is made."""
    return {name: v.astype(dtype)
            for name, v in make_leaves(key, d, kind, layer).items()}


def program_ops(d: Dims):
    """[(op name, kind, layer)] of every op of the program that has
    weights, in graph order."""
    ops = [("tok_embed", "tok_embed", 0)]
    for i in range(d.L):
        ops += [(f"input_norm_{i}", "norm", 2 * i), (f"attn_{i}", "attn", i),
                (f"post_norm_{i}", "norm", 2 * i + 1), (f"mlp_{i}", "mlp", i)]
    return ops + [("final_norm", "norm", 2 * d.L), ("lm_head", "lm_head", 0)]


class ReferenceWeights:
    """What the reference is handed: the seed.  Every float32 leaf is
    made where it is used (`leaf`), a layer at a time."""

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


def rotate(x, first, d: Dims):
    """Rotary embedding on x [n, heads, hd] at positions first ..
    first + n - 1: first half of the channels against the second."""
    half = d.hd // 2
    freq = d.theta ** (-np.arange(0, d.hd, 2, dtype=np.float64) / d.hd)
    angle = ((first + jnp.arange(x.shape[0])).astype(jnp.float32)
             [:, None, None] * jnp.asarray(freq, jnp.float32))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


QUERIES_AT_ONCE = 512  # [h, 512, w + chunks] scores at a time


def pooled(k, v, phi, mu, d: Dims, q):
    """One window's keys and values [w, h, hd] -> its chunks' summaries
    (K, V) [w / c, h, hd]."""
    kc = k.reshape(-1, d.c, d.h, d.hd)
    vc = v.reshape(-1, d.c, d.h, d.hd)
    a = jax.nn.softmax(
        jnp.einsum("mjhd,hd->mjh", q(kc), q(phi)) / math.sqrt(d.hd), axis=1)
    return (jnp.einsum("mjh,mjhd->mhd", q(a), q(kc)) + mu,
            jnp.einsum("mjh,mjhd->mhd", q(a), q(vc)))


def attend(qh, kh, vh, K, V, seen, d: Dims, q):
    """One window: queries, keys, values [w, h, hd] and the store of
    summaries K, V [chunks, h, hd], of which the first `seen` belong to
    earlier windows -> [w, h, hd]: one softmax over the window's own
    keys (causal) and those summaries, a block of queries at a time."""
    w = qh.shape[0]
    block = math.gcd(w, QUERIES_AT_ONCE)
    key_at, chunk_at = jnp.arange(w), jnp.arange(K.shape[0])

    def some_queries(args):
        qb, first = args  # [block, h, hd]
        own = jnp.einsum("qhd,khd->hqk", q(qb), q(kh)) / math.sqrt(d.hd)
        past = jnp.einsum("qhd,mhd->hqm", q(qb), q(K)) / math.sqrt(d.hd)
        own = jnp.where(
            key_at[None, :] <= (first + jnp.arange(block))[:, None],
            own, -jnp.inf)
        past = jnp.where(chunk_at[None, :] < seen, past, -jnp.inf)
        probs = jax.nn.softmax(jnp.concatenate([own, past], axis=-1),
                               axis=-1)
        return (jnp.einsum("hqk,khd->qhd", q(probs[..., :w]), q(vh))
                + jnp.einsum("hqm,mhd->qhd", q(probs[..., w:]), q(V)))

    return jax.lax.map(some_queries, (
        qh.reshape(w // block, block, d.h, d.hd),
        jnp.arange(0, w, block))).reshape(w, d.h, d.hd)


@functools.partial(jax.jit, static_argnames=("d",))
def layer_weights(key, layer, *, d: Dims):
    return {"n1": leaf(key, d, "norm", "gamma", (d.e,), 2 * layer),
            "n2": leaf(key, d, "norm", "gamma", (d.e,), 2 * layer + 1),
            **make_leaves(key, d, "attn", layer),
            **make_leaves(key, d, "mlp", layer)}


@functools.partial(jax.jit, static_argnames=("d", "precision"),
                   donate_argnums=(3, 4))
def window_fn(lw, x, window, K, V, *, d: Dims, precision: str):
    """One layer over ONE window of the sequence: x [w, e], the
    window's index, and the summaries of every chunk before it in K, V
    [max chunks, h, hd] -> (x after the layer, K, V with this window's
    chunks added)."""
    q = ref.rounder(precision)
    n = rms(x, lw["n1"], d.eps)
    first = window * d.w
    heads = lambda v: v.reshape(-1, d.h, d.hd)  # noqa: E731
    qh = rotate(heads(jnp.matmul(q(n), q(lw["wq"]))), first, d)
    kh = rotate(heads(jnp.matmul(q(n), q(lw["wk"]))), first, d)
    vh = heads(jnp.matmul(q(n), q(lw["wv"])))
    seen = window * (d.w // d.c)
    o = attend(qh, kh, vh, K, V, seen, d, q)
    h = x + jnp.matmul(q(o.reshape(-1, d.e)), q(lw["wo"]))
    n = rms(h, lw["n2"], d.eps)
    y = h + jnp.matmul(q(jax.nn.silu(jnp.matmul(q(n), q(lw["w_gate"])))
                         * jnp.matmul(q(n), q(lw["w_up"]))),
                       q(lw["w_down"]))
    Kw, Vw = pooled(kh, vh, lw["adaptive_phi"], lw["adaptive_mu_k"], d, q)
    return (y, jax.lax.dynamic_update_slice_in_dim(K, Kw, seen, 0),
            jax.lax.dynamic_update_slice_in_dim(V, Vw, seen, 0))


@functools.partial(jax.jit, static_argnames=("d",))
def embed_fn(key, ids, *, d: Dims):
    return jnp.take(leaf(key, d, "tok_embed", "weight", (d.v, d.e)), ids,
                    axis=0)


@functools.partial(jax.jit, static_argnames=("d", "precision"))
def head_fn(key, x, *, d: Dims, precision: str):
    q = ref.rounder(precision)
    x = rms(x, leaf(key, d, "norm", "gamma", (d.e,), 2 * d.L), d.eps)
    return jnp.matmul(q(x), q(leaf(key, d, "lm_head", "kernel",
                                   (d.e, d.v * d.heads_out))))


def logits_fn(w: ReferenceWeights, ids, precision: str):
    """ids [s] -> logits [s, num_pred_heads x vocab]: one full forward,
    a window of the sequence and a layer at a time (the last window
    padded: nothing before a pad reads it).  The store of summaries is
    as long as the configuration's positions allow whatever s is, so
    one compiled `window_fn` serves every length."""
    d = w.d
    s = int(ids.shape[0])
    nw = -(-s // d.w)
    if nw * d.w > d.p:
        raise ValueError(f"{s} positions do not fit n_positions {d.p}")
    ids = jnp.pad(jnp.asarray(ids, jnp.int32), (0, nw * d.w - s))
    with jax.default_matmul_precision("highest"):
        xs = [embed_fn(w.key, ids[i * d.w:(i + 1) * d.w], d=d)
              for i in range(nw)]
        for layer in range(d.L):
            lw = layer_weights(w.key, layer, d=d)
            K = jnp.zeros((d.p // d.c, d.h, d.hd))
            V = jnp.zeros_like(K)
            for i in range(nw):
                xs[i], K, V = window_fn(lw, xs[i], i, K, V, d=d,
                                        precision=precision)
        out = [head_fn(w.key, x, d=d, precision=precision) for x in xs]
    return jnp.concatenate(out)[:s]


def position_regrets(w: ReferenceWeights, ids, chooser=None):
    """ids [s] (a served sequence, right-padded with zeros) -> regret
    [s - 1] of the byte at position p + 1 under the float32 reference's
    head-0 logits at p.  The forward runs over the sequence up to the
    next multiple of `window_size` past its last non-zero byte (the
    attention is causal, and positions past that read regret 0: a
    served sequence that ENDS in zero bytes across a window's end would
    lose those few positions from the statistic).  With ``chooser`` (a
    lower precision) the bytes judged are the ones the reference at
    that precision would pick, teacher-forced on the same context: the
    control."""
    d = w.d
    host = np.asarray(ids)
    used = int(np.flatnonzero(host)[-1]) + 1 if host.any() else 1
    upto = min(len(host), -(-used // d.w) * d.w)
    want = logits_fn(w, host[:upto], "float32")[:-1, :d.v]
    chosen = (jnp.asarray(host[1:upto], jnp.int32) if chooser is None else
              jnp.argmax(logits_fn(w, host[:upto], chooser)[:-1, :d.v],
                         axis=-1))
    regret = check.position_regret(want, chosen)
    return jnp.pad(regret, (0, len(host) - upto))


# -- what a dispatch has to move ------------------------------------------------
def parameter_counts(d: Dims) -> dict:
    """Parameters by where a pass finds them."""
    n = lambda kind: sum(int(np.prod(s))  # noqa: E731
                         for s in leaf_shapes(d, kind).values())
    return {"attention": d.L * n("attn"), "mlp": d.L * n("mlp"),
            "norms": (2 * d.L + 1) * d.e, "table": d.v * d.e,
            "head": d.e * d.v * d.heads_out}


def eva_row_bytes(cfg) -> int:
    """Bytes of ONE row of a layer's window or summary store, key and
    value together."""
    d = dims(cfg)
    return 2 * d.h * d.hd * jnp.dtype(cfg["precision"]).itemsize


def eva_state_bytes(cfg) -> int:
    """Bytes of every slot's windows and summary stores, all layers."""
    d = dims(cfg)
    return (d.L * cfg["deployment"]["serving_slots"]
            * (d.w + d.p // d.c + d.c) * eva_row_bytes(cfg))


def eva_read_bytes(cfg, rows: float) -> float:
    """Bytes of `rows` singleton or summary rows (as the dispatch spans
    count them: `eva_rows_window` + `eva_rows_summary`, summed over the
    layers), each read once."""
    return rows * eva_row_bytes(cfg)


def decode_pass_bytes(cfg, rows: int, eva_rows: float) -> float:
    """Bytes one seq-1 pass over `rows` slots cannot avoid moving: every
    weight once in the stated precision (of the table only the rows' own
    lines), and the singleton and summary rows visible to the rows that
    advance (`eva_rows`, summed over layers).  Activations, logits and
    the step's own writes are left out: the floor stays a floor."""
    d, c = dims(cfg), parameter_counts(dims(cfg))
    b = jnp.dtype(cfg["precision"]).itemsize
    return (b * (c["attention"] + c["mlp"] + c["norms"] + c["head"]
                 + rows * d.e) + eva_read_bytes(cfg, eva_rows))


def prefill_pass_flops(cfg, tokens: float, eva_rows: float) -> float:
    """Operations one chunked-prefill pass cannot avoid: two a weight of
    the layers' matrices a REAL token (no logits come back, so no head),
    and four a channel for every (query, visible row) pair of the EVA
    layers (`eva_rows`: the dispatch's `eva_rows_window` +
    `eva_rows_summary`, already summed over the tokens and the layers)."""
    d, c = dims(cfg), parameter_counts(dims(cfg))
    return (2.0 * tokens * (c["attention"] + c["mlp"])
            + 4.0 * d.h * d.hd * eva_rows)


def prefill_pass_bytes(cfg, tokens: float) -> float:
    """Bytes one chunked-prefill pass cannot avoid: the layers' weights
    once and the tokens' own table lines (the state it reads is left
    out: a floor)."""
    d, c = dims(cfg), parameter_counts(dims(cfg))
    b = jnp.dtype(cfg["precision"]).itemsize
    return b * (c["attention"] + c["mlp"] + c["norms"] + tokens * d.e)


def dispatch_least_s(cfg, peak, program: str, args: dict):
    """The least seconds the chip could take for ONE dispatch of
    `program` ("decode" or "prefill") whose span carries `args`
    (`readers/serve.mfu_share.py`): a decode pass by bytes, a prefill
    pass by the larger of operations and bytes.  None where the span
    lacks the counts."""
    if "eva_rows_window" not in args:
        return None
    eva = args["eva_rows_window"] + args["eva_rows_summary"]
    if program == "decode":
        return decode_pass_bytes(cfg, args["rows"] + args.get("feeding", 0),
                                 eva) / peak["hbm_bytes_per_s"]
    return max(prefill_pass_flops(cfg, args["tokens"], eva)
               / peak["bf16_flops_per_s"],
               prefill_pass_bytes(cfg, args["tokens"])
               / peak["hbm_bytes_per_s"])
