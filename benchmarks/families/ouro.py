"""`ouro` (Ouro-2.6B, a looped language model) behind the serving front:
how to build it in the program, its seeded weights, its plain reference.

The program side is `models.ouro.build_ouro` ->
`FFModel.compile(defer_weights=True)` -> `set_weights` ->
`serving.build_front`.  What the model computes for a sequence of
tokens, written from the family's published description and NOT from
the program (no bias anywhere but the gate's;
`RMS(v; w) = v * rsqrt(mean(v^2) + eps) * w`, eps 1e-6, a gain of its
own at every place a norm stands):

    layer i, loop step t (the SAME weights for every t), x [s, hidden]:
        a = RMS(x; w_in1_i);  q, k, v = a Wq_i, a Wk_i, a Wv_i     heads of head_dim
        q, k = rope(q, k): angle pos * theta^(-2j / head_dim) on the pair
                           (channel j, channel j + head_dim / 2), all channels
        x = x + RMS( softmax(q k^T / sqrt(head_dim), causal) v  Wo_i ; w_in2_i )
        m = RMS(x; w_post1_i)
        x = x + RMS( (silu(m Wg_i) * (m Wu_i)) Wd_i ; w_post2_i )
    h = tok_embed[ids]
    for t in 0 .. total_ut_steps - 1:
        h = layers_0..L-1(h);  h = RMS(h; w_final)       (h, normed, starts step t + 1)
        g_t = sigmoid(h w_gate + b_gate)
    exit pdf:  p_t = g_t prod_{j<t} (1 - g_j) for t < T - 1,  p_{T-1} = prod_{j<T-1} (1 - g_j)
    logits = h lm_head       after the last step: the config's
                             early_exit_threshold 1.0 is reached by the
                             pdf's running sum only there

The reference keeps no cache and runs no kernel: one full causal
forward over the whole sequence, a Python loop over the loop steps and
the layers, every product in float32 at "highest".

The model's weights ARE the stated precision's values (as the family's
published checkpoint is bfloat16): `make_weights(.., "program")` draws
each leaf in float32 from the seed and rounds it once, as it is made,
and `make_weights(.., "reference")` hands the reference THE SAME
ARRAYS (no second copy on the device: the server holds what it was
given as given), which the forward widens to float32 a layer at a
time, 205 MB at once.  So the comparison sees what the program's
ARITHMETIC loses (activations, products, the cache in bfloat16), not
the rounding of the weights, which both sides share.  Why not a
float32 tree of unrounded weights: 10.7 GB cannot sit beside a 13.4 GB
server, and `drivers/serve.py` asks for the regrets one request at a
time, so making them anew from the seed would walk 4 x 48 layers of
random numbers 40 times a check (~200 s by cell 5's measured rate of
making weights).  `forward` also takes float32 weights held in a tree
(`held_weights`, the CPU tests' gradient).

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
#: the kinds of op that stand outside the repeated layers
OUTSIDE_LAYERS = ("tok_embed", "final_norm", "early_exit_gate", "lm_head")

KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "intermediate_size",
        "rms_norm_eps", "rope_theta", "total_ut_steps",
        "early_exit_threshold", "vocab_size")


# -- sizes ------------------------------------------------------------------
def published(cfg) -> dict:
    """The keyword arguments of `build_ouro`, under the published
    config's own keys; the position range is the configuration's."""
    kw = {k: cfg[k] for k in KEYS}
    kw["max_position_embeddings"] = cfg["n_positions"]
    return kw


class Dims:
    """The sizes the reference and the counting functions read
    (hashable by identity: one per configuration, `dims`)."""

    def __init__(self, kw):
        self.e = kw["hidden_size"]
        self.L = kw["num_hidden_layers"]
        self.h = kw["num_attention_heads"]
        self.hd = kw["head_dim"]
        self.f = kw["intermediate_size"]
        self.eps = float(kw["rms_norm_eps"])
        self.theta = float(kw["rope_theta"])
        self.T = kw["total_ut_steps"]
        self.v = kw["vocab_size"]
        self.p = kw["max_position_embeddings"]
        if kw["num_key_value_heads"] != self.h:
            raise ValueError("the ouro reference is written for as many "
                             "key/value heads as query heads")


@functools.lru_cache(maxsize=8)
def _dims(frozen: str) -> Dims:
    return Dims(json.loads(frozen))


def dims(cfg) -> Dims:
    return _dims(json.dumps(published(cfg), sort_keys=True))


# -- the program --------------------------------------------------------------
def build_server(cfg, devices):
    """A model that is only ever served: no weight drawn, none held in
    float32; `set_weights` brings them in the stated precision.  The
    slots, the page and the pool are the configuration's `deployment`;
    every other option at FFConfig's default (prefill_chunk 8,
    paged_kernel auto, prefix_cache on)."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.ouro import build_ouro

    dep = cfg["deployment"]
    ff = FFModel(FFConfig(batch_size=1, num_devices=1,
                          compute_dtype=cfg["precision"],
                          serving_slots=dep["serving_slots"],
                          kv_page_size=dep["kv_page_size"],
                          kv_pool_blocks=dep["kv_pool_blocks"]))
    build_ouro(ff, batch_size=1, seq_length=cfg["n_positions"],
               **published(cfg))
    ff.compile(devices=list(devices), defer_weights=True)
    return ff


# -- weights, from the seed -----------------------------------------------------
def leaf_shapes(d: Dims, kind: str) -> dict:
    """{leaf: shape} of one op of a kind, in the program's layout.  A
    `layer` is the eight ops of one decoder layer, `<op>/<leaf>`."""
    e = d.e
    return {
        "tok_embed": {"weight": (d.v, e)},
        "layer": {"input_norm/gamma": (e,),
                  "attn/wq": (e, d.h, d.hd), "attn/wk": (e, d.h, d.hd),
                  "attn/wv": (e, d.h, d.hd), "attn/wo": (d.h, d.hd, e),
                  "attn_out_norm/gamma": (e,), "post_norm/gamma": (e,),
                  "mlp/w_gate": (e, d.f), "mlp/w_up": (e, d.f),
                  "mlp/w_down": (d.f, e), "mlp_out_norm/gamma": (e,)},
        "final_norm": {"gamma": (e,)},
        "early_exit_gate": {"kernel": (e, 1), "bias": (1,)},
        "lm_head": {"kernel": (e, d.v)},
    }[kind]


def leaf(key, kind: str, name: str, shape, layer=0):
    """One leaf in float32, from a key of its own: the seed's, folded
    with the kind of op and the leaf's name (a fixed hash) and the
    layer.  Normal, std 0.02; a norm's gain around its identity,
    1 + N(0, 0.02); the gate's bias 0."""
    k = jax.random.fold_in(
        key, zlib.crc32(f"{kind}/{name}".encode()) & 0x7FFFFFFF)
    k = jax.random.fold_in(k, layer)
    if name == "bias":
        return jnp.zeros(shape, jnp.float32)
    v = STD * jax.random.normal(k, shape, jnp.float32)
    return v + 1.0 if name.endswith("gamma") else v


def make_leaves(key, d: Dims, kind: str, layer=0) -> dict:
    return {name: leaf(key, kind, name, shape, layer)
            for name, shape in leaf_shapes(d, kind).items()}


@functools.partial(jax.jit, static_argnames=("d", "kind", "dtype"))
def make_op(key, layer, *, d: Dims, kind: str, dtype):
    """One kind's leaves in the program's precision, each rounded as it
    is made."""
    return {name: v.astype(dtype)
            for name, v in make_leaves(key, d, kind, layer).items()}


class ServedWeights:
    """What the reference is handed on the chip: the program's own
    tree (op name -> leaves, in the stated precision), by reference."""

    def __init__(self, d: Dims, tree: dict):
        self.d, self.tree = d, tree

    def layer(self, i: int) -> dict:
        names = (name.split("/") for name in leaf_shapes(self.d, "layer"))
        return {f"{op}/{leaf_name}": self.tree[f"{op}_{i}"][leaf_name]
                for op, leaf_name in names}


#: the tree `make_weights(.., "program")` made last, by (sizes, seed):
#: the reference of the same seed reads it instead of a second copy
_MADE = {}


def held_weights(cfg, seed: int) -> dict:
    """The same float32 weights held in a tree (a toy size's, for the
    CPU tests: a gradient needs leaves to differentiate):
    {kind: leaves, "layers": [leaves of layer i]}."""
    d, key = dims(cfg), ref.seed_key(seed)
    out = {kind: make_leaves(key, d, kind) for kind in OUTSIDE_LAYERS}
    out["layers"] = [make_leaves(key, d, "layer", i) for i in range(d.L)]
    return out


def spread(out: dict, leaves: dict, i: int) -> None:
    """A layer's leaves (`<op>/<leaf>`) into `out` under the program's
    op names (`<op>_<i>`)."""
    for name, v in leaves.items():
        op, leaf_name = name.split("/")
        out.setdefault(f"{op}_{i}", {})[leaf_name] = v


def to_program_layout(held: dict) -> dict:
    """A `held_weights` tree under the program's op names."""
    out = {k: dict(v) for k, v in held.items() if k != "layers"}
    for i, leaves in enumerate(held["layers"]):
        spread(out, leaves, i)
    return out


def make_weights(cfg, seed: int, layout: str):
    d, key = dims(cfg), ref.seed_key(seed)
    mine = (json.dumps(published(cfg), sort_keys=True), cfg["precision"],
            int(seed))
    if layout == "reference":
        if mine not in _MADE:  # (the tests; a run makes the program's first)
            make_weights(cfg, seed, "program")
        return ServedWeights(d, _MADE[mine])
    dtype = jnp.dtype(cfg["precision"])
    out = {kind: make_op(key, 0, d=d, kind=kind, dtype=dtype)
           for kind in OUTSIDE_LAYERS}
    for i in range(d.L):
        spread(out, make_op(key, i, d=d, kind="layer", dtype=dtype), i)
    _MADE.clear()  # one tree at a time: a sweep's last seed's is freed
    _MADE[mine] = out
    return out


# -- the plain reference --------------------------------------------------------
def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * gain


def rope(x, d: Dims):
    """Rotary embedding on every channel of x [s, heads, hd], positions
    0..s-1: channel j against channel j + hd / 2."""
    half = d.hd // 2
    freq = d.theta ** (-np.arange(0, d.hd, 2, dtype=np.float64) / d.hd)
    angle = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None]
             * jnp.asarray(freq, jnp.float32))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def layer(x, w, d: Dims, q):
    """One decoder layer over x [s, e]; `w` the layer's leaves, `q` the
    rounding of every matrix product's operands."""
    s = x.shape[0]
    a = rms(x, w["input_norm/gamma"], d.eps)
    qh = rope(jnp.einsum("se,ehd->shd", q(a), q(w["attn/wq"])), d)
    kh = rope(jnp.einsum("se,ehd->shd", q(a), q(w["attn/wk"])), d)
    vh = jnp.einsum("se,ehd->shd", q(a), q(w["attn/wv"]))
    scores = jnp.einsum("qhd,khd->hqk", q(qh), q(kh)) / math.sqrt(d.hd)
    keep = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("hqk,khd->qhd", q(probs), q(vh))
    out = jnp.einsum("shd,hde->se", q(ctx), q(w["attn/wo"]))
    x = x + rms(out, w["attn_out_norm/gamma"], d.eps)
    m = rms(x, w["post_norm/gamma"], d.eps)
    up = (jax.nn.silu(jnp.matmul(q(m), q(w["mlp/w_gate"])))
          * jnp.matmul(q(m), q(w["mlp/w_up"])))
    out = jnp.matmul(q(up), q(w["mlp/w_down"]))
    return x + rms(out, w["mlp_out_norm/gamma"], d.eps)


def widened(leaves: dict) -> dict:
    return {k: v.astype(jnp.float32) for k, v in leaves.items()}


@functools.partial(jax.jit, static_argnames=("d", "precision"))
def served_layer(x, leaves, *, d: Dims, precision: str):
    return layer(x, widened(leaves), d, ref.rounder(precision))


@functools.partial(jax.jit, static_argnames=("precision",))
def served_head(x, kernel, *, precision: str):
    q = ref.rounder(precision)
    return jnp.matmul(q(x), q(kernel.astype(jnp.float32)))


def exit_pdf(gates):
    """gates [T, ...] in (0, 1) -> the exit distribution [T, ...]."""
    stay = jnp.cumprod(1.0 - gates, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([(gates * before)[:-1], before[-1:]])


def forward(w, ids, precision: str = "float32", d: Dims = None):
    """ids [s] -> (logits [s, vocab], exit pdf [T, s]): one full causal
    forward, `w` a `ServedWeights` (each layer's leaves widened when
    the loop gets there, every loop step again, one jitted call a
    layer) or a `held_weights` tree with its `d`."""
    served = isinstance(w, ServedWeights)
    d = w.d if served else d
    q = ref.rounder(precision)
    small = widened_small(w.tree) if served else w
    with jax.default_matmul_precision("highest"):
        x = jnp.take(small["tok_embed"]["weight"], ids, axis=0)
        if served:
            x = x.astype(jnp.float32)
        gates = []
        for _ in range(d.T):
            for i in range(d.L):
                x = (served_layer(x, w.layer(i), d=d, precision=precision)
                     if served else layer(x, w["layers"][i], d, q))
            x = rms(x, small["final_norm"]["gamma"], d.eps)
            gate = small["early_exit_gate"]
            gates.append(jax.nn.sigmoid(
                jnp.matmul(x, gate["kernel"])[:, 0] + gate["bias"][0]))
        logits = (served_head(x, small["lm_head"]["kernel"],
                              precision=precision)
                  if served else
                  jnp.matmul(q(x), q(w["lm_head"]["kernel"])))
        return logits, exit_pdf(jnp.stack(gates))


def widened_small(tree: dict) -> dict:
    """What stands outside the layers, float32 where it is small; the
    two tables stay as they are held (a lookup, and `served_head`)."""
    return {"tok_embed": tree["tok_embed"], "lm_head": tree["lm_head"],
            "final_norm": widened(tree["final_norm"]),
            "early_exit_gate": widened(tree["early_exit_gate"])}


def logits_fn(w, ids, precision: str):
    return forward(w, ids, precision)[0]


def position_regrets(w, ids, chooser=None):
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
    n = lambda kind: sum(int(np.prod(s))  # noqa: E731
                         for s in leaf_shapes(d, kind).values())
    return {"layers": d.L * n("layer"), "final_norm": n("final_norm"),
            "gate": n("early_exit_gate"), "table": n("tok_embed"),
            "head": n("lm_head")}


def parameters(cfg) -> int:
    return sum(parameter_counts(dims(cfg)).values())


def kv_block_bytes(cfg) -> int:
    """Bytes of one block of a sequence's table: a page of keys and of
    values in every plane, one a (loop step, layer)."""
    d = dims(cfg)
    return (d.T * d.L * cfg["deployment"]["kv_page_size"]
            * 2 * d.h * d.hd * jnp.dtype(cfg["precision"]).itemsize)


def paged_read_bytes(cfg, kv_blocks_live: float) -> float:
    """Bytes the paged reads of one decode pass cannot avoid: the live
    blocks' pages, every plane."""
    return kv_blocks_live * kv_block_bytes(cfg)


def decode_pass_bytes(cfg, rows: int, loop_steps: float,
                      kv_blocks_live: float) -> float:
    """Bytes one seq-1 pass over `rows` slots cannot avoid moving: the
    layers' weights and the final norm once a loop step, the gate and
    the head once, of the table the rows' own lines, and the live pages
    of every plane.  Activations, logits and the new keys and values
    are left out: the floor stays a floor."""
    d, c = dims(cfg), parameter_counts(dims(cfg))
    b = jnp.dtype(cfg["precision"]).itemsize
    return (b * (loop_steps * (c["layers"] + c["final_norm"])
                 + c["gate"] + c["head"] + rows * d.e)
            + paged_read_bytes(cfg, kv_blocks_live))
