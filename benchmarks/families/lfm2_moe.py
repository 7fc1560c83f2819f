"""LFM2-MoE decoder family (`model_type: lfm2_moe`), trained: how to
build it in the program, its seeded weights, its plain reference with
the gradient, and the operations a step needs.

The program side is `flexflow_tpu.models.lfm2_moe.build_lfm2_moe`.  What
the reference computes (h = `hidden_size`, eps = `norm_eps`, RMS(x; g) =
x / sqrt(mean(x^2) + eps) * g; every departure from the published model
is in the configuration file under ``departures``):

    x = E[ids]                                          E [vocab slice, h]
    layer i:  u  = x + Op_i(RMS(x; g_op))               `layer_types[i]`
              x' = u + FF_i(RMS(u; g_ffn))              dense if i < `num_dense_layers`
    logits = RMS(x_L; g_out) W_head                     untied
    loss = mean over b, s of -log_softmax(logits)[next id]

    conv:   [B | C | z] = a W_in;  y_t = C_t * sum_{j<K} w[:, j] (B z)_{t-(K-1)+j}
            (causal, depthwise, zeros before the sequence);  out = y W_out
    full_attention:  q = a W_q -> [heads, d], k, v -> [kv heads, d];  q, k =
            RMS over d (gains q_norm, k_norm), then rotate-half rotary on all
            d channels (theta `rope_theta`);  causal softmax(q k^T / sqrt(d)) v,
            each key/value head shared by heads / kv heads query heads;  ctx W_o
    dense FF:   W_2 (silu(a W_1) * (a W_3))
    routed FF:  s = sigmoid(a W_r) in float32 over ALL the router's experts;
            chosen = top-k of (s + bias);  w = s[chosen] / (sum s[chosen] + 1e-6)
            * `routed_scaling_factor`;  out = sum over chosen AND HELD e of
            w_e W2_e (silu(a W1_e) * (a W3_e))

The reference is given the chip's share the program is given: the held
experts from `first_held_expert` and the held slice of the vocabulary.
It applies EVERY held expert to every row and weights the results by the
routing (zero where an expert was not chosen): the straightforward form,
no sort, no dispatch, no kernel.  The bias that only chooses gets no
gradient and belongs to no group; `make_weights` hands both sides the
same one, moved until the experts' loads are even (`with_even_bias`).

Memory (the check runs beside a program that holds 7.2 GB): the
reference's weights and its gradient are parked on the HOST
(`make_weights(..., "reference")` and `reference_grads` return numpy
trees), the gradient is that of ONE scanned, checkpointed sum over the
batch's sequences, the attention's scores are made a block of queries at
a time, and the reference layout only regroups the program's per-op
leaves by gradient group: nothing is stacked or copied.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import reference as ref

# gradient groups the comparison reports = top-level keys of the
# reference layout (the choosing bias sits under a key of its own)
GROUPS = {g: (g,) for g in ("embedding", "head", "conv", "attention",
                            "dense_mlp", "router", "experts", "norm")}
ROUTER_EPS = 1e-6
QUERY_BLOCK = 512  # queries whose [heads, block, s] scores exist at once
# `with_even_bias`: sequences it looks at, rounds a layer, and the first
# round's step (in standard deviations of the layer's scores, a unit of
# relative load)
CALIBRATION_SEQUENCES, EVEN_BIAS_STEPS, EVEN_BIAS_RATE = 4, 96, 0.2


def dims(cfg):
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(
        e=h, heads=nh, kv=cfg["num_key_value_heads"],
        d=cfg["assumed"].get("head_dim") or h // nh,
        f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
        taps=cfg["conv_L_cache"], L=cfg["num_hidden_layers"],
        kinds=tuple(cfg["layer_types"]), dense=cfg["num_dense_layers"],
        held=cfg["num_experts"], total=cfg["n_routed_experts_total"],
        first=cfg["first_held_expert"], k=cfg["num_experts_per_tok"],
        scale=float(cfg["routed_scaling_factor"]), v=cfg["vocab_size"],
        eps=float(cfg["norm_eps"]), theta=float(cfg["rope_theta"]))


# -- the program ----------------------------------------------------------
def build_model(cfg, batch: int, seq: int, num_devices: int):
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.lfm2_moe import build_lfm2_moe

    d = dims(cfg)
    ff = FFModel(FFConfig(batch_size=batch, num_devices=num_devices,
                          compute_dtype=cfg["precision"],
                          remat=bool(cfg["assumed"].get("remat", False))))
    build_lfm2_moe(
        ff, batch_size=batch, seq_length=seq, hidden_size=d["e"],
        num_hidden_layers=d["L"], layer_types=d["kinds"],
        num_attention_heads=d["heads"], num_key_value_heads=d["kv"],
        head_dim=d["d"], conv_L_cache=d["taps"], conv_bias=cfg["conv_bias"],
        intermediate_size=d["f"], moe_intermediate_size=d["fe"],
        num_dense_layers=d["dense"], num_experts=d["held"],
        n_routed_experts_total=d["total"], first_held_expert=d["first"],
        num_experts_per_tok=d["k"], norm_topk_prob=cfg["norm_topk_prob"],
        use_expert_bias=cfg["use_expert_bias"],
        routed_scaling_factor=d["scale"], vocab_size=d["v"],
        max_position_embeddings=cfg["max_position_embeddings"],
        norm_eps=d["eps"], rope_theta=d["theta"])
    return ff


def compile_model(ff, cfg, devices):
    from flexflow_tpu import AdamOptimizer, LossType

    o = cfg["optimizer"]
    ff.compile(optimizer=AdamOptimizer(alpha=o["alpha"], beta1=o["beta1"],
                                       beta2=o["beta2"], weight_decay=0.0,
                                       epsilon=o["epsilon"]),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=(), devices=devices)


def make_batch(cfg, batch: int, seq: int, rng: np.random.Generator,
               one_label: bool = False):
    """Seeded ids, uniform over the held slice of the vocabulary, and
    as labels the ids shifted left (the id after the last is drawn with
    them).  ``one_label`` is the classifier families' and means nothing
    to a next-token loss."""
    del one_label
    tok = rng.integers(0, cfg["vocab_size"], (batch, seq + 1), dtype=np.int32)
    return {"input": np.ascontiguousarray(tok[:, :-1])}, \
        np.ascontiguousarray(tok[:, 1:])


# -- weights, from the seed ------------------------------------------------
def op_shapes(cfg) -> dict:
    """{op name in the program: {leaf: shape}}."""
    d = dims(cfg)
    e, nh, kv, hd = d["e"], d["heads"], d["kv"], d["d"]
    ops = {"tok_embed": {"weight": (d["v"], e)},
           "final_norm": {"gamma": (e,)},
           "lm_head": {"kernel": (e, d["v"])}}
    for i, kind in enumerate(d["kinds"]):
        ops[f"operator_norm_{i}"] = {"gamma": (e,)}
        ops[f"ffn_norm_{i}"] = {"gamma": (e,)}
        if kind == "conv":
            ops[f"conv_{i}"] = {"in_proj": (e, 3 * e), "conv": (e, d["taps"]),
                                "out_proj": (e, e)}
        else:
            ops[f"attn_{i}"] = {"wq": (e, nh, hd), "wk": (e, kv, hd),
                                "wv": (e, kv, hd), "wo": (nh, hd, e),
                                "q_norm": (hd,), "k_norm": (hd,)}
        if i < d["dense"]:
            ops[f"mlp_{i}"] = {"w_gate": (e, d["f"]), "w_up": (e, d["f"]),
                               "w_down": (d["f"], e)}
        else:
            n, fe = d["held"], d["fe"]
            ops[f"moe_{i}"] = {
                "router": (e, d["total"]), "router_bias": (d["total"],),
                "w_gate": (n, e, fe), "w_up": (n, e, fe),
                "w_down": (n, fe, e)}
    return ops


def parameter_count(cfg) -> int:
    return sum(int(np.prod(shape)) for leaves in op_shapes(cfg).values()
               for shape in leaves.values())


def group_of(op: str, leaf: str) -> str:
    if op == "tok_embed":
        return "embedding"
    if op == "lm_head":
        return "head"
    if "norm" in op:
        return "norm"
    kind = op.split("_")[0]
    if kind == "moe":
        return {"router": "router", "router_bias": "choosing_bias"}.get(
            leaf, "experts")
    return {"conv": "conv", "attn": "attention", "mlp": "dense_mlp"}[kind]


def to_reference_layout(per_op, cfg=None):
    """The program's per-op tree regrouped as {group: {op: {leaf}}}: the
    same leaves under other keys, nothing stacked, nothing copied."""
    out = {}
    for op, leaves in per_op.items():
        for leaf, v in leaves.items():
            out.setdefault(group_of(op, leaf), {}).setdefault(op, {})[leaf] = v
    return out


def to_program_layout(grouped):
    out = {}
    for ops in grouped.values():
        for op, leaves in ops.items():
            out.setdefault(op, {}).update(leaves)
    return out


def make_weights(cfg, seed: int, layout: str):
    """The seed's weights: normal, std 0.02, norm gains 1 + N(0, 0.02)
    (`reference.normal_tree` sees leaves called ``gamma``; the head
    norms' gains get their 1 here), the choosing bias N(0, 0.02) and
    then moved until every expert is chosen equally often
    (`with_even_bias`).  ``"program"``: the per-op tree on the
    device; ``"reference"``: the same numbers regrouped by gradient
    group and parked on the HOST (module docstring)."""
    def make(key):
        w = ref.normal_tree(key, op_shapes(cfg))
        for op, leaves in w.items():
            for name in ("q_norm", "k_norm"):
                if name in leaves:
                    leaves[name] = leaves[name] + 1.0
        return with_even_bias(w, cfg, jax.random.fold_in(key, 2 ** 20))

    w = jax.jit(make)(ref.seed_key(seed))
    if layout == "reference":
        return ReferenceWeights(to_reference_layout(jax.device_get(w)), cfg)
    return w


class ReferenceWeights(dict):
    """`make_weights(..., "reference")`: the grouped tree, on the host,
    with the configuration it was made for (`reference_grads` is handed
    nothing else)."""

    def __init__(self, tree, cfg):
        super().__init__(tree)
        self.cfg = cfg


def with_even_bias(w, cfg, key):
    """w with every routed layer's choosing bias moved until the
    router sends each of its experts the same number of pairs, on
    `CALIBRATION_SEQUENCES` seeded sequences of uniform ids.  That is
    what the bias is FOR: the published family keeps its experts' loads
    even by nudging it against each expert's load while it trains.
    Random weights without that rule load the held quarter of the
    experts 4 % more or less from seed to seed, and the step's time
    follows the routed rows (PERF.md section 6, PR 36).  Layer by layer
    on the way forward: the rule's proportional form on the layer's
    input (`EVEN_BIAS_STEPS` rounds), then on with the bias it found.
    Operands rounded to bf16, as the program computes."""
    d, q = dims(cfg), ref.rounder("bfloat16")
    seq = min(cfg["max_position_embeddings"], 4096)
    ids = jax.random.randint(key, (CALIBRATION_SEQUENCES, seq), 0, d["v"])
    xs = [jnp.take(w["tok_embed"]["weight"], row, axis=0) for row in ids]
    w = {op: dict(leaves) for op, leaves in w.items()}
    even = len(xs) * seq * d["k"] / d["total"]  # pairs an expert, even

    def nudge(i, bias, scores):
        _, chosen = jax.lax.top_k(scores + bias, d["k"])
        load = jnp.sum(jax.nn.one_hot(chosen, d["total"]), axis=(0, 1))
        # a step in units of the scores' own spread, shrinking
        return bias - EVEN_BIAS_RATE / (1.0 + i / 8.0) * jnp.std(scores) * (
            load / even - 1.0)

    for i, kind in enumerate(d["kinds"]):
        op = w[f"conv_{i}" if kind == "conv" else f"attn_{i}"]
        us, normed = [], []
        for x in xs:
            a = rms(x, w[f"operator_norm_{i}"]["gamma"], d["eps"])
            us.append(x + (short_conv(a, op, d["taps"], q) if kind == "conv"
                           else attention(a, op, d, q)))
            normed.append(rms(us[-1], w[f"ffn_norm_{i}"]["gamma"], d["eps"]))
        if i < d["dense"]:
            ff = w[f"mlp_{i}"]
            xs = [u + gated(a, ff["w_gate"], ff["w_up"], ff["w_down"], q)
                  for u, a in zip(us, normed)]
            continue
        ff = w[f"moe_{i}"]
        scores = jax.nn.sigmoid(jnp.matmul(jnp.concatenate(normed),
                                           ff["router"]))
        ff["router_bias"] = jax.lax.fori_loop(
            0, EVEN_BIAS_STEPS, functools.partial(nudge, scores=scores),
            ff["router_bias"])
        xs = [u + routed(a, ff, d, q) for u, a in zip(us, normed)]
    return w


# -- the plain reference -----------------------------------------------------
def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * gain


def short_conv(a, w, taps: int, q):
    """a [s, e] -> [s, e]."""
    e = a.shape[-1]
    bcz = jnp.matmul(q(a), q(w["in_proj"]))
    bz = bcz[:, :e] * bcz[:, 2 * e:]
    padded = jnp.concatenate([jnp.zeros((taps - 1, e), bz.dtype), bz])
    conv = sum(w["conv"][:, j] * padded[j:j + a.shape[0]]
               for j in range(taps))
    return jnp.matmul(q(bcz[:, e:2 * e] * conv), q(w["out_proj"]))


def rotary(x, theta: float):
    """x [s, heads, d]: channel i against channel i + d/2, angle
    pos * theta^(-2i/d)."""
    s, _, d = x.shape
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(a, w, d, q):
    """a [s, e] -> [s, e]; the scores of `QUERY_BLOCK` queries at a
    time against every key (plain softmax over the whole row)."""
    s = a.shape[0]
    qh = jnp.einsum("se,ehd->shd", q(a), q(w["wq"]))
    kh = jnp.einsum("se,ehd->shd", q(a), q(w["wk"]))
    vh = jnp.einsum("se,ehd->shd", q(a), q(w["wv"]))
    qh = rotary(rms(qh, w["q_norm"], d["eps"]), d["theta"])
    kh = rotary(rms(kh, w["k_norm"], d["eps"]), d["theta"])
    group = d["heads"] // d["kv"]
    kh, vh = jnp.repeat(kh, group, axis=1), jnp.repeat(vh, group, axis=1)
    block = min(QUERY_BLOCK, s)

    @jax.checkpoint
    def some_queries(args):
        qb, start = args
        scores = jnp.einsum("qhd,khd->hqk", q(qb), q(kh)) \
            / jnp.sqrt(jnp.float32(d["d"]))
        keep = (start + jnp.arange(block))[:, None] >= jnp.arange(s)[None]
        probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf),
                               axis=-1)
        return jnp.einsum("hqk,khd->qhd", q(probs), q(vh))

    ctx = jax.lax.map(some_queries,
                      (qh.reshape(s // block, block, *qh.shape[1:]),
                       jnp.arange(0, s, block)))
    return jnp.einsum("shd,hde->se", q(ctx.reshape(qh.shape)), q(w["wo"]))


def gated(a, wg, wu, wd, q):
    return jnp.matmul(q(jax.nn.silu(jnp.matmul(q(a), q(wg)))
                        * jnp.matmul(q(a), q(wu))), q(wd))


def routing(a, router, bias, d):
    """[s, total] float32: the routing weight of every expert of the
    router's width, zero where it was not chosen."""
    scores = jax.nn.sigmoid(jnp.matmul(a, router))
    _, chosen = jax.lax.top_k(scores + bias, d["k"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTER_EPS) * d["scale"]
    return jnp.sum(jax.nn.one_hot(chosen, d["total"]) * w[..., None], axis=1)


def chosen_experts(a, router, bias, d):
    """[s, k] ids of the experts a layer's input chooses, sorted."""
    scores = jax.nn.sigmoid(jnp.matmul(a, router))
    return jnp.sort(jax.lax.top_k(scores + bias, d["k"])[1], axis=-1)


def routed(a, w, d, q):
    """Every held expert (axis x) over every row, weighted by the
    routing."""
    combine = routing(a, w["router"], w["router_bias"], d)
    held = combine[:, d["first"]:d["first"] + d["held"]]
    gate = jnp.einsum("se,xef->xsf", q(a), q(w["w_gate"]))
    up = jnp.einsum("se,xef->xsf", q(a), q(w["w_up"]))
    y = jnp.einsum("xsf,xfe->xse", q(jax.nn.silu(gate) * up), q(w["w_down"]))
    return jnp.einsum("xse,sx->se", y, held)


def forward(w, ids, cfg, precision: str = "float32"):
    """w in the PROGRAM's per-op layout, ids [s] -> (logits [s, vocab],
    [every routed layer's `chosen_experts`])."""
    d, q = dims(cfg), ref.rounder(precision)
    x = jnp.take(w["tok_embed"]["weight"], ids, axis=0)
    choices = []
    for i, kind in enumerate(d["kinds"]):
        @jax.checkpoint
        def layer(x, lw, i=i, kind=kind):
            a = rms(x, lw["op_norm"]["gamma"], d["eps"])
            u = x + (short_conv(a, lw["op"], d["taps"], q) if kind == "conv"
                     else attention(a, lw["op"], d, q))
            a = rms(u, lw["ffn_norm"]["gamma"], d["eps"])
            if i < d["dense"]:
                return u + gated(a, lw["ff"]["w_gate"], lw["ff"]["w_up"],
                                 lw["ff"]["w_down"], q), None
            return u + routed(a, lw["ff"], d, q), chosen_experts(
                a, lw["ff"]["router"], lw["ff"]["router_bias"], d)

        x, chosen = layer(x, {
            "op_norm": w[f"operator_norm_{i}"], "ffn_norm": w[f"ffn_norm_{i}"],
            "op": w[f"conv_{i}" if kind == "conv" else f"attn_{i}"],
            "ff": w[f"mlp_{i}" if i < d["dense"] else f"moe_{i}"]})
        choices += [] if chosen is None else [chosen]
    x = rms(x, w["final_norm"]["gamma"], d["eps"])
    return jnp.matmul(q(x), q(w["lm_head"]["kernel"])), choices


def logits_fn(w, ids, cfg, precision: str = "float32"):
    return forward(w, ids, cfg, precision)[0]


def sequence_loss(w, ids, labels, cfg, precision: str):
    logp = jax.nn.log_softmax(logits_fn(w, ids, cfg, precision), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _grads(grouped, ids, labels, cfg_key: str, precision: str):
    cfg = json.loads(cfg_key)

    def batch_loss(grouped):
        w = to_program_layout(grouped)

        def one(total, xs):
            return total + sequence_loss(w, xs[0], xs[1], cfg, precision), None

        total, _ = jax.lax.scan(jax.checkpoint(one), jnp.float32(0.0),
                                (ids, labels))
        return total / ids.shape[0]

    with jax.default_matmul_precision("highest"):
        return jax.grad(batch_loss)(grouped)


def reference_grads(w: "ReferenceWeights", ids, labels,
                    precision: str = "float32", micro: int = 1):
    """Gradient of the batch-mean next-token loss in the reference
    layout, a sequence at a time (``micro`` is the classifier families';
    a sequence is this family's unit), as ONE gradient of a scanned,
    checkpointed sum, parked on the host."""
    del micro
    return jax.device_get(_grads(
        dict(w), jnp.asarray(ids), jnp.asarray(labels),
        json.dumps(w.cfg, sort_keys=True), precision))


# -- operations a step needs ------------------------------------------------
def macs_per_token(cfg) -> dict:
    """Forward multiply-adds a token, by part; the routed experts by
    the pairs that land on held ones in expectation (k x held / total);
    the embedding lookup, the norms and everything elementwise count as
    zero."""
    d = dims(cfg)
    e = d["e"]
    n_conv = d["kinds"].count("conv")
    n_attn = d["L"] - n_conv
    n_moe = d["L"] - d["dense"]
    return {
        "conv": n_conv * (3 * e * e + e * e + e * d["taps"]),
        "attention_proj": n_attn * 2 * e * d["d"] * (d["heads"] + d["kv"]),
        "dense_mlp": d["dense"] * 3 * e * d["f"],
        "router": n_moe * e * d["total"],
        "experts": n_moe * 3 * e * d["fe"] * d["k"] * d["held"] / d["total"],
        "head": e * d["v"],
    }


def attention_core_products(cfg, batch: int, seq: int) -> float:
    """FLOPs of ONE [s, s] product of the causal core over every
    attention layer: 2 x b x heads x s (s + 1) / 2 x d."""
    d = dims(cfg)
    n_attn = d["L"] - d["kinds"].count("conv")
    return 2.0 * n_attn * batch * d["heads"] * seq * (seq + 1) / 2 * d["d"]


def attention_core_flops(cfg, batch: int, seq: int) -> float:
    """What the flash kernels of a step need: 2 products forward
    (q k^T, p v) and 5 backward (the scores again, dp, dv, dq, dk),
    causal.  (The two backward kernels each recompute the scores and
    dp: 9 products are multiplied, 7 are needed.)"""
    return 7.0 * attention_core_products(cfg, batch, seq)


def train_flops_per_step(cfg, batch: int, seq: int) -> float:
    """3 x forward (a product, its input gradient, its weight gradient),
    the causal attention core at 2 products forward; recomputation
    never counts."""
    tokens = batch * seq
    fwd = 2.0 * tokens * sum(macs_per_token(cfg).values()) \
        + 2.0 * attention_core_products(cfg, batch, seq)
    return 3.0 * fwd
