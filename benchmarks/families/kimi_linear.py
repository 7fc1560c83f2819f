"""Kimi-Linear decoder family (`model_type: kimi_linear`), trained: how
to build it in the program, its seeded weights, its plain reference
with the gradient, and the operations a step needs.

The program side is `flexflow_tpu.models.kimi_linear.build_kimi_linear`.
What the reference computes (e = `hidden_size`, eps = `rms_norm_eps`,
RMS(x; g) = x / sqrt(mean(x^2) + eps) * g; every departure from the
published model is in the configuration file under ``departures``):

    x = E[ids]                                          E [vocab slice, e]
    layer i:  u  = x + Mixer_i(RMS(x; g_mixer))         KDA or MLA, from
                                                        `linear_attn_config`
              x' = u + FF_i(RMS(u; g_ffn))              dense if i < `first_k_dense_replace`
    logits = RMS(x_L; g_out) W_head                     untied
    loss = mean over b, s of -log_softmax(logits)[next id]

    KDA (H heads of d = `linear_attn_config.head_dim`, K taps):
        q~, k~, v = silu(conv_K(a W_q)), silu(conv_K(a W_k)), silu(conv_K(a W_v))
            (causal, depthwise, zeros before the sequence)
        q = q~_h / sqrt(sum q~_h^2 + 1e-6) / sqrt(d);  k likewise, without / sqrt(d)
        g = -exp(A_log_h) softplus(((a W_fa) W_fb + dt_bias)_h)      [d] a head
        beta = sigmoid(a w_b)_h
        S_0 = 0;  A POSITION AT A TIME:  S' = Diag(exp(g_t)) S_{t-1}
            d_t = beta_t (v_t - S'^T k_t);  S_t = S' + k_t d_t^T;  o_t = S_t^T q_t
        out = concat_h(w_n RMS(o_h; 1) sigmoid(((a W_ga) W_gb)_h)) W_o
    MLA (no query bottleneck, no positions):
        q_h = (a W_q)_h  [nope + rope];  [c | k_r] = a W_kva;  c <- RMS(c; g_kv)
        [k_nope | v]_h = c W_kvb;  k_h = [k_nope_h | k_r]
        causal softmax(q_h . k_h (nope + rope)^-1/2) v_h;  ctx W_o
    dense FF:   W_2 (silu(a W_1) * (a W_3))
    routed FF:  s = sigmoid(a W_r) in float32 over ALL the router's experts;
        chosen = top-k of (s + bias);  w = s[chosen] / (sum s[chosen] + 1e-20)
        * `routed_scaling_factor`;  out = sum over chosen AND HELD x of
        w_x W2_x (silu(a W1_x) * (a W3_x))  +  the shared expert of a

The recurrence is a `lax.scan` of one position, never chunked: it has to
be independent of the program's chunked rule.  For the gradient at 8,192
positions the scan is checkpointed in blocks of `POSITION_BLOCK`
positions, which changes its memory (a state a block and the states of
one block, not 8,192 states) and not its mathematics.  The attention's
scores are made `QUERY_BLOCK` queries at a time against every key; EVERY
held expert is applied to every row and weighted by the routing.

Memory, the layouts, the bias that only chooses (`with_even_bias`) and
the one scanned, checkpointed sum over the batch's sequences are
`families/lfm2_moe.py`'s: see there.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import reference as ref

# gradient groups the comparison reports = top-level keys of the
# reference layout (the choosing bias sits under a key of its own)
GROUPS = {g: (g,) for g in ("embedding", "head", "kda", "mla", "dense_mlp",
                            "router", "experts", "shared_expert", "norm")}
ROUTER_EPS = 1e-20  # `RoutedExpertsParams.norm_eps` as build_kimi_linear leaves it
L2_EPS = 1e-6
QUERY_BLOCK = 128  # queries whose [heads, block, s] scores exist at once
POSITION_BLOCK = 128  # positions of the recurrence a checkpoint holds
# `with_even_bias`: as families/lfm2_moe.py
CALIBRATION_SEQUENCES, EVEN_BIAS_STEPS, EVEN_BIAS_RATE = 4, 96, 0.2


def dims(cfg):
    from flexflow_tpu.models.kimi_linear import layer_kinds

    lin = cfg["linear_attn_config"]
    if not cfg["mla_use_nope"] or cfg.get("q_lora_rank") \
            or cfg.get("rope_scaling"):
        raise ValueError("the kimi_linear reference is written for the "
                         "published block: no positions, no query "
                         "bottleneck, no rope scaling")
    return dict(
        e=cfg["hidden_size"], L=cfg["num_hidden_layers"],
        kinds=tuple(layer_kinds(lin, cfg["num_hidden_layers"])),
        kh=lin["num_heads"], kd=lin["head_dim"],
        taps=lin["short_conv_kernel_size"],
        heads=cfg["num_attention_heads"], rk=cfg["kv_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"],
        f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
        fs=cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        dense=cfg["first_k_dense_replace"],
        held=cfg["num_experts"], total=cfg["n_routed_experts_total"],
        first=cfg["first_held_expert"], k=cfg["num_experts_per_token"],
        scale=float(cfg["routed_scaling_factor"]), v=cfg["vocab_size"],
        eps=float(cfg["rms_norm_eps"]))


# -- the program ----------------------------------------------------------
def build_model(cfg, batch: int, seq: int, num_devices: int):
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.kimi_linear import build_kimi_linear

    ff = FFModel(FFConfig(batch_size=batch, num_devices=num_devices,
                          compute_dtype=cfg["precision"],
                          remat=bool(cfg["assumed"].get("remat", False))))
    build_kimi_linear(
        ff, batch_size=batch, seq_length=seq,
        n_routed_experts_total=cfg["n_routed_experts_total"],
        first_held_expert=cfg["first_held_expert"],
        **{k: cfg[k] for k in (
            "hidden_size", "num_hidden_layers", "linear_attn_config",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "mla_use_nope", "rope_theta", "rope_scaling",
            "intermediate_size", "moe_intermediate_size",
            "first_k_dense_replace", "num_experts", "num_shared_experts",
            "num_experts_per_token", "num_expert_group", "topk_group",
            "moe_renormalize", "moe_router_activation_func",
            "routed_scaling_factor", "vocab_size", "model_max_length",
            "rms_norm_eps")})
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
def mixer_shapes(d, kind: str) -> dict:
    e = d["e"]
    if kind == "kda":
        h, hd = d["kh"], d["kd"]
        c = h * hd
        return {"q_proj": (e, c), "k_proj": (e, c), "v_proj": (e, c),
                "q_conv": (c, d["taps"]), "k_conv": (c, d["taps"]),
                "v_conv": (c, d["taps"]),
                "f_a_proj": (e, hd), "f_b_proj": (hd, c), "dt_bias": (c,),
                "A_log": (h,), "b_proj": (e, h),
                "g_a_proj": (e, hd), "g_b_proj": (hd, c),
                "o_norm": (hd,), "o_proj": (c, e)}
    nh = d["heads"]
    return {"wq": (e, nh, d["dn"] + d["dr"]),
            "wkv_a": (e, d["rk"] + d["dr"]), "kv_norm": (d["rk"],),
            "wkv_b": (d["rk"], nh, d["dn"] + d["dv"]),
            "wo": (nh, d["dv"], e)}


def op_shapes(cfg) -> dict:
    """{op name in the program: {leaf: shape}}."""
    d = dims(cfg)
    e = d["e"]
    ops = {"tok_embed": {"weight": (d["v"], e)},
           "final_norm": {"gamma": (e,)},
           "lm_head": {"kernel": (e, d["v"])}}
    for i, kind in enumerate(d["kinds"]):
        ops[f"mixer_norm_{i}"] = {"gamma": (e,)}
        ops[f"ffn_norm_{i}"] = {"gamma": (e,)}
        ops[f"{kind}_{i}"] = mixer_shapes(d, kind)
        if i < d["dense"]:
            ops[f"mlp_{i}"] = {"w_gate": (e, d["f"]), "w_up": (e, d["f"]),
                               "w_down": (d["f"], e)}
        else:
            n, fe, fs = d["held"], d["fe"], d["fs"]
            ops[f"moe_{i}"] = {
                "router": (e, d["total"]), "router_bias": (d["total"],),
                "w_gate": (n, e, fe), "w_up": (n, e, fe),
                "w_down": (n, fe, e), "shared_gate": (e, fs),
                "shared_up": (e, fs), "shared_down": (fs, e)}
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
        if leaf.startswith("shared_"):
            return "shared_expert"
        return {"router": "router", "router_bias": "choosing_bias"}.get(
            leaf, "experts")
    return {"kda": "kda", "mla": "mla", "mlp": "dense_mlp"}[kind]


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


def decay_leaves(key, h: int, c: int) -> dict:
    """`A_log` and `dt_bias` as the delta-rule families are started (the
    config carries no initial values; `assumed`): A uniform in [1, 16],
    a step dt log-uniform in [0.001, 0.1] and `dt_bias` its inverse
    softplus, so that a channel forgets over tens to thousands of
    positions and not, as N(0, 0.02) would have it, over two."""
    ka, kt = jax.random.split(key)
    dt = jnp.exp(jax.random.uniform(kt, (c,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return {"A_log": jnp.log(jax.random.uniform(ka, (h,), jnp.float32,
                                                1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt))}


@functools.partial(jax.jit, static_argnames=("cfg_key",))
def _seeded_weights(key, cfg_key: str):
    """`make_weights`' program layout; jitted once a configuration, so
    the second layout of a run does not compile the balancing rule
    again."""
    cfg = json.loads(cfg_key)
    d = dims(cfg)
    w = ref.normal_tree(key, op_shapes(cfg))
    for i, (op, leaves) in enumerate(sorted(w.items())):
        for name in ("o_norm", "kv_norm"):
            if name in leaves:
                leaves[name] = leaves[name] + 1.0
        if "A_log" in leaves:
            leaves.update(decay_leaves(
                jax.random.fold_in(key, 2 ** 21 + i), d["kh"],
                d["kh"] * d["kd"]))
    return with_even_bias(w, cfg, jax.random.fold_in(key, 2 ** 20))


def make_weights(cfg, seed: int, layout: str):
    """The seed's weights: normal, std 0.02, norm gains 1 + N(0, 0.02)
    (`reference.normal_tree` sees leaves called ``gamma``; the head norm
    of a KDA layer and the latent's norm get their 1 here), the decays'
    `A_log` and `dt_bias` by `decay_leaves`, the choosing bias N(0,
    0.02) and then moved until every expert is chosen equally often
    (`with_even_bias`).  ``"program"``: the per-op tree on the device;
    ``"reference"``: the same numbers regrouped by gradient group and
    parked on the HOST."""
    w = _seeded_weights(ref.seed_key(seed), json.dumps(cfg, sort_keys=True))
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
    """w with every routed layer's choosing bias moved until the router
    sends each of its experts the same number of pairs, on
    `CALIBRATION_SEQUENCES` seeded sequences of uniform ids: what
    `families/lfm2_moe.with_even_bias` does and for the reason measured
    there (random weights load the held experts unevenly from seed to
    seed, and the step's time follows the routed rows).  Layer by layer
    on the way forward, operands rounded to bf16, as the program
    computes."""
    d, q = dims(cfg), ref.rounder("bfloat16")
    seq = min(cfg["model_max_length"], 4096)
    ids = jax.random.randint(key, (CALIBRATION_SEQUENCES, seq), 0, d["v"])
    xs = jnp.take(w["tok_embed"]["weight"], ids, axis=0)  # [n, s, e]
    w = {op: dict(leaves) for op, leaves in w.items()}
    even = CALIBRATION_SEQUENCES * seq * d["k"] / d["total"]

    def nudge(i, bias, scores):
        _, chosen = jax.lax.top_k(scores + bias, d["k"])
        load = jnp.sum(jax.nn.one_hot(chosen, d["total"]), axis=(0, 1))
        # a step in units of the scores' own spread, shrinking
        return bias - EVEN_BIAS_RATE / (1.0 + i / 8.0) * jnp.std(scores) * (
            load / even - 1.0)

    each = jax.vmap  # every function below takes one sequence [s, e]
    for i, kind in enumerate(d["kinds"]):
        mixer = functools.partial(kda if kind == "kda" else mla,
                                  w=w[f"{kind}_{i}"], d=d, q=q)
        us = xs + each(mixer)(rms(xs, w[f"mixer_norm_{i}"]["gamma"], d["eps"]))
        normed = rms(us, w[f"ffn_norm_{i}"]["gamma"], d["eps"])
        if i < d["dense"]:
            ff = w[f"mlp_{i}"]
            xs = us + gated(normed, ff["w_gate"], ff["w_up"], ff["w_down"], q)
            continue
        ff = w[f"moe_{i}"]
        scores = jax.nn.sigmoid(jnp.matmul(normed.reshape(-1, d["e"]),
                                           ff["router"]))
        ff["router_bias"] = jax.lax.fori_loop(
            0, EVEN_BIAS_STEPS, functools.partial(nudge, scores=scores),
            ff["router_bias"])
        xs = us + each(lambda a: routed(a, ff, d, q))(normed)
    return w


# -- the plain reference -----------------------------------------------------
def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * gain


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + L2_EPS)


def causal_conv(x, taps):
    """x [s, c], taps [c, K] -> silu of the causal depthwise conv."""
    s, k = x.shape[0], taps.shape[1]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return jax.nn.silu(sum(taps[:, j] * padded[j:j + s] for j in range(k)))


def delta_rule(qh, kh, vh, g, beta):
    """The recurrence a position at a time from S = 0: qh, kh, g [s, h,
    dk], vh [s, h, dv], beta [s, h] -> o [s, h, dv]; the scan is
    checkpointed in blocks of positions (module docstring)."""
    s, h, dk = qh.shape

    def position(S, x):
        qt, kt, vt, gt, bt = x
        S = S * jnp.exp(gt)[..., None]
        delta = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt))
        S = S + kt[..., None] * delta[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(position, S, xs)

    size = math.gcd(s, POSITION_BLOCK)
    xs = tuple(t.reshape((s // size, size) + t.shape[1:])
               for t in (qh, kh, vh, g, beta))
    _, o = jax.lax.scan(block, jnp.zeros((h, dk, vh.shape[-1]), jnp.float32),
                        xs)
    return o.reshape((s,) + o.shape[2:])


def kda(a, w, d, q):
    """a [s, e] -> [s, e]."""
    s, h, hd = a.shape[0], d["kh"], d["kd"]

    def heads(name):
        return causal_conv(jnp.matmul(q(a), q(w[f"{name}_proj"])),
                           w[f"{name}_conv"]).reshape(s, h, hd)

    def low_rank(name):
        return jnp.matmul(q(jnp.matmul(q(a), q(w[f"{name}_a_proj"]))),
                          q(w[f"{name}_b_proj"]))

    qh, kh, vh = l2norm(heads("q")) * hd ** -0.5, l2norm(heads("k")), \
        heads("v")
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
        (low_rank("f") + w["dt_bias"]).reshape(s, h, hd))
    beta = jax.nn.sigmoid(jnp.matmul(q(a), q(w["b_proj"])))
    o = delta_rule(qh, kh, vh, g, beta)
    y = rms(o, w["o_norm"], d["eps"]) * jax.nn.sigmoid(
        low_rank("g").reshape(s, h, hd))
    return jnp.matmul(q(y.reshape(s, h * hd)), q(w["o_proj"]))


def mla(a, w, d, q):
    """a [s, e] -> [s, e]; the scores of `QUERY_BLOCK` queries at a
    time against every key (plain softmax over the whole row)."""
    s, dn, rk = a.shape[0], d["dn"], d["rk"]
    qh = jnp.einsum("se,ehd->shd", q(a), q(w["wq"]))
    kv = jnp.matmul(q(a), q(w["wkv_a"]))
    c = rms(kv[:, :rk], w["kv_norm"], d["eps"])
    kvh = jnp.einsum("sc,chd->shd", q(c), q(w["wkv_b"]))
    kh = jnp.concatenate(
        [kvh[..., :dn], jnp.broadcast_to(kv[:, None, rk:],
                                         (s, d["heads"], d["dr"]))], axis=-1)
    vh = kvh[..., dn:]
    block = min(QUERY_BLOCK, s)

    @jax.checkpoint
    def some_queries(args):
        qb, start = args
        scores = jnp.einsum("qhd,khd->hqk", q(qb), q(kh)) \
            * (dn + d["dr"]) ** -0.5
        keep = (start + jnp.arange(block))[:, None] >= jnp.arange(s)[None]
        probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf),
                               axis=-1)
        return jnp.einsum("hqk,khd->qhd", q(probs), q(vh))

    ctx = jax.lax.map(some_queries,
                      (qh.reshape(s // block, block, *qh.shape[1:]),
                       jnp.arange(0, s, block)))
    return jnp.einsum("shd,hde->se", q(ctx.reshape(vh.shape)), q(w["wo"]))


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


def routed_part(a, w, d, q, first=None, held=None):
    """Every held expert (axis x) over every row, weighted by the
    routing: the part of the layer's result the experts `first ..
    first + held` of the router's width give."""
    first = d["first"] if first is None else first
    held = d["held"] if held is None else held
    combine = routing(a, w["router"], w["router_bias"], d)[:, first:first + held]
    gate = jnp.einsum("se,xef->xsf", q(a), q(w["w_gate"]))
    up = jnp.einsum("se,xef->xsf", q(a), q(w["w_up"]))
    y = jnp.einsum("xsf,xfe->xse", q(jax.nn.silu(gate) * up), q(w["w_down"]))
    return jnp.einsum("xse,sx->se", y, combine)


def shared_part(a, w, q):
    return gated(a, w["shared_gate"], w["shared_up"], w["shared_down"], q)


def routed(a, w, d, q):
    return routed_part(a, w, d, q) + shared_part(a, w, q)


def forward(w, ids, cfg, precision: str = "float32"):
    """w in the PROGRAM's per-op layout, ids [s] -> (logits [s, vocab],
    [every routed layer's `chosen_experts`])."""
    d, q = dims(cfg), ref.rounder(precision)
    x = jnp.take(w["tok_embed"]["weight"], ids, axis=0)
    choices = []
    for i, kind in enumerate(d["kinds"]):
        # the mixer and the feed-forward are checkpointed apart: the
        # backward pass holds one's internals at a time
        @jax.checkpoint
        def mix(x, norm, mw, kind=kind):
            a = rms(x, norm["gamma"], d["eps"])
            return x + (kda if kind == "kda" else mla)(a, mw, d, q)

        @jax.checkpoint
        def feed(u, norm, fw, i=i):
            a = rms(u, norm["gamma"], d["eps"])
            if i < d["dense"]:
                return u + gated(a, fw["w_gate"], fw["w_up"], fw["w_down"],
                                 q), None
            return u + routed(a, fw, d, q), chosen_experts(
                a, fw["router"], fw["router_bias"], d)

        u = mix(x, w[f"mixer_norm_{i}"], w[f"{kind}_{i}"])
        x, chosen = feed(u, w[f"ffn_norm_{i}"],
                         w[f"mlp_{i}" if i < d["dense"] else f"moe_{i}"])
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
    """Forward multiply-adds a token, by part, outside the two cores
    (`kda_core_flops`, `attention_core_flops`); the routed experts by
    the pairs that land on held ones in expectation (k x held / total);
    the embedding lookup, the norms and everything elementwise count as
    zero."""
    d = dims(cfg)
    e = d["e"]
    n_kda = d["kinds"].count("kda")
    n_mla = d["L"] - n_kda
    n_moe = d["L"] - d["dense"]
    c = d["kh"] * d["kd"]
    return {
        "kda": n_kda * (4 * e * c + 2 * (e * d["kd"] + d["kd"] * c)
                        + e * d["kh"] + 3 * c * d["taps"]),
        "mla": n_mla * (e * d["heads"] * (d["dn"] + d["dr"])
                        + e * (d["rk"] + d["dr"])
                        + d["rk"] * d["heads"] * (d["dn"] + d["dv"])
                        + d["heads"] * d["dv"] * e),
        "dense_mlp": d["dense"] * 3 * e * d["f"],
        "router": n_moe * e * d["total"],
        "experts": n_moe * 3 * e * d["fe"] * d["k"] * d["held"] / d["total"],
        "shared_expert": n_moe * 3 * e * d["fs"],
        "head": e * d["v"],
    }


def kda_state_elements(cfg, batch: int, seq: int) -> float:
    """Elements of state a step's positions pass: every KDA layer's
    heads x d_k x d_v, a position."""
    d = dims(cfg)
    return (float(d["kinds"].count("kda")) * batch * seq * d["kh"]
            * d["kd"] * d["kd"])


def kda_core_flops(cfg, batch: int, seq: int) -> float:
    """What the delta rule of a step needs, from the configuration
    alone: a position of a head decays S, reads it twice (`S^T k`, `S^T
    q`) and adds an outer product, 7 operations an element of state
    (`GatedDeltaNet.flops`), and the backward pass twice that (each
    product's two gradients, the decay's): 21 an element a position.
    Never the implementation's chunk length, and no forward run twice."""
    return 21.0 * kda_state_elements(cfg, batch, seq)


def kda_core_bytes(cfg, batch: int, seq: int, itemsize: int = 2) -> float:
    """The least bytes the cores of a step move: q, k, v, g in and o
    out a position in the compute precision (beta is a number a head),
    and their gradients the other way: 2 x 5 x heads x d a position a
    layer.  The state itself can live on chip."""
    d = dims(cfg)
    return (2.0 * 5 * d["kinds"].count("kda") * batch * seq * d["kh"]
            * d["kd"] * itemsize)


def attention_core_products(cfg, batch: int, seq: int, width: int) -> float:
    """FLOPs of ONE causal [s, s] product of the given width over every
    MLA layer: 2 x b x heads x s (s + 1) / 2 x width."""
    d = dims(cfg)
    n_mla = d["L"] - d["kinds"].count("kda")
    return 2.0 * n_mla * batch * d["heads"] * seq * (seq + 1) / 2 * width


def attention_core_flops(cfg, batch: int, seq: int) -> float:
    """What the flash kernels of a step need at the TRUE widths: forward
    q k^T at nope + rope (192) and p v at v_head_dim (128); backward the
    scores again, dq and dk at 192, dp and dv at 128: 4 products of the
    key's width and 3 of the value's.  (The kernels pad q and k to 256
    lanes and each backward kernel recomputes the scores and dp: more
    is multiplied than is needed.)"""
    d = dims(cfg)
    return (4.0 * attention_core_products(cfg, batch, seq, d["dn"] + d["dr"])
            + 3.0 * attention_core_products(cfg, batch, seq, d["dv"]))


def train_flops_per_step(cfg, batch: int, seq: int) -> float:
    """3 x forward (a product, its input gradient, its weight gradient)
    for the matrix products, the two cores at what they need forward and
    backward; recomputation never counts."""
    tokens = batch * seq
    return (3.0 * 2.0 * tokens * sum(macs_per_token(cfg).values())
            + kda_core_flops(cfg, batch, seq)
            + attention_core_flops(cfg, batch, seq))
