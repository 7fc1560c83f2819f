"""`granite_hybrid` (Granite-4.0-H, `model_type` granitemoehybrid, dense)
behind the serving front: how to build it in the program, its seeded
weights, its plain reference.

The program side is `models.granite_hybrid.build_granite_hybrid` ->
`FFModel.compile(defer_weights=True)` -> `set_weights` ->
`serving.build_front`.  What the model computes for a sequence of
tokens, written from the published config's keys and NOT from the
program (no bias anywhere but the conv's; `RMS(v; w) = v * rsqrt(mean(
v^2) + eps) * w`, eps `rms_norm_eps`):

    h = embedding_multiplier * E[ids]
    layer i, by layer_types[i]:
        h = h + residual_multiplier * Mixer_i(RMS(h; w_in))
        m = RMS(h; w_post);  h = h + residual_multiplier *
                                 ((silu(m W_gate) * (m W_up)) W_down)
    logits = (RMS(h; w_f) E^T) / logits_scaling        (the head is E)

    Mixer, "mamba" (H heads of P, state N, one group, conv of K taps):
        [z | xBC | dt] = a W_in         (e -> H P + (H P + 2 N) + H)
        xBC = silu(causal depthwise conv_K(xBC) + b_conv)
        [x | B | C] = xBC               (H P | N | N)
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        per head j, S_j [P, N] = 0, every position t in order:
            S_j = exp(dt_tj A_j) S_j + dt_tj x_tj (outer) B_t
            y_tj = S_j C_t + D_j x_tj
        y = RMS(y * silu(z); w_norm) over all H P channels;  y W_out
    Mixer, "attention": grouped-query heads of e / heads channels, no
        positional encoding, causal softmax(q k^T attention_multiplier) v

The reference keeps no cache and no state between calls and runs no
kernel: one full causal forward over the whole sequence, the recurrence
as the scan a position written above, attention as plain causal softmax
a block of queries at a time, every product in float32 at "highest".

The seeded weights are drawn so that the comparison can SEE the state
(`leaf`): the conv's taps as the published module draws them (uniform
in +-1/sqrt(K); at normal std 0.02 x, B and C come out near 0.02, the
state's part of y reads 1e-4 of the skip's and NO fault of the state
moves a logit), and the table at std 0.004 (at 0.02 the tied head puts
the CURRENT token's own logit, 12 |E[id]|^2 / rms(h), 5.2-5.5 logit
standard deviations up where the largest of the 100,352 others stands
4.4 up: the reference's choice is the current token whatever the
mixers do; at 0.004 it stands 1.1 up and the choice is the mixers').

The model's weights ARE the stated precision's values (as the published
checkpoint is bfloat16): `make_weights(.., "program")` draws each leaf
in float32 from the seed and rounds it once, as it is made (`A_log`,
`dt_bias` and `D` stay float32), and `make_weights(.., "reference")`
hands the reference THE SAME ARRAYS, which the forward widens to
float32 ONE LAYER at a time (families/ouro.py: 6.4 GB of float32
weights cannot sit beside a 12.4 GB server).  So the comparison sees
what the program's ARITHMETIC loses, not the rounding of the weights.
The table is ONE leaf: `tok_embed/weight` embeds and is the head.

A serving family offers `build_server`, `make_weights` and
`position_regrets` (`drivers/serve.py` calls them).
"""
from __future__ import annotations

import functools
import json
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check
from benchmarks import reference as ref

STD = 0.02
#: the table's: 12 E[id] into a head that is E again must not decide
#: the logits alone (the module docstring)
TABLE_STD = 0.004
KEYS = ("hidden_size", "num_hidden_layers", "layer_types",
        "num_attention_heads", "num_key_value_heads",
        "attention_multiplier", "embedding_multiplier",
        "residual_multiplier", "logits_scaling", "mamba_n_heads",
        "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
        "mamba_expand", "mamba_chunk_size", "mamba_conv_bias",
        "mamba_proj_bias", "shared_intermediate_size", "num_local_experts",
        "position_embedding_type", "attention_bias", "tie_word_embeddings",
        "vocab_size", "rms_norm_eps")
MAMBA, ATTENTION = "mamba", "attention"


# -- sizes ------------------------------------------------------------------
def published(cfg) -> dict:
    """The keyword arguments of `build_granite_hybrid`, under the
    published config's own keys; the position range is the
    configuration's."""
    kw = {k: cfg[k] for k in KEYS}
    kw["max_position_embeddings"] = cfg["n_positions"]
    return kw


class Dims:
    """The sizes the reference and the counting functions read
    (hashable by identity: one per configuration, `dims`)."""

    def __init__(self, kw):
        self.e = kw["hidden_size"]
        self.L = kw["num_hidden_layers"]
        self.types = tuple(kw["layer_types"])
        self.h, self.kvh = kw["num_attention_heads"], kw["num_key_value_heads"]
        self.hd = self.e // self.h
        self.scale = float(kw["attention_multiplier"])
        self.embed_mult = float(kw["embedding_multiplier"])
        self.res_mult = float(kw["residual_multiplier"])
        self.logits_div = float(kw["logits_scaling"])
        self.H, self.P = kw["mamba_n_heads"], kw["mamba_d_head"]
        self.N, self.K = kw["mamba_d_state"], kw["mamba_d_conv"]
        self.di = self.H * self.P
        self.conv_dim = self.di + 2 * self.N
        self.f = kw["shared_intermediate_size"]
        self.v = kw["vocab_size"]
        self.p = kw["max_position_embeddings"]
        self.eps = float(kw["rms_norm_eps"])
        if kw["mamba_n_groups"] != 1 or not kw["tie_word_embeddings"] \
                or kw["num_local_experts"]:
            raise ValueError("the granite_hybrid reference is written for "
                             "one group of B and C, a tied head and no "
                             "routed experts")

    @property
    def mamba_layers(self) -> int:
        return self.types.count(MAMBA)

    @property
    def attention_layers(self) -> int:
        return self.types.count(ATTENTION)


@functools.lru_cache(maxsize=8)
def _dims(frozen: str) -> Dims:
    return Dims(json.loads(frozen))


def dims(cfg) -> Dims:
    return _dims(json.dumps(published(cfg), sort_keys=True))


# -- the program --------------------------------------------------------------
def build_server(cfg, devices):
    """A model that is only ever served: no weight drawn, none held in
    float32; `set_weights` brings them in the stated precision.  The
    slots, the page, the pool and the prefill chunk are the
    configuration's `deployment`; `prefix_cache` off, because the family
    does not carry it (a page hit without the state-space state at that
    position is wrong) and FFConfig's default asks for it; every other
    option at FFConfig's default (paged_kernel auto)."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.granite_hybrid import build_granite_hybrid

    dep = cfg["deployment"]
    ff = FFModel(FFConfig(batch_size=1, num_devices=1,
                          compute_dtype=cfg["precision"],
                          serving_slots=dep["serving_slots"],
                          kv_page_size=dep["kv_page_size"],
                          kv_pool_blocks=dep["kv_pool_blocks"],
                          prefill_chunk=dep["prefill_chunk"],
                          prefix_cache=False))
    build_granite_hybrid(ff, batch_size=1, seq_length=cfg["n_positions"],
                         **published(cfg))
    ff.compile(devices=list(devices), defer_weights=True)
    return ff


# -- weights, from the seed -----------------------------------------------------
def leaf_shapes(d: Dims, kind: str) -> dict:
    """{leaf: shape} of one kind, in the program's layout.  A layer is
    the ops of one decoder layer, `<op>/<leaf>`."""
    e = d.e
    mlp = {"input_norm/gamma": (e,), "post_norm/gamma": (e,),
           "mlp/w_gate": (e, d.f), "mlp/w_up": (e, d.f),
           "mlp/w_down": (d.f, e)}
    return {
        "tok_embed": {"weight": (d.v, e)},
        MAMBA: {**mlp,
                "mamba/in_proj": (e, d.di + d.conv_dim + d.H),
                "mamba/conv1d": (d.conv_dim, d.K),
                "mamba/conv_bias": (d.conv_dim,),
                "mamba/dt_bias": (d.H,), "mamba/A_log": (d.H,),
                "mamba/D": (d.H,), "mamba/norm": (d.di,),
                "mamba/out_proj": (d.di, e)},
        ATTENTION: {**mlp,
                    "attn/wq": (e, d.h, d.hd), "attn/wk": (e, d.kvh, d.hd),
                    "attn/wv": (e, d.kvh, d.hd), "attn/wo": (d.h, d.hd, e)},
        "final_norm": {"gamma": (e,)},
    }[kind]


#: leaves the program keeps in float32 whatever the precision
FLOAT32_LEAVES = ("mamba/A_log", "mamba/dt_bias", "mamba/D")
#: gains and the skip: around their identity, 1 + N(0, STD)
ONE_CENTRED = ("mamba/norm", "mamba/D")
OUTSIDE_LAYERS = ("tok_embed", "final_norm")


def leaf(key, kind: str, name: str, shape, layer=0):
    """One leaf in float32, from a key of its own: the seed's, folded
    with the kind, the leaf's name (a fixed hash) and the layer.
    Normal, std 0.02 (the conv's bias too; the table `TABLE_STD`); a
    gain around its identity; `A_log = log U(1, 16)`, `dt_bias` the
    inverse softplus of a step drawn log-uniform in (0.001, 0.1) and
    the conv's taps uniform in +-1/sqrt(K), as the published module
    draws all three: no leaf is a constant the comparison cannot see,
    and the state's part of y is a tenth of the skip's, not 1e-4."""
    k = jax.random.fold_in(
        key, zlib.crc32(f"{kind}/{name}".encode()) & 0x7FFFFFFF)
    k = jax.random.fold_in(k, layer)
    if name == "mamba/A_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    if name == "mamba/dt_bias":
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        np.log(0.001), np.log(0.1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "mamba/conv1d":
        bound = shape[-1] ** -0.5
        return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
    std = TABLE_STD if kind == "tok_embed" else STD
    v = std * jax.random.normal(k, shape, jnp.float32)
    return v + 1.0 if name.endswith("gamma") or name in ONE_CENTRED else v


def make_leaves(key, d: Dims, kind: str, layer=0) -> dict:
    return {name: leaf(key, kind, name, shape, layer)
            for name, shape in leaf_shapes(d, kind).items()}


@functools.partial(jax.jit, static_argnames=("d", "kind", "dtype"))
def make_op(key, layer, *, d: Dims, kind: str, dtype):
    """One kind's leaves in the program's precision, each rounded as it
    is made."""
    return {name: v if name in FLOAT32_LEAVES else v.astype(dtype)
            for name, v in make_leaves(key, d, kind, layer).items()}


def op_name(op: str, i: int) -> str:
    return f"{op}_{i}"


def spread(out: dict, leaves: dict, i: int) -> None:
    """A layer's leaves (`<op>/<leaf>`) into `out` under the program's
    op names (`<op>_<i>`)."""
    for name, v in leaves.items():
        op, leaf_name = name.split("/")
        out.setdefault(op_name(op, i), {})[leaf_name] = v


class ServedWeights:
    """What the reference is handed: the program's own tree (op name ->
    leaves, in the stated precision), by reference."""

    def __init__(self, d: Dims, tree: dict):
        self.d, self.tree = d, tree

    def layer(self, i: int) -> dict:
        names = (n.split("/") for n in leaf_shapes(self.d, self.d.types[i]))
        return {f"{op}/{leaf_name}": self.tree[op_name(op, i)][leaf_name]
                for op, leaf_name in names}


#: the tree `make_weights(.., "program")` made last, by (sizes, seed):
#: the reference of the same seed reads it instead of a second copy
_MADE = {}


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
    for i, kind in enumerate(d.types):
        spread(out, make_op(key, i, d=d, kind=kind, dtype=dtype), i)
    _MADE.clear()  # one tree at a time: a sweep's last seed's is freed
    _MADE[mine] = out
    return out


# -- the plain reference --------------------------------------------------------
def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * gain


QUERIES_AT_ONCE = 256  # [h, 256, s] scores, [256, vocab] logits at a time


def attention(a, w, d: Dims, q):
    """a [s, e] (already normed) -> [s, e]: grouped-query attention, no
    positional encoding, causal softmax scaled by the published
    multiplier, a block of queries at a time."""
    s = a.shape[0]
    qh = jnp.einsum("se,ehd->shd", q(a), q(w["attn/wq"]))
    kh = jnp.einsum("se,ehd->shd", q(a), q(w["attn/wk"]))
    vh = jnp.einsum("se,ehd->shd", q(a), q(w["attn/wv"]))
    kh = jnp.repeat(kh, d.h // d.kvh, axis=1)  # every query head its copy
    vh = jnp.repeat(vh, d.h // d.kvh, axis=1)
    block = int(np.gcd(s, QUERIES_AT_ONCE))
    key_pos = jnp.arange(s)

    def some_queries(args):
        qb, start = args  # [block, h, hd]
        scores = jnp.einsum("qhd,khd->hqk", q(qb), q(kh)) * d.scale
        keep = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", q(probs), q(vh))

    ctx = jax.lax.map(some_queries, (
        qh.reshape(s // block, block, d.h, d.hd),
        jnp.arange(0, s, block))).reshape(s, d.h, d.hd)
    return jnp.einsum("shd,hde->se", q(ctx), q(w["attn/wo"]))


def mamba(a, w, d: Dims, q):
    """a [s, e] (already normed) -> [s, e]: the Mamba-2 mixer, the state
    starting at zero, one position after another."""
    s = a.shape[0]
    mixed = jnp.matmul(q(a), q(w["mamba/in_proj"]))
    z, xbc, dt = (mixed[:, :d.di], mixed[:, d.di:d.di + d.conv_dim],
                  mixed[:, d.di + d.conv_dim:])
    padded = jnp.concatenate([jnp.zeros((d.K - 1, d.conv_dim)), xbc])
    xbc = jax.nn.silu(sum(padded[i:i + s] * w["mamba/conv1d"][:, i]
                          for i in range(d.K)) + w["mamba/conv_bias"])
    x = xbc[:, :d.di].reshape(s, d.H, d.P)
    B, C = xbc[:, d.di:d.di + d.N], xbc[:, d.di + d.N:]
    dt = jax.nn.softplus(dt + w["mamba/dt_bias"])          # [s, H]
    A = -jnp.exp(w["mamba/A_log"])

    def position(S, xs):  # S [H, P, N]
        x_t, B_t, C_t, dt_t = xs
        S = (S * jnp.exp(dt_t * A)[:, None, None]
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :])
        return S, jnp.sum(S * C_t[None, None, :], axis=-1)

    _, y = jax.lax.scan(position, jnp.zeros((d.H, d.P, d.N)),
                        (x, B, C, dt))
    y = (y + w["mamba/D"][:, None] * x).reshape(s, d.di)
    y = rms(y * jax.nn.silu(z), w["mamba/norm"], d.eps)
    return jnp.matmul(q(y), q(w["mamba/out_proj"]))


def layer(x, w, d: Dims, q, kind: str):
    """One decoder layer over x [s, e]; `w` the layer's leaves, `q` the
    rounding of every matrix product's operands."""
    a = rms(x, w["input_norm/gamma"], d.eps)
    a = attention(a, w, d, q) if kind == ATTENTION else mamba(a, w, d, q)
    x = x + d.res_mult * a
    m = rms(x, w["post_norm/gamma"], d.eps)
    up = (jax.nn.silu(jnp.matmul(q(m), q(w["mlp/w_gate"])))
          * jnp.matmul(q(m), q(w["mlp/w_up"])))
    return x + d.res_mult * jnp.matmul(q(up), q(w["mlp/w_down"]))


def widened(leaves: dict) -> dict:
    return {k: v.astype(jnp.float32) for k, v in leaves.items()}


@functools.partial(jax.jit, static_argnames=("d", "precision", "kind"))
def served_layer(x, leaves, *, d: Dims, precision: str, kind: str):
    return layer(x, widened(leaves), d, ref.rounder(precision), kind)


@functools.partial(jax.jit, static_argnames=("d",))
def served_embed(ids, table, *, d: Dims):
    return d.embed_mult * jnp.take(table, ids, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("d", "precision"))
def served_head(x, gain, table, *, d: Dims, precision: str):
    """x [q, e] -> logits [q, vocab]: the final norm, the tied table
    transposed, the published division."""
    q = ref.rounder(precision)
    x = rms(x, gain.astype(jnp.float32), d.eps)
    return jnp.einsum("qe,ve->qv", q(x),
                      q(table.astype(jnp.float32))) / d.logits_div


def hidden(w: ServedWeights, ids, precision: str):
    """ids [s] -> the last layer's output [s, e], a layer at a time."""
    d = w.d
    x = served_embed(ids, w.tree["tok_embed"]["weight"], d=d)
    for i, kind in enumerate(d.types):
        x = served_layer(x, w.layer(i), d=d, precision=precision, kind=kind)
    return x


def head_blocks(w: ServedWeights, x, precision: str):
    """x [s, e] -> logits [block, vocab] for one block of queries after
    another (the whole [s, vocab] in float32 is 0.8 GB at the real
    size)."""
    block = int(np.gcd(x.shape[0], QUERIES_AT_ONCE))
    for start in range(0, x.shape[0], block):
        yield start, served_head(
            x[start:start + block], w.tree["final_norm"]["gamma"],
            w.tree["tok_embed"]["weight"], d=w.d, precision=precision)


def logits_fn(w: ServedWeights, ids, precision: str):
    """ids [s] -> logits [s, vocab]: one full causal forward (the tests'
    and a toy size's; `position_regrets` never holds them all)."""
    with jax.default_matmul_precision("highest"):
        x = hidden(w, ids, precision)
        return jnp.concatenate(
            [part for _, part in head_blocks(w, x, precision)])


def position_regrets(w: ServedWeights, ids, chooser=None):
    """ids [s] (a served sequence, right-padded) -> regret [s - 1] of the
    token at position p + 1 under the float32 reference's logits at p.
    With ``chooser`` (a lower precision) the tokens judged are the ones
    the reference at that precision would pick, teacher-forced on the
    same context: the control."""
    with jax.default_matmul_precision("highest"):
        x = hidden(w, ids, "float32")
        picks = None
        if chooser is not None:
            picks = head_blocks(w, hidden(w, ids, chooser), chooser)
        nxt = jnp.concatenate([ids[1:], ids[:1]])  # (the last: unused)
        out = []
        for start, want in head_blocks(w, x, "float32"):
            chosen = (nxt[start:start + want.shape[0]] if picks is None
                      else jnp.argmax(next(picks)[1], axis=-1))
            out.append(check.position_regret(want, chosen))
        return jnp.concatenate(out)[:-1]


# -- what a pass has to move -----------------------------------------------------
def parameter_counts(d: Dims) -> dict:
    """Parameters by where a pass finds them; the table ONCE."""
    n = lambda kind, prefix="": sum(  # noqa: E731
        int(np.prod(s)) for name, s in leaf_shapes(d, kind).items()
        if name.startswith(prefix))
    return {
        "mamba_mixers": d.mamba_layers * n(MAMBA, "mamba/"),
        "attention_mixers": d.attention_layers * n(ATTENTION, "attn/"),
        "mlps": d.L * n(MAMBA, "mlp/"),
        "norms": (2 * d.L + 1) * d.e,
        "table": d.v * d.e,
    }


def parameters(cfg) -> int:
    return sum(parameter_counts(dims(cfg)).values())


def kv_block_bytes(cfg) -> int:
    """Bytes of one block of a sequence's table: a page of keys and of
    values in every attention layer."""
    d = dims(cfg)
    return (d.attention_layers * cfg["deployment"]["kv_page_size"]
            * 2 * d.kvh * d.hd * jnp.dtype(cfg["precision"]).itemsize)


def paged_read_bytes(cfg, kv_blocks_live: float) -> float:
    """Bytes the paged reads of one pass cannot avoid: the live blocks'
    pages, every attention layer (`readers/gqa_read.hbm_share.py`)."""
    return kv_blocks_live * kv_block_bytes(cfg)


def ssm_state_bytes(cfg, rows: float) -> float:
    """Bytes of `rows` slots' state-space state, every Mamba layer:
    `[H, P, N]` float32 a layer (`readers/ssm.state_hbm_share.py`
    counts it once read and once written a live row)."""
    d = dims(cfg)
    return rows * d.mamba_layers * 4 * d.H * d.P * d.N


def rstate_row_bytes(cfg) -> int:
    """Bytes of ONE slot's recurrent state, all Mamba layers: the
    state-space state in float32 and the conv's tail in the stated
    precision."""
    d = dims(cfg)
    return int(ssm_state_bytes(cfg, 1)) + d.mamba_layers * (
        (d.K - 1) * d.conv_dim * jnp.dtype(cfg["precision"]).itemsize)


def pass_flops(cfg, tokens: float, sampled: float, keys: float) -> float:
    """Operations one pass cannot avoid: every product of every layer
    over `tokens` REAL tokens, the recurrence's five operations an
    element of the state a token, the attention layers' scores and
    values against `keys` (query, key) pairs in all, and the head over
    the `sampled` rows."""
    d, c = dims(cfg), parameter_counts(dims(cfg))
    table_free = c["mamba_mixers"] + c["attention_mixers"] + c["mlps"]
    return (2.0 * tokens * table_free
            + 5.0 * tokens * d.mamba_layers * d.H * d.P * d.N
            + 4.0 * keys * d.attention_layers * d.h * d.hd
            + 2.0 * sampled * c["table"])


def pass_bytes(cfg, tokens: float, kv_blocks_live: float,
               rstate_rows_live: float) -> float:
    """Bytes one pass cannot avoid moving: every weight once (the tied
    table once: the head reads all of it, the lookup its rows of it),
    the live pages of the k/v pool, and the state of the live rows READ
    AND WRITTEN.  Activations, logits and k/v writes are left out: the
    floor stays a floor."""
    c = parameter_counts(dims(cfg))
    b = jnp.dtype(cfg["precision"]).itemsize
    return (b * sum(c.values()) + paged_read_bytes(cfg, kv_blocks_live)
            + 2.0 * rstate_rows_live * rstate_row_bytes(cfg))


def dispatch_least_s(cfg, peak, program: str, args: dict):
    """The least seconds the chip could take for ONE dispatch of
    `program` ("decode" or "prefill") whose span carries `args`
    (`readers/serve.mfu_share.py`): the larger of its operations over
    the bf16 peak and its bytes over the bandwidth, both over REAL
    tokens and LIVE state only.  The keys an attention layer's queries
    see are counted from `kv_blocks_live` from below: a row's last page
    may hold one token, and of a chunk's queries only each row's first
    is counted, against the pages before the chunk.  None where the
    span lacks the counts."""
    if "rstate_rows_live" not in args or "kv_blocks_live" not in args:
        return None
    dep = cfg["deployment"]
    page = dep["kv_page_size"]
    if program == "decode":
        rows = tokens = sampled = args["rows"] + args.get("feeding", 0)
        own = 1  # a row's last page
    else:
        rows, tokens = args["rows"], args["tokens"]
        sampled = args.get("decode_rows", 0)
        own = 1 + -(-dep["prefill_chunk"] // page)  # and the chunk's
    keys = page * max(0, args["kv_blocks_live"] - own * rows)
    return max(
        pass_flops(cfg, tokens, sampled, keys) / peak["bf16_flops_per_s"],
        pass_bytes(cfg, tokens, args["kv_blocks_live"],
                   args["rstate_rows_live"]) / peak["hbm_bytes_per_s"])
