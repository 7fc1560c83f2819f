"""BERT encoder family: how to build it in the program, its seeded
weights, its plain reference, and the FLOPs a step needs.

The program side is `flexflow_tpu.models.transformer.build_bert(...,
from_token_ids=True)`.  What that graph is, and so what the reference
computes (every departure from arXiv:1810.04805 is listed in the
configuration file under ``departures``):

    x = tok_embed[ids]                                  no position /
    for each layer (post-LN):                           segment table,
        x = LN(x + MHA(x))        wq wk wv wo, no bias  no embedding LN
        x = LN(x + W2 gelu(W1 x + b1) + b2)
    logits = mean_s(x) @ Wc + bc                        2-class head
    loss   = mean_b( -log_softmax(logits)[label] )

A family file is found by the configuration's ``"family"`` key; a
training family offers the functions below and nothing else is asked
of it (`drivers/train.py` calls them).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import reference as ref

# gradient groups the comparison reports, by op name in the stacked tree
GROUPS = {
    "embedding": ("tok_embed",),
    "attention": ("attn",),
    "ffn": ("ffn1", "ffn2"),
    "layernorm": ("attn_ln", "ffn_ln"),
    "head": ("classifier",),
}


def dims(cfg):
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(e=e, h=h, d=e // h, f=cfg["intermediate_size"],
                v=cfg["vocab_size"], L=cfg["num_hidden_layers"],
                c=cfg["assumed"]["num_classes"])


# -- the program ----------------------------------------------------------
def build_model(cfg, batch: int, seq: int, num_devices: int):
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.transformer import build_bert

    d = dims(cfg)
    ff = FFModel(FFConfig(batch_size=batch, num_devices=num_devices,
                          compute_dtype=cfg["precision"]))
    build_bert(ff, batch_size=batch, seq_length=seq, hidden_size=d["e"],
               num_layers=d["L"], num_heads=d["h"], intermediate_size=d["f"],
               vocab_size=d["v"], num_classes=d["c"], from_token_ids=True)
    return ff


def compile_model(ff, cfg, devices):
    from flexflow_tpu import AdamOptimizer, LossType

    o = cfg["optimizer"]
    ff.compile(optimizer=AdamOptimizer(alpha=o["alpha"], beta1=o["beta1"],
                                       beta2=o["beta2"], weight_decay=0.0,
                                       epsilon=o["epsilon"]),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=devices)


def make_batch(cfg, batch: int, seq: int, rng: np.random.Generator,
               one_label: bool = False):
    """Seeded token ids and class labels.  ``one_label`` gives every
    sequence the same (seeded) class: the batch the gradient check runs
    on.  With random weights the pooled vectors of all sequences are
    nearly equal, so the shared weights' gradient is close to
    J^T sum_i (p_i - y_i); with mixed labels that sum nearly cancels on
    some seeds and the relative error of what is left read 0.015 on
    most seeds and 0.11 on one (PR 25, chip).  Equal labels cannot
    cancel, and the program computes the same step either way."""
    ids = rng.integers(0, cfg["vocab_size"], (batch, seq), dtype=np.int32)
    classes = cfg["assumed"]["num_classes"]
    labels = (np.full((batch,), rng.integers(0, classes), np.int32)
              if one_label else
              rng.integers(0, classes, (batch,), dtype=np.int32))
    return {"input": ids}, labels


# -- weights, from the seed ------------------------------------------------
def weight_shapes(cfg):
    d = dims(cfg)
    e, h, hd, f, L = d["e"], d["h"], d["d"], d["f"], d["L"]
    return {
        "tok_embed": {"weight": (d["v"], e)},
        "classifier": {"kernel": (e, d["c"]), "bias": (d["c"],)},
        "layers": {
            "attn": {"wq": (L, e, h, hd), "wk": (L, e, h, hd),
                     "wv": (L, e, h, hd), "wo": (L, h, hd, e)},
            "attn_ln": {"gamma": (L, e), "beta": (L, e)},
            "ffn1": {"kernel": (L, e, f), "bias": (L, f)},
            "ffn2": {"kernel": (L, f, e), "bias": (L, e)},
            "ffn_ln": {"gamma": (L, e), "beta": (L, e)},
        },
    }


def make_weights(cfg, seed: int, layout: str):
    return ref.make_weights(weight_shapes(cfg), cfg["num_hidden_layers"], seed, layout)


def to_reference_layout(per_op, cfg):
    return ref.stack_layers(per_op, weight_shapes(cfg),
                            cfg["num_hidden_layers"])


# -- the plain reference -----------------------------------------------------
def logits_fn(w, ids, precision: str):
    q = ref.rounder(precision)

    def block(x, lw):
        x = ref.layer_norm(x + ref.attention(x, lw["attn"], q, causal=False),
                           lw["attn_ln"]["gamma"], lw["attn_ln"]["beta"])
        x = ref.layer_norm(x + ref.ffn(x, lw["ffn1"], lw["ffn2"], q),
                           lw["ffn_ln"]["gamma"], lw["ffn_ln"]["beta"])
        return x, None

    x = jnp.take(w["tok_embed"]["weight"], ids, axis=0)
    x, _ = jax.lax.scan(jax.checkpoint(block), x, w["layers"])
    pooled = jnp.mean(x, axis=1)
    return (jnp.matmul(q(pooled), q(w["classifier"]["kernel"]))
            + w["classifier"]["bias"])


def loss_fn(w, ids, labels, precision: str):
    logp = jax.nn.log_softmax(logits_fn(w, ids, precision), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


@functools.partial(jax.jit, static_argnames=("precision", "micro"))
def reference_grads(w, ids, labels, precision: str = "float32",
                    micro: int = 1):
    """Gradient of the batch-mean loss, taken ``micro`` sequences at a
    time and summed (the float32 activations of one 512-token sequence
    through 24 layers are about 1.9 GB)."""
    n = ids.shape[0] // micro
    ids = ids.reshape(n, micro, -1)
    labels = labels.reshape(n, micro)

    def one(acc, xs):
        g = jax.grad(loss_fn)(w, xs[0], xs[1], precision)
        return jax.tree.map(lambda a, b: a + b / n, acc, g), None

    with jax.default_matmul_precision("highest"):
        acc, _ = jax.lax.scan(one, jax.tree.map(jnp.zeros_like, w),
                              (ids, labels))
    return acc


# -- operations a step needs (the yardstick for step.roofline_share) --------
def train_flops_per_step(cfg, batch: int, seq: int) -> float:
    """Forward + backward multiply-adds x 2 that the mathematics needs:
    three matrix products per weight matrix (forward, grad-input,
    grad-weight) and the same for the two attention products; the
    embedding lookup and everything elementwise count as zero, and
    recomputation never counts."""
    d = dims(cfg)
    tokens = batch * seq
    per_token_block = 2 * (4 * d["e"] * d["e"] + 2 * d["e"] * d["f"])
    per_token_attn = 2 * 2 * seq * d["e"]  # QK^T and PV, full (no mask)
    fwd = tokens * d["L"] * (per_token_block + per_token_attn)
    fwd += 2 * batch * d["e"] * d["c"]
    return 3.0 * fwd
