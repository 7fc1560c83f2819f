"""GPT-2 decoder family behind the serving front: how to build it in the
program, its seeded weights, its plain reference.

The program side is `models.transformer.build_gpt` -> `FFModel.compile`
-> `serving.build_front`.  What that graph is, and so what the
reference computes (departures from openai/gpt-2 are listed in the
configuration file):

    x = tok_embed[ids] + pos_embed[positions]
    for each layer (pre-LN):
        x = x + MHA_causal(LN1(x))          wq wk wv wo, no bias
        x = x + W2 gelu(W1 LN2(x) + b1) + b2
    logits = LN_f(x) @ lm_head               untied, no bias

A serving family offers `build_server`, `make_weights` and
`position_regrets` (`drivers/serve.py` calls them).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks import check
from benchmarks import reference as ref


def dims(cfg):
    e, h = cfg["n_embd"], cfg["n_head"]
    return dict(e=e, h=h, d=e // h, f=cfg["assumed"]["n_inner"],
                v=cfg["vocab_size"], L=cfg["n_layer"], p=cfg["n_positions"])


# -- the program ----------------------------------------------------------
def build_server(cfg, devices):
    """The path users have today (examples/python/native/serve_gpt.py):
    a training graph whose weights the decode twin copies.  Only sizes
    leave their defaults: slots, and the training graph's batch of 1."""
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models.transformer import build_gpt

    d, dep = dims(cfg), cfg["deployment"]
    ff = FFModel(FFConfig(batch_size=1, num_devices=1,
                          compute_dtype=cfg["precision"],
                          serving_slots=dep["serving_slots"],
                          kv_pool_blocks=dep["kv_pool_blocks"]))
    build_gpt(ff, batch_size=1, seq_length=d["p"], hidden_size=d["e"],
              num_layers=d["L"], num_heads=d["h"], intermediate_size=d["f"],
              vocab_size=d["v"])
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=list(devices))
    return ff


# -- weights, from the seed ------------------------------------------------
def weight_shapes(cfg):
    d = dims(cfg)
    e, h, hd, f, L = d["e"], d["h"], d["d"], d["f"], d["L"]
    return {
        "tok_embed": {"weight": (d["v"], e)},
        "pos_embed": {"weight": (d["p"], e)},
        "final_ln": {"gamma": (e,), "beta": (e,)},
        "lm_head": {"kernel": (e, d["v"])},
        "layers": {
            "ln1": {"gamma": (L, e), "beta": (L, e)},
            "attn": {"wq": (L, e, h, hd), "wk": (L, e, h, hd),
                     "wv": (L, e, h, hd), "wo": (L, h, hd, e)},
            "ln2": {"gamma": (L, e), "beta": (L, e)},
            "ffn1": {"kernel": (L, e, f), "bias": (L, f)},
            "ffn2": {"kernel": (L, f, e), "bias": (L, e)},
        },
    }


def make_weights(cfg, seed: int, layout: str):
    return ref.make_weights(weight_shapes(cfg), cfg["n_layer"], seed, layout)


# -- the plain reference -----------------------------------------------------
def logits_fn(w, ids, precision: str):
    """ids [s] -> logits [s, vocab]: one full causal forward."""
    q = ref.rounder(precision)

    def block(x, lw):
        a = ref.layer_norm(x, lw["ln1"]["gamma"], lw["ln1"]["beta"])
        x = x + ref.attention(a, lw["attn"], q, causal=True)
        h = ref.layer_norm(x, lw["ln2"]["gamma"], lw["ln2"]["beta"])
        return x + ref.ffn(h, lw["ffn1"], lw["ffn2"], q), None

    s = ids.shape[0]
    x = (jnp.take(w["tok_embed"]["weight"], ids, axis=0)
         + w["pos_embed"]["weight"][:s])[None]
    x, _ = jax.lax.scan(block, x, w["layers"])
    x = ref.layer_norm(x[0], w["final_ln"]["gamma"], w["final_ln"]["beta"])
    return jnp.matmul(q(x), q(w["lm_head"]["kernel"]))


@functools.partial(jax.jit, static_argnames=("chooser",))
def position_regrets(w, ids, chooser=None):
    """ids [s] (a served sequence, right-padded) -> regret [s - 1] of the
    token at position p + 1 under the float32 reference's logits at p.
    With ``chooser`` (a lower precision) the tokens judged are not the
    served ones but the ones the reference at that precision would
    pick, teacher-forced on the same context: the control."""
    with jax.default_matmul_precision("highest"):
        want = logits_fn(w, ids, "float32")[:-1]
        chosen = (ids[1:] if chooser is None else
                  jnp.argmax(logits_fn(w, ids, chooser)[:-1], axis=-1))
    return check.position_regret(want, chosen)
