"""The `granitemoehybrid` language model (Granite 4.0-H): Mamba-2 layers
with a grouped-query attention layer at the positions `layer_types`
names, every layer followed by a SiLU-gated MLP, no positional encoding,
a head tied to the embedding's table, and Granite's four multipliers.
Text ids in, logits out; dense (`num_local_experts` 0: the routed
variants of the family are not built).

`build_granite_hybrid` takes the keys of the published `config.json`
under their own names.

    h = embedding_multiplier * tok_embed[ids]
    layer i:  h = h + residual_multiplier * Mixer_i(RMS(h))
                  Mamba-2 (ops/mamba2.py) where layer_types[i] is
                  "mamba", else attention: grouped heads, causal, NO
                  rotary, softmax(q k^T * attention_multiplier) v
              h = h + residual_multiplier * GatedMLP(RMS(h))
    logits = (RMS(h) tok_embed^T) / logits_scaling

None of the four is folded into a weight: `embedding_multiplier` and
`logits_scaling` scale the two uses of ONE table differently, and the
others are kept beside them as ops of the graph.

A decode twin of it holds two kinds of per-sequence state: the
attention layers' keys and values in the paged pool, and the Mamba
layers' conv tail and state-space state in `[slots, ...]` arrays of
fixed size.
"""
from __future__ import annotations

from typing import Sequence

from ..decoding import DecoderRecipe
from ..model import FFModel
from ..ops.mamba2 import Mamba2Params

MAMBA, ATTENTION = "mamba", "attention"


def build_granite_hybrid(
    ff: FFModel,
    batch_size: int = 1,
    seq_length: int = 1,
    *,
    hidden_size: int = 2048,
    num_hidden_layers: int = 40,
    layer_types: Sequence[str] = (),
    num_attention_heads: int = 32,
    num_key_value_heads: int = 8,
    attention_multiplier: float = 0.015625,
    embedding_multiplier: float = 12.0,
    residual_multiplier: float = 0.22,
    logits_scaling: float = 8.0,
    mamba_n_heads: int = 64,
    mamba_d_head: int = 64,
    mamba_d_state: int = 128,
    mamba_n_groups: int = 1,
    mamba_d_conv: int = 4,
    mamba_expand: int = 2,
    mamba_chunk_size: int = 256,
    mamba_conv_bias: bool = True,
    mamba_proj_bias: bool = False,
    shared_intermediate_size: int = 8192,
    num_local_experts: int = 0,
    position_embedding_type: str = "nope",
    attention_bias: bool = False,
    tie_word_embeddings: bool = True,
    vocab_size: int = 100352,
    max_position_embeddings: int = 131072,
    rms_norm_eps: float = 1e-5,
    decode_max_seq: int = 0,
    kv_page_size: int = 0,
    kv_num_blocks: int = 0,
    kv_kernel: str = "gather",
):
    from ..config import ConfigError

    if decode_max_seq and not kv_page_size:
        raise ConfigError(
            "granitemoehybrid does not carry the dense per-slot cache "
            "(decode_max_seq without kv_page_size): its attention layers "
            "cache in the paged pool; build the twin with kv_page_size > 0")
    layer_types = list(layer_types) or [MAMBA] * num_hidden_layers
    if len(layer_types) != num_hidden_layers \
            or set(layer_types) - {MAMBA, ATTENTION}:
        raise ConfigError(
            f"granitemoehybrid: layer_types must name {num_hidden_layers} "
            f"layers, each {MAMBA!r} or {ATTENTION!r}")
    not_built = {
        "num_local_experts": num_local_experts != 0,
        "mamba_n_groups": mamba_n_groups != 1,
        "mamba_expand": mamba_expand * hidden_size
        != mamba_n_heads * mamba_d_head,
        "mamba_conv_bias": not mamba_conv_bias,
        "mamba_proj_bias": bool(mamba_proj_bias),
        "attention_bias": bool(attention_bias),
        "position_embedding_type": position_embedding_type != "nope",
        "tie_word_embeddings": not tie_word_embeddings,
    }
    if any(not_built.values()):
        raise ConfigError(
            "granitemoehybrid: built for a dense model, one group of B "
            "and C, mamba_expand x hidden_size = heads x head size, a "
            "conv with bias, projections without, no positional "
            "encoding and a tied head; not for the given "
            + ", ".join(k for k, bad in not_built.items() if bad))
    eps = rms_norm_eps
    head_dim = hidden_size // num_attention_heads
    attention = dict(
        causal=True, num_kv_heads=num_key_value_heads, rotary_dim=0,
        softmax_scale=float(attention_multiplier), paged_read_once=True)
    mamba = Mamba2Params(
        embed_dim=hidden_size, num_heads=mamba_n_heads,
        head_dim=mamba_d_head, state_dim=mamba_d_state,
        conv_kernel=mamba_d_conv, chunk_size=mamba_chunk_size, eps=eps)

    ids = ff.create_tensor([batch_size, seq_length], dtype="int32",
                           name="input")
    t = ff.embedding(ids, vocab_size, hidden_size, name="tok_embed")
    t = ff.scalar_multiply(t, float(embedding_multiplier),
                           name="embed_scale")
    for i in range(num_hidden_layers):
        a = ff.rms_norm(t, eps, name=f"input_norm_{i}")
        if layer_types[i] == ATTENTION:
            a = ff.multihead_attention(
                a, a, a, hidden_size, num_attention_heads,
                name=f"attn_{i}", decode_max_seq=decode_max_seq,
                kv_page_size=kv_page_size, kv_num_blocks=kv_num_blocks,
                kv_kernel=kv_kernel, **attention)
        else:
            a = ff.mamba2_mixer(a, mamba, name=f"mamba_{i}",
                                slot_state=decode_max_seq > 0)
        a = ff.scalar_multiply(a, float(residual_multiplier),
                               name=f"mixer_scale_{i}")
        t = ff.add(t, a, name=f"mixer_res_{i}")
        m = ff.rms_norm(t, eps, name=f"post_norm_{i}")
        m = ff.gated_mlp(m, shared_intermediate_size, name=f"mlp_{i}")
        m = ff.scalar_multiply(m, float(residual_multiplier),
                               name=f"mlp_scale_{i}")
        t = ff.add(t, m, name=f"mlp_res_{i}")
    t = ff.rms_norm(t, eps, name="final_norm")
    t = ff.tied_dense(t, "tok_embed", name="lm_head")
    logits = ff.scalar_true_divide(t, float(logits_scaling),
                                   name="logits_scale")

    # what a decode twin is built from (decoding.make_decoder): this
    # builder again, at seq 1 with paged state for the attention layers
    # and per-slot state for the Mamba layers.  `prefill_pass`: every op
    # of this graph is per-token or takes the step's length from its
    # input (the attention's one-view read, the Mamba step's one chunk
    # from each row's state).  Not `pallas_read`: the in-place kernel
    # that takes grouped heads (head-major pages) copies a page's
    # `[16, head_dim]` rows of one head, and Mosaic refuses the slice at
    # a head of 64 channels ("must be aligned to tiling (128)"); the
    # four attention layers read by the gather.  Not `prefix_cache`,
    # `speculative`, `handoff`: a page hit (or a moved page) without the
    # state-space state at that position is wrong, and no snapshot of
    # the state is kept (ROADMAP R3)
    ff.decoder_recipe = DecoderRecipe(
        family="granitemoehybrid", build=build_granite_hybrid,
        kwargs=dict(
            hidden_size=hidden_size, num_hidden_layers=num_hidden_layers,
            layer_types=tuple(layer_types),
            num_attention_heads=num_attention_heads,
            num_key_value_heads=num_key_value_heads,
            attention_multiplier=attention_multiplier,
            embedding_multiplier=embedding_multiplier,
            residual_multiplier=residual_multiplier,
            logits_scaling=logits_scaling, mamba_n_heads=mamba_n_heads,
            mamba_d_head=mamba_d_head, mamba_d_state=mamba_d_state,
            mamba_n_groups=mamba_n_groups, mamba_d_conv=mamba_d_conv,
            mamba_expand=mamba_expand, mamba_chunk_size=mamba_chunk_size,
            shared_intermediate_size=shared_intermediate_size,
            vocab_size=vocab_size,
            max_position_embeddings=max_position_embeddings,
            rms_norm_eps=rms_norm_eps),
        dims={"num_layers": num_hidden_layers, "hidden_size": hidden_size,
              "num_heads": num_attention_heads,
              "num_kv_heads": num_key_value_heads, "vocab_size": vocab_size,
              "max_seq": max_position_embeddings},
        carries=frozenset({"paged", "chunked_prefill", "prefill_pass"}),
        head=("final_norm", "lm_head", "logits_scale"))
    return logits
