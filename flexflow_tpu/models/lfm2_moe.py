"""The `lfm2_moe` language model (LFM2-8B-A1B): gated short-convolution
layers with a grouped-query attention layer among them, two leading
layers with a dense gated MLP and then sigmoid-routed experts with a
choosing bias and no shared expert, RMSNorm with a plain gain
everywhere, head norms and a full rotary embedding in the attention
layers.  Text ids in, logits out.

`build_lfm2_moe` takes the keys of the published `config.json` under
their own names.  Two of them may state ONE CHIP'S SHARE of a wider
deployment, as in `build_kimi_k2`: `num_experts` is the experts HELD
here out of `n_routed_experts_total` (the router's width, unchanged),
starting at `first_held_expert`; `vocab_size` is the slice of the
vocabulary held here (ids, logits and the loss are over the slice).

    x = tok_embed[ids]
    layer i:  x = x + Op_i(RMS(x))     `layer_types[i]`: "conv" a gated
                                       short convolution, "full_attention"
    i < num_dense_layers:
              x = x + GatedMLP(RMS(x))                intermediate_size
    else:     x = x + RoutedExperts(RMS(x))           moe_intermediate_size
    logits = RMS(x) lm_head

The head is untied (the family ties it to the embedding; a parameter
read by two ops is something the graph cannot say yet).  The graph is a
trainer's: the model records no decoder recipe, and asking the serving
tier for a twin of it is a `ConfigError` by name (the short
convolution's per-slot tail is not built).
"""
from __future__ import annotations

from typing import Optional, Sequence

from ..model import FFModel
from ..ops.routed_experts import RoutedExpertsParams

LAYER_TYPES = ("conv", "full_attention")


def build_lfm2_moe(
    ff: FFModel,
    batch_size: int = 1,
    seq_length: int = 1,
    *,
    hidden_size: int = 2048,
    num_hidden_layers: int = 24,
    layer_types: Optional[Sequence[str]] = None,
    num_attention_heads: int = 32,
    num_key_value_heads: int = 8,
    head_dim: Optional[int] = None,
    conv_L_cache: int = 3,
    conv_bias: bool = False,
    intermediate_size: int = 7168,
    moe_intermediate_size: int = 1792,
    num_dense_layers: int = 2,
    num_experts: int = 32,
    n_routed_experts_total: Optional[int] = None,
    first_held_expert: int = 0,
    num_experts_per_tok: int = 4,
    norm_topk_prob: bool = True,
    use_expert_bias: bool = True,
    routed_scaling_factor: float = 1.0,
    vocab_size: int = 65536,
    max_position_embeddings: int = 128000,
    norm_eps: float = 1e-5,
    rope_theta: float = 1000000.0,
):
    from ..config import ConfigError

    layer_types = list(layer_types if layer_types is not None
                       else ["conv"] * num_hidden_layers)
    if len(layer_types) != num_hidden_layers \
            or set(layer_types) - set(LAYER_TYPES):
        raise ConfigError(
            f"lfm2_moe: layer_types must name {num_hidden_layers} layers "
            f"out of {LAYER_TYPES}, got {layer_types}")
    if conv_bias or not use_expert_bias:
        raise ConfigError(
            "lfm2_moe: conv_bias and a router without its choosing bias "
            "are not built (the published config sets neither)")
    if seq_length > max_position_embeddings:
        raise ConfigError(
            f"lfm2_moe: seq_length {seq_length} passes "
            f"max_position_embeddings {max_position_embeddings}")
    head_dim = head_dim or hidden_size // num_attention_heads
    attention = dict(
        kdim=num_attention_heads * head_dim,
        vdim=num_attention_heads * head_dim, causal=True,
        num_kv_heads=num_key_value_heads, qk_norm=True, norm_eps=norm_eps,
        rotary_dim=head_dim, rope_theta=float(rope_theta))
    experts = RoutedExpertsParams(
        experts_total=n_routed_experts_total or num_experts,
        experts_held=num_experts, first_held=first_held_expert,
        top_k=num_experts_per_tok, expert_hidden=moe_intermediate_size,
        routed_scaling_factor=routed_scaling_factor,
        norm_topk_prob=norm_topk_prob, scoring="sigmoid", norm_eps=1e-6)

    ids = ff.create_tensor([batch_size, seq_length], dtype="int32",
                           name="input")
    t = ff.embedding(ids, vocab_size, hidden_size, name="tok_embed")
    for i, kind in enumerate(layer_types):
        a = ff.rms_norm(t, norm_eps, name=f"operator_norm_{i}")
        if kind == "conv":
            a = ff.short_conv(a, conv_L_cache, name=f"conv_{i}")
        else:
            a = ff.multihead_attention(a, a, a, hidden_size,
                                       num_attention_heads,
                                       name=f"attn_{i}", **attention)
        t = ff.add(t, a, name=f"operator_res_{i}")
        h = ff.rms_norm(t, norm_eps, name=f"ffn_norm_{i}")
        if i < num_dense_layers:
            h = ff.gated_mlp(h, intermediate_size, name=f"mlp_{i}")
        else:
            h = ff.routed_experts(h, experts, name=f"moe_{i}")
        t = ff.add(t, h, name=f"ffn_res_{i}")
    t = ff.rms_norm(t, norm_eps, name="final_norm")
    logits = ff.dense(t, vocab_size, use_bias=False, name="lm_head")
    # read by decoding.decoder_recipe: why this model has no twin
    ff.not_served = (
        "lfm2_moe is built for training only: its short-convolution "
        "layers carry no per-slot tail (Op.slot_state_entries) yet, so "
        "no decode twin of it can be built")
    return logits
