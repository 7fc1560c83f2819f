"""The `qwen3_next` language model (Qwen3-Next-80B-A3B): three Gated
DeltaNet layers to one gated full-attention layer, every layer followed
by softmax-routed experts with a gated shared expert, zero-centred
RMSNorm everywhere, an untied head.  Text ids in, logits out; the
multi-token-prediction module is not built.

`build_qwen3_next` takes the keys of the published `config.json` under
their own names.  Two of them may state ONE CHIP'S SHARE of a wider
deployment (docs/SERVING.md "Serving one chip's share of an
expert-parallel layer"): `num_experts` is the experts HELD here out of
`n_routed_experts_total` (the router's width, unchanged), starting at
`first_held_expert`; `vocab_size` is the slice of the vocabulary held
here (ids and logits are over the slice).

    x = tok_embed[ids]
    layer i:  x = x + Mixer_i(RMS(x))      full attention if
                                           (i + 1) % full_attention_interval
                                           == 0, else Gated DeltaNet
              x = x + RoutedExperts(RMS(x))
    logits = RMS(x) lm_head

A decode twin of it holds two kinds of per-sequence state: the full
layers' keys and values in the paged pool (`[blocks, page, kv_heads,
head_dim]`), and the linear layers' conv tail and delta-rule matrix in
`[slots, ...]` arrays of fixed size (ops/gated_delta_net.py).
"""
from __future__ import annotations

from typing import Optional, Sequence

from ..decoding import DecoderRecipe
from ..model import FFModel
from ..ops.gated_delta_net import GatedDeltaNetParams
from ..ops.routed_experts import RoutedExpertsParams


def is_full_attention(layer: int, full_attention_interval: int) -> bool:
    return (layer + 1) % full_attention_interval == 0


def build_qwen3_next(
    ff: FFModel,
    batch_size: int = 1,
    seq_length: int = 1,
    *,
    hidden_size: int = 2048,
    num_hidden_layers: int = 48,
    full_attention_interval: int = 4,
    num_attention_heads: int = 16,
    num_key_value_heads: int = 2,
    head_dim: int = 256,
    partial_rotary_factor: float = 0.25,
    rope_theta: float = 10000000.0,
    rope_scaling: Optional[dict] = None,
    linear_num_key_heads: int = 16,
    linear_num_value_heads: int = 32,
    linear_key_head_dim: int = 128,
    linear_value_head_dim: int = 128,
    linear_conv_kernel_dim: int = 4,
    moe_intermediate_size: int = 512,
    shared_expert_intermediate_size: int = 512,
    num_experts: int = 512,
    n_routed_experts_total: Optional[int] = None,
    first_held_expert: int = 0,
    num_experts_per_tok: int = 10,
    norm_topk_prob: bool = True,
    decoder_sparse_step: int = 1,
    mlp_only_layers: Sequence[int] = (),
    vocab_size: int = 151936,
    max_position_embeddings: int = 262144,
    rms_norm_eps: float = 1e-6,
    decode_max_seq: int = 0,
    kv_page_size: int = 0,
    kv_num_blocks: int = 0,
    kv_kernel: str = "gather",
):
    from ..config import ConfigError

    if decode_max_seq and not kv_page_size:
        raise ConfigError(
            "qwen3_next does not carry the dense per-slot cache "
            "(decode_max_seq without kv_page_size): its full-attention "
            "layers cache in the paged pool; build the twin with "
            "kv_page_size > 0")
    if rope_scaling or decoder_sparse_step != 1 or list(mlp_only_layers):
        raise ConfigError(
            "qwen3_next: rope_scaling, decoder_sparse_step != 1 and "
            "mlp_only_layers are not built (the published config sets "
            "none of them)")
    total = n_routed_experts_total or num_experts
    eps = rms_norm_eps
    attention = dict(
        kdim=num_attention_heads * head_dim,
        vdim=num_attention_heads * head_dim, causal=True,
        num_kv_heads=num_key_value_heads, qk_norm=True, norm_eps=eps,
        norm_zero_centered=True,
        rotary_dim=int(head_dim * partial_rotary_factor),
        rope_theta=float(rope_theta), output_gate=True,
        paged_read_once=True)
    delta = GatedDeltaNetParams(
        embed_dim=hidden_size, num_k_heads=linear_num_key_heads,
        num_v_heads=linear_num_value_heads,
        head_k_dim=linear_key_head_dim, head_v_dim=linear_value_head_dim,
        conv_kernel=linear_conv_kernel_dim, eps=eps)
    experts = RoutedExpertsParams(
        experts_total=total, experts_held=num_experts,
        first_held=first_held_expert, top_k=num_experts_per_tok,
        expert_hidden=moe_intermediate_size,
        shared_hidden=shared_expert_intermediate_size,
        norm_topk_prob=norm_topk_prob, scoring="softmax",
        shared_expert_gate=True)

    ids = ff.create_tensor([batch_size, seq_length], dtype="int32",
                           name="input")
    t = ff.embedding(ids, vocab_size, hidden_size, name="tok_embed")
    for i in range(num_hidden_layers):
        a = ff.rms_norm(t, eps, name=f"input_norm_{i}", zero_centered=True)
        if is_full_attention(i, full_attention_interval):
            a = ff.multihead_attention(
                a, a, a, hidden_size, num_attention_heads,
                name=f"attn_{i}", decode_max_seq=decode_max_seq,
                kv_page_size=kv_page_size, kv_num_blocks=kv_num_blocks,
                kv_kernel=kv_kernel, **attention)
        else:
            a = ff.gated_delta_net(a, delta, name=f"gdn_{i}",
                                   slot_state=decode_max_seq > 0)
        t = ff.add(t, a, name=f"mixer_res_{i}")
        h = ff.rms_norm(t, eps, name=f"post_norm_{i}", zero_centered=True)
        h = ff.routed_experts(h, experts, name=f"moe_{i}")
        t = ff.add(t, h, name=f"moe_res_{i}")
    t = ff.rms_norm(t, eps, name="final_norm", zero_centered=True)
    logits = ff.dense(t, vocab_size, use_bias=False, name="lm_head")

    # what a decode twin is built from (decoding.make_decoder): this
    # builder again, at seq 1 with paged and per-slot state.
    # `prefill_pass`: every op of this graph is per-token or takes the
    # step's length from its input (the attention's one-view read, the
    # delta rule's scan from each row's state).  Not `prefix_cache`: a
    # page hit without the recurrent state at that position is wrong,
    # and no snapshot of the state is kept (ROADMAP R3)
    ff.decoder_recipe = DecoderRecipe(
        family="qwen3_next", build=build_qwen3_next,
        kwargs=dict(
            hidden_size=hidden_size, num_hidden_layers=num_hidden_layers,
            full_attention_interval=full_attention_interval,
            num_attention_heads=num_attention_heads,
            num_key_value_heads=num_key_value_heads, head_dim=head_dim,
            partial_rotary_factor=partial_rotary_factor,
            rope_theta=rope_theta,
            linear_num_key_heads=linear_num_key_heads,
            linear_num_value_heads=linear_num_value_heads,
            linear_key_head_dim=linear_key_head_dim,
            linear_value_head_dim=linear_value_head_dim,
            linear_conv_kernel_dim=linear_conv_kernel_dim,
            moe_intermediate_size=moe_intermediate_size,
            shared_expert_intermediate_size=shared_expert_intermediate_size,
            num_experts=num_experts, n_routed_experts_total=total,
            first_held_expert=first_held_expert,
            num_experts_per_tok=num_experts_per_tok,
            norm_topk_prob=norm_topk_prob, vocab_size=vocab_size,
            max_position_embeddings=max_position_embeddings,
            rms_norm_eps=rms_norm_eps),
        dims={"num_layers": num_hidden_layers, "hidden_size": hidden_size,
              "num_heads": num_attention_heads,
              "num_kv_heads": num_key_value_heads, "vocab_size": vocab_size,
              "max_seq": max_position_embeddings},
        carries=frozenset({"paged", "chunked_prefill", "prefill_pass"}),
        head=("final_norm", "lm_head"))
    return logits
