"""The `kimi_k2` language model (Kimi-K2 / K2.5; the DeepSeek-V3 block):
multi-head latent attention, one leading dense layer, then layers of
sigmoid-routed experts with a shared expert, RMSNorm everywhere, an
untied head.  Text ids in, logits out; the vision tower is not built.

`build_kimi_k2` takes the keys of the published `config.json` under
their own names.  Three of them may state ONE CHIP'S SHARE of a wider
deployment (docs/SERVING.md "Serving one chip's share of an
expert-parallel layer"): `n_routed_experts` is the experts HELD here
out of `n_routed_experts_total` (the router's width, unchanged),
starting at `first_held_expert`; `vocab_size` is the slice of the
vocabulary held here (ids and logits are over the slice).

    x = tok_embed[ids]
    every layer:  x = x + MLA(RMS(x), positions)
    layer < first_k_dense_replace:
                  x = x + GatedMLP(RMS(x))                intermediate_size
    else:         x = x + RoutedExperts(RMS(x))           moe_intermediate_size
    logits = RMS(x) lm_head
"""
from __future__ import annotations

from typing import Optional

from ..decoding import DecoderRecipe
from ..model import FFModel
from ..ops.mla import MLAParams
from ..ops.routed_experts import RoutedExpertsParams


def build_kimi_k2(
    ff: FFModel,
    batch_size: int = 1,
    seq_length: int = 1,
    *,
    hidden_size: int = 7168,
    num_hidden_layers: int = 61,
    num_attention_heads: int = 64,
    q_lora_rank: int = 1536,
    kv_lora_rank: int = 512,
    qk_nope_head_dim: int = 128,
    qk_rope_head_dim: int = 64,
    v_head_dim: int = 128,
    intermediate_size: int = 18432,
    moe_intermediate_size: int = 2048,
    first_k_dense_replace: int = 1,
    n_routed_experts: int = 384,
    n_routed_experts_total: Optional[int] = None,
    first_held_expert: int = 0,
    n_shared_experts: int = 1,
    num_experts_per_tok: int = 8,
    routed_scaling_factor: float = 2.827,
    norm_topk_prob: bool = True,
    vocab_size: int = 163840,
    max_position_embeddings: int = 262144,
    rms_norm_eps: float = 1e-5,
    rope_theta: float = 50000.0,
    rope_scaling: Optional[dict] = None,
    decode_max_seq: int = 0,
    kv_page_size: int = 0,
    kv_num_blocks: int = 0,
    kv_kernel: str = "gather",
):
    from ..config import ConfigError

    if decode_max_seq and not kv_page_size:
        raise ConfigError(
            "kimi_k2 does not carry the dense per-slot cache "
            "(decode_max_seq without kv_page_size): its cache is the "
            "paged latent pool; build the twin with kv_page_size > 0")
    rs = dict(rope_scaling or {})
    if rs and rs.get("type", "yarn") != "yarn":
        raise ConfigError(f"kimi_k2: rope_scaling type {rs['type']!r} is "
                          "not built; only 'yarn'")
    total = n_routed_experts_total or n_routed_experts
    mla = MLAParams(
        embed_dim=hidden_size, num_heads=num_attention_heads,
        q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
        qk_nope_head_dim=qk_nope_head_dim,
        qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
        rope_theta=float(rope_theta),
        rope_factor=float(rs.get("factor", 1.0)),
        rope_original_max=int(rs.get("original_max_position_embeddings",
                                     max_position_embeddings)),
        beta_fast=float(rs.get("beta_fast", 32)),
        beta_slow=float(rs.get("beta_slow", 1)),
        mscale=float(rs.get("mscale", 1.0)),
        mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)),
        eps=rms_norm_eps)
    experts = RoutedExpertsParams(
        experts_total=total, experts_held=n_routed_experts,
        first_held=first_held_expert, top_k=num_experts_per_tok,
        expert_hidden=moe_intermediate_size,
        shared_hidden=n_shared_experts * moe_intermediate_size,
        routed_scaling_factor=float(routed_scaling_factor),
        norm_topk_prob=norm_topk_prob)

    ids = ff.create_tensor([batch_size, seq_length], dtype="int32",
                           name="input")
    pos = ff.create_tensor([batch_size, seq_length], dtype="int32",
                           name="positions")
    t = ff.embedding(ids, vocab_size, hidden_size, name="tok_embed")
    for i in range(num_hidden_layers):
        a = ff.rms_norm(t, rms_norm_eps, name=f"attn_norm_{i}")
        a = ff.mla_attention(a, pos, mla, name=f"attn_{i}",
                             decode_max_seq=decode_max_seq,
                             kv_page_size=kv_page_size,
                             kv_num_blocks=kv_num_blocks,
                             kv_kernel=kv_kernel)
        t = ff.add(t, a, name=f"attn_res_{i}")
        h = ff.rms_norm(t, rms_norm_eps, name=f"ffn_norm_{i}")
        if i < first_k_dense_replace:
            h = ff.gated_mlp(h, intermediate_size, name=f"mlp_{i}")
        else:
            h = ff.routed_experts(h, experts, name=f"moe_{i}")
        t = ff.add(t, h, name=f"ffn_res_{i}")
    t = ff.rms_norm(t, rms_norm_eps, name="final_norm")
    logits = ff.dense(t, vocab_size, use_bias=False, name="lm_head")

    # what a decode twin is built from (decoding.make_decoder): this
    # builder again, at seq 1 with paged state.  `prefill_pass`: every
    # op of this graph is per-token or, as MLAttention's paged path,
    # takes the step's length from its input, and nothing the family
    # carries rests on byte equality with the seq-1 step, so the engine
    # prefills a chunk in ONE forward of the twin over [slots, C]
    # (decoding.build_paged_prefill_pass) where GPT scans the seq-1 step
    ff.decoder_recipe = DecoderRecipe(
        family="kimi_k2", build=build_kimi_k2,
        kwargs=dict(
            hidden_size=hidden_size, num_hidden_layers=num_hidden_layers,
            num_attention_heads=num_attention_heads,
            q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            intermediate_size=intermediate_size,
            moe_intermediate_size=moe_intermediate_size,
            first_k_dense_replace=first_k_dense_replace,
            n_routed_experts=n_routed_experts,
            n_routed_experts_total=total,
            first_held_expert=first_held_expert,
            n_shared_experts=n_shared_experts,
            num_experts_per_tok=num_experts_per_tok,
            routed_scaling_factor=routed_scaling_factor,
            norm_topk_prob=norm_topk_prob, vocab_size=vocab_size,
            max_position_embeddings=max_position_embeddings,
            rms_norm_eps=rms_norm_eps, rope_theta=rope_theta,
            rope_scaling=rs or None),
        dims={"num_layers": num_hidden_layers, "hidden_size": hidden_size,
              "num_heads": num_attention_heads, "vocab_size": vocab_size,
              "max_seq": max_position_embeddings},
        carries=frozenset({"paged", "prefix_cache", "chunked_prefill",
                           "prefill_pass"}),
        head=("final_norm", "lm_head"))
    return logits
