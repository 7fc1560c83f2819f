"""The `longcat_flash` language model (LongCat-Flash; the text model of
LongCat-Flash-Omni): a DOUBLE layer of two latent attentions and two
dense MLPs with the routed experts on a shortcut across it, a softmax
router whose width is the real experts plus identity ("zero-compute")
experts, constant scales on both latent bottlenecks, RMSNorm
everywhere, an untied head.  Text ids in, logits out; the audio and
vision encoders and the codec decoder are not built.

`build_longcat_flash` takes the keys of the published `config.json`
under their own names.  Three of them may state ONE CHIP'S SHARE of a
wider deployment (docs/SERVING.md "Serving one chip's share of an
expert-parallel layer"), as `build_kimi_k2` takes it:
`n_routed_experts` is the experts HELD here out of
`n_routed_experts_total`, starting at `first_held_expert`; `vocab_size`
is the slice of the vocabulary held here.  The `zero_expert_num`
identity experts are every chip's own.

    x = tok_embed[ids]
    every layer (A_i, F_i its two latent attentions and dense MLPs,
    E its routed experts, the router `n_routed_experts_total +
    zero_expert_num` wide):
        a   = x + A_0(RMS_0(x), positions)
        u   = RMS_1(a)
        m   = E(u)                      the shortcut: read here ...
        b   = a + F_0(u)
        c   = b + A_1(RMS_2(b), positions)
        out = c + F_1(RMS_3(c)) + m     ... joined here
    logits = RMS(x) lm_head

In the graph `m` is made before `F_0` and consumed four ops after it is
made (`F_0`, its add, `RMS_2`, `A_1`, its add, `RMS_3`, `F_1` run
beside it): the order in which a backend runs the two branches is the
compiler's, the dataflow is the published one.
"""
from __future__ import annotations

import math
from typing import Optional

from ..decoding import DecoderRecipe
from ..model import FFModel
from ..ops.mla import MLAParams
from ..ops.routed_experts import RoutedExpertsParams


def build_longcat_flash(
    ff: FFModel,
    batch_size: int = 1,
    seq_length: int = 1,
    *,
    hidden_size: int = 6144,
    num_layers: int = 28,
    num_attention_heads: int = 64,
    q_lora_rank: int = 1536,
    kv_lora_rank: int = 512,
    qk_nope_head_dim: int = 128,
    qk_rope_head_dim: int = 64,
    v_head_dim: int = 128,
    mla_scale_q_lora: bool = True,
    mla_scale_kv_lora: bool = True,
    ffn_hidden_size: int = 12288,
    expert_ffn_hidden_size: int = 2048,
    n_routed_experts: int = 512,
    n_routed_experts_total: Optional[int] = None,
    first_held_expert: int = 0,
    zero_expert_num: int = 256,
    zero_expert_type: str = "identity",
    moe_topk: int = 12,
    routed_scaling_factor: float = 6.0,
    vocab_size: int = 131072,
    max_position_embeddings: int = 131072,
    rms_norm_eps: float = 1e-5,
    rope_theta: float = 1e7,
    decode_max_seq: int = 0,
    kv_page_size: int = 0,
    kv_num_blocks: int = 0,
    kv_kernel: str = "gather",
):
    from ..config import ConfigError

    if decode_max_seq and not kv_page_size:
        raise ConfigError(
            "longcat_flash does not carry the dense per-slot cache "
            "(decode_max_seq without kv_page_size): its cache is the "
            "paged latent pool; build the twin with kv_page_size > 0")
    if zero_expert_num and zero_expert_type != "identity":
        raise ConfigError(
            f"longcat_flash: zero_expert_type {zero_expert_type!r} is not "
            "built; only 'identity'")
    total = n_routed_experts_total or n_routed_experts
    mla = MLAParams(
        embed_dim=hidden_size, num_heads=num_attention_heads,
        q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
        qk_nope_head_dim=qk_nope_head_dim,
        qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
        rope_theta=float(rope_theta),
        rope_original_max=max_position_embeddings, eps=rms_norm_eps,
        # the published constants: sqrt(hidden / rank) on a bottleneck
        q_lora_scale=(math.sqrt(hidden_size / q_lora_rank)
                      if mla_scale_q_lora else 1.0),
        kv_lora_scale=(math.sqrt(hidden_size / kv_lora_rank)
                       if mla_scale_kv_lora else 1.0))
    # a softmax over the whole width whose chosen scores are NOT
    # renormalised, no shared expert
    experts = RoutedExpertsParams(
        experts_total=total, experts_held=n_routed_experts,
        first_held=first_held_expert, top_k=moe_topk,
        expert_hidden=expert_ffn_hidden_size,
        routed_scaling_factor=float(routed_scaling_factor),
        norm_topk_prob=False, scoring="softmax",
        zero_experts=zero_expert_num)

    ids = ff.create_tensor([batch_size, seq_length], dtype="int32",
                           name="input")
    pos = ff.create_tensor([batch_size, seq_length], dtype="int32",
                           name="positions")

    def attention(t, name):
        return ff.mla_attention(
            ff.rms_norm(t, rms_norm_eps, name=f"{name}_norm"), pos, mla,
            name=name, decode_max_seq=decode_max_seq,
            kv_page_size=kv_page_size, kv_num_blocks=kv_num_blocks,
            kv_kernel=kv_kernel)

    t = ff.embedding(ids, vocab_size, hidden_size, name="tok_embed")
    for i in range(num_layers):
        a = ff.add(t, attention(t, f"attn_{i}_0"), name=f"attn_res_{i}_0")
        u = ff.rms_norm(a, rms_norm_eps, name=f"mlp_{i}_0_norm")
        m = ff.routed_experts(u, experts, name=f"moe_{i}")  # the shortcut
        b = ff.add(a, ff.gated_mlp(u, ffn_hidden_size, name=f"mlp_{i}_0"),
                   name=f"mlp_res_{i}_0")
        c = ff.add(b, attention(b, f"attn_{i}_1"), name=f"attn_res_{i}_1")
        f1 = ff.gated_mlp(
            ff.rms_norm(c, rms_norm_eps, name=f"mlp_{i}_1_norm"),
            ffn_hidden_size, name=f"mlp_{i}_1")
        t = ff.add(ff.add(c, f1, name=f"mlp_res_{i}_1"), m,
                   name=f"moe_res_{i}")
    t = ff.rms_norm(t, rms_norm_eps, name="final_norm")
    logits = ff.dense(t, vocab_size, use_bias=False, name="lm_head")

    # the twin is this builder again at seq 1 with paged state; every op
    # is per-token or takes the step's length from its input, so a chunk
    # prefills in ONE forward over [slots, C] (`prefill_pass`), as
    # kimi_k2's does.  A layer has TWO latent pools (one an attention
    # op) under the sequence's one block table: the serving tier finds
    # pools by asking the graph's ops (`decoding.cache_entries`), so
    # `num_layers` below counts layers, not pools
    ff.decoder_recipe = DecoderRecipe(
        family="longcat_flash", build=build_longcat_flash,
        kwargs=dict(
            hidden_size=hidden_size, num_layers=num_layers,
            num_attention_heads=num_attention_heads,
            q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            mla_scale_q_lora=mla_scale_q_lora,
            mla_scale_kv_lora=mla_scale_kv_lora,
            ffn_hidden_size=ffn_hidden_size,
            expert_ffn_hidden_size=expert_ffn_hidden_size,
            n_routed_experts=n_routed_experts,
            n_routed_experts_total=total,
            first_held_expert=first_held_expert,
            zero_expert_num=zero_expert_num,
            zero_expert_type=zero_expert_type, moe_topk=moe_topk,
            routed_scaling_factor=routed_scaling_factor,
            vocab_size=vocab_size,
            max_position_embeddings=max_position_embeddings,
            rms_norm_eps=rms_norm_eps, rope_theta=rope_theta),
        dims={"num_layers": num_layers, "hidden_size": hidden_size,
              "num_heads": num_attention_heads, "vocab_size": vocab_size,
              "max_seq": max_position_embeddings},
        carries=frozenset({"paged", "prefix_cache", "chunked_prefill",
                           "prefill_pass"}),
        head=("final_norm", "lm_head"))
    return logits
