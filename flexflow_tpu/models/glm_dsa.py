"""The `glm_moe_dsa` language model (GLM-5.x): the DeepSeek-V3 block
(`models/kimi_k2.py`: multi-head latent attention, leading dense layers,
then sigmoid-routed experts with a shared one, RMSNorm, an untied head)
whose attention reads only the keys a learned INDEXER picks (DeepSeek
sparse attention; `ops/mla.py` "Selected keys"), with the indexer in
SOME layers and its picks reused by the layers above it.

`build_glm_dsa` takes the keys of the published `config.json` under
their own names.  `indexer_types[i]` is `"full"` (layer i scores and
picks) or `"shared"` (it reads the picks of the nearest `full` layer
below); `mlp_layer_types[i]` is `"dense"` or `"sparse"`.  Both lists
are the graph's layers in order and may be any stretch of the published
ones that starts at a `full` layer.  As in `build_kimi_k2`,
`n_routed_experts` of `n_routed_experts_total` from `first_held_expert`
and `vocab_size` may state ONE CHIP'S SHARE (docs/SERVING.md).

    x = tok_embed[ids]
    full layer:    a, picks = MLA(RMS(x), positions)        picks [b, s, index_topk]
    shared layer:  a = MLA(RMS(x), positions, picks)        the last full layer's
    x = x + a
    dense:   x = x + GatedMLP(RMS(x))                       intermediate_size
    sparse:  x = x + RoutedExperts(RMS(x))                  moe_intermediate_size
    logits = RMS(x) lm_head

The picks are a graph tensor: the one data edge of a served graph that
skips layers and is not the residual.  The multi-token-prediction layer
is not built.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

from ..decoding import DecoderRecipe
from ..model import FFModel
from ..ops.mla import MLAParams
from ..ops.routed_experts import RoutedExpertsParams


def published_layer_types(num_hidden_layers: int, first_k_dense_replace: int,
                          index_topk_freq: int, index_skip_topk_offset: int):
    """(indexer_types, mlp_layer_types) of the published model from its
    scalar keys: the first `index_skip_topk_offset` layers and then one
    layer in `index_topk_freq` (`index_skip_topk_offset - 1 +
    index_topk_freq`, and so on) carry an indexer; the first
    `first_k_dense_replace` layers a dense MLP."""
    full = lambda i: (i < index_skip_topk_offset or  # noqa: E731
                      (i - index_skip_topk_offset + 1) % index_topk_freq == 0)
    return (["full" if full(i) else "shared"
             for i in range(num_hidden_layers)],
            ["dense" if i < first_k_dense_replace else "sparse"
             for i in range(num_hidden_layers)])


def build_glm_dsa(
    ff: FFModel,
    batch_size: int = 1,
    seq_length: int = 1,
    *,
    hidden_size: int = 6144,
    num_attention_heads: int = 64,
    q_lora_rank: int = 2048,
    kv_lora_rank: int = 512,
    qk_nope_head_dim: int = 192,
    qk_rope_head_dim: int = 64,
    v_head_dim: int = 256,
    index_n_heads: int = 32,
    index_head_dim: int = 128,
    index_topk: int = 2048,
    indexer_types: Sequence[str] = ("full",),
    mlp_layer_types: Sequence[str] = ("dense",),
    intermediate_size: int = 12288,
    moe_intermediate_size: int = 2048,
    n_routed_experts: int = 256,
    n_routed_experts_total: Optional[int] = None,
    first_held_expert: int = 0,
    n_shared_experts: int = 1,
    num_experts_per_tok: int = 8,
    routed_scaling_factor: float = 2.5,
    norm_topk_prob: bool = True,
    vocab_size: int = 154880,
    max_position_embeddings: int = 1048576,
    rms_norm_eps: float = 1e-5,
    rope_theta: float = 8000000.0,
    decode_max_seq: int = 0,
    kv_page_size: int = 0,
    kv_num_blocks: int = 0,
    kv_kernel: str = "gather",
):
    from ..config import ConfigError

    if decode_max_seq and not kv_page_size:
        raise ConfigError(
            "glm_moe_dsa does not carry the dense per-slot cache "
            "(decode_max_seq without kv_page_size): its caches are the "
            "paged latent and index-key pools; build the twin with "
            "kv_page_size > 0")
    indexer_types, mlp_layer_types = list(indexer_types), list(mlp_layer_types)
    if (len(indexer_types) != len(mlp_layer_types)
            or indexer_types[:1] != ["full"]
            or set(indexer_types) - {"full", "shared"}
            or set(mlp_layer_types) - {"dense", "sparse"}):
        raise ConfigError(
            "glm_moe_dsa: indexer_types ('full' | 'shared', the first "
            "'full': a shared layer reads the picks of a full layer below "
            "it) and mlp_layer_types ('dense' | 'sparse') name the same "
            f"layers; got {indexer_types} and {mlp_layer_types}")
    total = n_routed_experts_total or n_routed_experts
    full = MLAParams(
        embed_dim=hidden_size, num_heads=num_attention_heads,
        q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
        qk_nope_head_dim=qk_nope_head_dim,
        qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
        rope_theta=float(rope_theta),
        rope_original_max=max_position_embeddings, eps=rms_norm_eps,
        index_topk=index_topk, index_n_heads=index_n_heads,
        index_head_dim=index_head_dim, indexer="full")
    shared = dataclasses.replace(full, indexer="shared")
    experts = RoutedExpertsParams(
        experts_total=total, experts_held=n_routed_experts,
        first_held=first_held_expert, top_k=num_experts_per_tok,
        expert_hidden=moe_intermediate_size,
        shared_hidden=n_shared_experts * moe_intermediate_size,
        routed_scaling_factor=float(routed_scaling_factor),
        norm_topk_prob=norm_topk_prob)
    cache = dict(decode_max_seq=decode_max_seq, kv_page_size=kv_page_size,
                 kv_num_blocks=kv_num_blocks, kv_kernel=kv_kernel)

    ids = ff.create_tensor([batch_size, seq_length], dtype="int32",
                           name="input")
    pos = ff.create_tensor([batch_size, seq_length], dtype="int32",
                           name="positions")
    t = ff.embedding(ids, vocab_size, hidden_size, name="tok_embed")
    picks = None
    for i, (role, mlp) in enumerate(zip(indexer_types, mlp_layer_types)):
        a = ff.rms_norm(t, rms_norm_eps, name=f"attn_norm_{i}")
        if role == "full":
            a, picks = ff.mla_attention(a, pos, full, name=f"attn_{i}",
                                        **cache)
        else:
            a = ff.mla_attention(a, pos, shared, name=f"attn_{i}",
                                 picks=picks, **cache)
        t = ff.add(t, a, name=f"attn_res_{i}")
        h = ff.rms_norm(t, rms_norm_eps, name=f"ffn_norm_{i}")
        if mlp == "dense":
            h = ff.gated_mlp(h, intermediate_size, name=f"mlp_{i}")
        else:
            h = ff.routed_experts(h, experts, name=f"moe_{i}")
        t = ff.add(t, h, name=f"ffn_res_{i}")
    t = ff.rms_norm(t, rms_norm_eps, name="final_norm")
    logits = ff.dense(t, vocab_size, use_bias=False, name="lm_head")
    ff.set_output(logits)  # (a last full layer's picks are a sink too)

    # what a decode twin is built from (decoding.make_decoder): this
    # builder again, at seq 1 with paged state.  `prefill_pass` as
    # `build_kimi_k2` argues it; `prefix_cache`: a full layer's index
    # keys sit in a pool of their own under the SAME block ids as the
    # latents and are a function of the prefix alone, so a block hit
    # serves both
    ff.decoder_recipe = DecoderRecipe(
        family="glm_moe_dsa", build=build_glm_dsa,
        kwargs=dict(
            hidden_size=hidden_size,
            num_attention_heads=num_attention_heads,
            q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            index_n_heads=index_n_heads, index_head_dim=index_head_dim,
            index_topk=index_topk, indexer_types=tuple(indexer_types),
            mlp_layer_types=tuple(mlp_layer_types),
            intermediate_size=intermediate_size,
            moe_intermediate_size=moe_intermediate_size,
            n_routed_experts=n_routed_experts,
            n_routed_experts_total=total,
            first_held_expert=first_held_expert,
            n_shared_experts=n_shared_experts,
            num_experts_per_tok=num_experts_per_tok,
            routed_scaling_factor=routed_scaling_factor,
            norm_topk_prob=norm_topk_prob, vocab_size=vocab_size,
            max_position_embeddings=max_position_embeddings,
            rms_norm_eps=rms_norm_eps, rope_theta=rope_theta),
        dims={"num_layers": len(indexer_types), "hidden_size": hidden_size,
              "num_heads": num_attention_heads, "vocab_size": vocab_size,
              "max_seq": max_position_embeddings},
        carries=frozenset({"paged", "prefix_cache", "chunked_prefill",
                           "prefill_pass"}),
        head=("final_norm", "lm_head"))
    return logits
