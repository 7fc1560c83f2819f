"""The `laguna` language model (poolside Laguna-XS.2): window and full
attention layers mixed (`layer_types`), a query-head count a layer
(`num_attention_heads_per_layer`) over one count of key/value heads,
one sigmoid gate a head, a leading dense MLP and then sigmoid-routed
experts beside a shared one (`mlp_layer_types`), an untied head.  Text
ids in, logits out.

`build_laguna` takes the keys of the published `config.json` under
their own names.  `num_experts` may state ONE CHIP'S SHARE of a wider
deployment (docs/SERVING.md "Serving one chip's share of an
expert-parallel layer"): the experts HELD here out of
`n_routed_experts_total` (the router's width, unchanged), starting at
`first_held_expert`.

Layer `i` has `t_i = layer_types[i]`, `n_i =
num_attention_heads_per_layer[i]`, `G_i = n_i / num_key_value_heads`;
`x` is the residual stream, `d = head_dim`:

    a = RMS(x; g1, eps)
    q = a Wq  [n_i x d]   k = a Wk  [kv x d]   v = a Wv  [kv x d]
    gate = sigmoid(a Wg)  [n_i]
    full:     RoPE on the first `partial_rotary_factor` of each head of
              q and k, YaRN frequencies (`rope_parameters
              ["full_attention"]`: theta, factor, original, beta_fast,
              beta_slow; ops/rope.py), cos and sin times
              `attention_factor`; the other channels pass
    sliding:  RoPE on all d channels, `rope_parameters
              ["sliding_attention"]`'s theta, no scaling
    score[h, s, j] = q[h, s] . k[h // G_i, j] / sqrt(d)
    visible:  full  j <= s ;  sliding  s - sliding_window < j <= s
    o[h, s] = sum_j softmax_j(score)[h, s, j] v[h // G_i, j]
    x = x + concat_h(gate[h] o[h]) Wo
    b = RMS(x; g2, eps)
    dense:   x = x + (silu(b Wgate) * (b Wup)) Wdown
    sparse:  s = sigmoid(b Wr) in float32; top k of s;
             w = moe_routed_scaling_factor s_e / sum_chosen s
             x = x + sum over e chosen AND held of w_e E_e(b) + E_shared(b)
    logits = RMS(x; g, eps) W_head

(the rotation pairs channel i of a head's rotary half with channel i +
rotary_dim / 2, as `ops/attention.py rotate_half` has it).

A decode twin of it holds two kinds of per-sequence state under one
slot: the full layers' keys and values in the paged pool, and each
window layer's in a ring of `sliding_window + prefill_chunk` rows
(rounded up to pages) a slot, whatever the sequence's length
(ops/attention.py `MultiHeadAttention`; docs/SERVING.md "Window layers
beside full ones").
"""
from __future__ import annotations

from typing import Optional, Sequence

from ..decoding import DecoderRecipe
from ..model import FFModel
from ..ops.routed_experts import RoutedExpertsParams

FULL, SLIDING = "full_attention", "sliding_attention"


def window_ring_rows(sliding_window: int, prefill_chunk: int,
                     kv_page_size: int) -> int:
    """Rows a slot of a window layer's ring: the window and the longest
    step beside it, rounded up to whole pages."""
    page = max(1, kv_page_size)
    return -(-(sliding_window + max(1, prefill_chunk)) // page) * page


def build_laguna(
    ff: FFModel,
    batch_size: int = 1,
    seq_length: int = 1,
    *,
    hidden_size: int = 2048,
    num_hidden_layers: int = 40,
    layer_types: Sequence[str] = (),
    mlp_layer_types: Sequence[str] = (),
    num_attention_heads_per_layer: Sequence[int] = (),
    num_attention_heads: int = 48,
    num_key_value_heads: int = 8,
    head_dim: int = 128,
    sliding_window: int = 512,
    rope_parameters: Optional[dict] = None,
    gating: bool = True,
    intermediate_size: int = 8192,
    moe_intermediate_size: int = 512,
    shared_expert_intermediate_size: int = 512,
    num_experts: int = 256,
    n_routed_experts_total: Optional[int] = None,
    first_held_expert: int = 0,
    num_experts_per_tok: int = 8,
    moe_routed_scaling_factor: float = 2.5,
    vocab_size: int = 100352,
    max_position_embeddings: int = 262144,
    rms_norm_eps: float = 1e-6,
    prefill_chunk: int = 0,
    decode_max_seq: int = 0,
    kv_page_size: int = 0,
    kv_num_blocks: int = 0,
    kv_kernel: str = "gather",
):
    """`prefill_chunk`: the longest step a decode twin of this model
    will be asked for (its window layers' rings are sized by it; the
    scheduler refuses a longer one)."""
    from ..config import ConfigError

    if decode_max_seq and not kv_page_size:
        raise ConfigError(
            "laguna does not carry the dense per-slot cache "
            "(decode_max_seq without kv_page_size): its full layers cache "
            "in the paged pool and its window layers in per-slot rings; "
            "build the twin with kv_page_size > 0")
    layer_types = list(layer_types) or [FULL] * num_hidden_layers
    mlp_layer_types = list(mlp_layer_types) or ["sparse"] * num_hidden_layers
    heads = (list(num_attention_heads_per_layer)
             or [num_attention_heads] * num_hidden_layers)
    for key, given in (("layer_types", layer_types),
                       ("mlp_layer_types", mlp_layer_types),
                       ("num_attention_heads_per_layer", heads)):
        if len(given) != num_hidden_layers:
            raise ConfigError(
                f"laguna: {key} has {len(given)} entries for "
                f"num_hidden_layers {num_hidden_layers}")
    unknown = (set(layer_types) - {FULL, SLIDING}) \
        | (set(mlp_layer_types) - {"dense", "sparse"})
    if unknown:
        raise ConfigError(f"laguna: layer types {sorted(unknown)} are not "
                          "built")
    rope = {FULL: {}, SLIDING: {}, **(rope_parameters or {})}
    for kind in (FULL, SLIDING):
        if rope[kind].get("rope_type", "default") not in ("default", "yarn"):
            raise ConfigError(
                f"laguna: rope_type {rope[kind]['rope_type']!r} of {kind} "
                "is not built; 'default' and 'yarn' are")
    total = n_routed_experts_total or num_experts
    eps = rms_norm_eps
    ring = window_ring_rows(sliding_window, prefill_chunk, kv_page_size)

    def attention(kind: str) -> dict:
        r = rope[kind]
        yarn = r.get("rope_type", "default") == "yarn"
        return dict(
            causal=True, num_kv_heads=num_key_value_heads,
            rotary_dim=int(head_dim * r.get("partial_rotary_factor", 1.0)),
            rope_theta=float(r.get("rope_theta", 10000.0)),
            rope_factor=float(r["factor"]) if yarn else 1.0,
            rope_original_max=int(r.get(
                "original_max_position_embeddings",
                max_position_embeddings)),
            beta_fast=float(r.get("beta_fast", 32)),
            beta_slow=float(r.get("beta_slow", 1)),
            rope_attention_factor=(float(r.get("attention_factor", 1.0))
                                   if yarn else 1.0),
            head_gate=bool(gating), paged_read_once=True,
            kv_head_major=True,
            sliding_window=sliding_window if kind == SLIDING else 0)

    experts = RoutedExpertsParams(
        experts_total=total, experts_held=num_experts,
        first_held=first_held_expert, top_k=num_experts_per_tok,
        expert_hidden=moe_intermediate_size,
        shared_hidden=shared_expert_intermediate_size,
        routed_scaling_factor=float(moe_routed_scaling_factor),
        norm_topk_prob=True)

    ids = ff.create_tensor([batch_size, seq_length], dtype="int32",
                           name="input")
    t = ff.embedding(ids, vocab_size, hidden_size, name="tok_embed")
    for i in range(num_hidden_layers):
        a = ff.rms_norm(t, eps, name=f"input_norm_{i}")
        a = ff.multihead_attention(
            a, a, a, hidden_size, heads[i], kdim=heads[i] * head_dim,
            vdim=heads[i] * head_dim, name=f"attn_{i}",
            decode_max_seq=decode_max_seq, kv_page_size=kv_page_size,
            kv_num_blocks=kv_num_blocks, kv_kernel=kv_kernel,
            window_ring=ring if decode_max_seq else 0,
            **attention(layer_types[i]))
        t = ff.add(t, a, name=f"attn_res_{i}")
        h = ff.rms_norm(t, eps, name=f"post_norm_{i}")
        if mlp_layer_types[i] == "dense":
            h = ff.gated_mlp(h, intermediate_size, name=f"mlp_{i}")
        else:
            h = ff.routed_experts(h, experts, name=f"moe_{i}")
        t = ff.add(t, h, name=f"mlp_res_{i}")
    t = ff.rms_norm(t, eps, name="final_norm")
    logits = ff.dense(t, vocab_size, use_bias=False, name="lm_head")

    # what a decode twin is built from (decoding.make_decoder): this
    # builder again, at seq 1 with paged state for the full layers and a
    # ring a slot for the window layers.  `prefill_pass`: every op of
    # this graph is per-token or takes the step's length from its input
    # (the one-view paged read, the ring's read).  `pallas_read`: the
    # in-place kernel takes the query heads grouped.  Not
    # `prefix_cache`, `speculative`, `handoff`: a page hit (or a moved
    # page) without the ring at that position is wrong, and no snapshot
    # of a ring is kept (ROADMAP R2)
    ff.decoder_recipe = DecoderRecipe(
        family="laguna", build=build_laguna,
        kwargs=dict(
            hidden_size=hidden_size, num_hidden_layers=num_hidden_layers,
            layer_types=tuple(layer_types),
            mlp_layer_types=tuple(mlp_layer_types),
            num_attention_heads_per_layer=tuple(heads),
            num_attention_heads=num_attention_heads,
            num_key_value_heads=num_key_value_heads, head_dim=head_dim,
            sliding_window=sliding_window, rope_parameters=rope,
            gating=gating, intermediate_size=intermediate_size,
            moe_intermediate_size=moe_intermediate_size,
            shared_expert_intermediate_size=shared_expert_intermediate_size,
            num_experts=num_experts, n_routed_experts_total=total,
            first_held_expert=first_held_expert,
            num_experts_per_tok=num_experts_per_tok,
            moe_routed_scaling_factor=moe_routed_scaling_factor,
            vocab_size=vocab_size,
            max_position_embeddings=max_position_embeddings,
            rms_norm_eps=rms_norm_eps, prefill_chunk=prefill_chunk),
        dims={"num_layers": num_hidden_layers, "hidden_size": hidden_size,
              "num_heads": max(heads), "heads_per_layer": tuple(heads),
              "num_kv_heads": num_key_value_heads, "vocab_size": vocab_size,
              "max_seq": max_position_embeddings},
        carries=frozenset({"paged", "chunked_prefill", "prefill_pass",
                           "pallas_read"}),
        head=("final_norm", "lm_head"))
    return logits
