"""The `kimi_linear` language model (Kimi-Linear-48B-A3B): Kimi Delta
Attention layers (the delta rule with a decay per channel,
`ops/kimi_delta_attention.py`) with a latent-attention layer without
positions and without a query bottleneck (`ops/mla.py`, `nope`,
`q_lora_rank` 0) after every third of them, one leading layer with a
dense gated MLP and then sigmoid-routed experts with a choosing bias
beside a shared expert (`models/kimi_k2.py`'s feed-forward), RMSNorm
everywhere, an untied head.  Text ids in, logits out.

`build_kimi_linear` takes the keys of the published `config.json` under
their own names; which layer is of which kind is READ from
`linear_attn_config` (`kda_layers` and `full_attn_layers`, 1-indexed as
published).  Three keys may state ONE CHIP'S SHARE of a wider
deployment, as in `build_kimi_k2`: `num_experts` is the experts HELD
here out of `n_routed_experts_total` (the router's width, unchanged),
starting at `first_held_expert`; `vocab_size` is the slice of the
vocabulary held here (ids, logits and the loss are over the slice).

    x = tok_embed[ids]
    layer i (1-indexed):
        x = x + KDA(RMS(x))         i in kda_layers
        x = x + MLA(RMS(x))         i in full_attn_layers
        x = x + GatedMLP(RMS(x))         i <= first_k_dense_replace
        x = x + RoutedExperts(RMS(x))    after
    logits = RMS(x) lm_head

`num_expert_group` 1 and `topk_group` 1, as published, make the grouped
top-k a plain one; other values are a `ConfigError`.  The graph is a
trainer's: the model records no decoder recipe, and asking the serving
tier for a twin of it is a `ConfigError` by name (the delta-rule layers
carry no per-slot state yet).
"""
from __future__ import annotations

from typing import Optional

from ..model import FFModel
from ..ops.kimi_delta_attention import KimiDeltaAttentionParams
from ..ops.mla import MLAParams
from ..ops.routed_experts import RoutedExpertsParams


def layer_kinds(linear_attn_config: dict, num_hidden_layers: int) -> list:
    """["kda" | "mla"] a layer, from the published 1-indexed lists; a
    layer in neither or in both is a `ConfigError`."""
    from ..config import ConfigError

    kda = set(linear_attn_config.get("kda_layers", ()))
    full = set(linear_attn_config.get("full_attn_layers", ()))
    wanted = set(range(1, num_hidden_layers + 1))
    if kda & full or (kda | full) != wanted:
        raise ConfigError(
            "kimi_linear: linear_attn_config's kda_layers "
            f"{sorted(kda)} and full_attn_layers {sorted(full)} must "
            f"name each of layers 1..{num_hidden_layers} once")
    return ["kda" if i in kda else "mla" for i in sorted(wanted)]


def build_kimi_linear(
    ff: FFModel,
    batch_size: int = 1,
    seq_length: int = 1,
    *,
    hidden_size: int = 2304,
    num_hidden_layers: int = 27,
    linear_attn_config: Optional[dict] = None,
    num_attention_heads: int = 32,
    q_lora_rank: Optional[int] = None,
    kv_lora_rank: int = 512,
    qk_nope_head_dim: int = 128,
    qk_rope_head_dim: int = 64,
    v_head_dim: int = 128,
    mla_use_nope: bool = True,
    rope_theta: float = 10000.0,
    rope_scaling: Optional[dict] = None,
    intermediate_size: int = 9216,
    moe_intermediate_size: int = 1024,
    first_k_dense_replace: int = 1,
    num_experts: int = 256,
    n_routed_experts_total: Optional[int] = None,
    first_held_expert: int = 0,
    num_shared_experts: int = 1,
    num_experts_per_token: int = 8,
    num_expert_group: int = 1,
    topk_group: int = 1,
    moe_renormalize: bool = True,
    moe_router_activation_func: str = "sigmoid",
    routed_scaling_factor: float = 2.446,
    vocab_size: int = 163840,
    model_max_length: int = 1048576,
    rms_norm_eps: float = 1e-5,
):
    from ..config import ConfigError

    if linear_attn_config is None:
        raise ConfigError("kimi_linear: linear_attn_config (the layer "
                          "pattern and the delta-rule heads) is required")
    kinds = layer_kinds(linear_attn_config, num_hidden_layers)
    if num_expert_group != 1 or topk_group != 1:
        raise ConfigError(
            "kimi_linear: a grouped top-k over more than one group is not "
            f"built (num_expert_group {num_expert_group}, topk_group "
            f"{topk_group}; the published config sets both to 1)")
    if rope_scaling:
        raise ConfigError("kimi_linear: rope_scaling is not built (the "
                          "published config has none)")
    if seq_length > model_max_length:
        raise ConfigError(f"kimi_linear: seq_length {seq_length} passes "
                          f"model_max_length {model_max_length}")
    kda = KimiDeltaAttentionParams(
        embed_dim=hidden_size, num_heads=linear_attn_config["num_heads"],
        head_dim=linear_attn_config["head_dim"],
        conv_kernel=linear_attn_config["short_conv_kernel_size"],
        eps=rms_norm_eps)
    mla = MLAParams(
        embed_dim=hidden_size, num_heads=num_attention_heads,
        q_lora_rank=q_lora_rank or 0, kv_lora_rank=kv_lora_rank,
        qk_nope_head_dim=qk_nope_head_dim,
        qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
        rope_theta=float(rope_theta), eps=rms_norm_eps,
        nope=bool(mla_use_nope))
    experts = RoutedExpertsParams(
        experts_total=n_routed_experts_total or num_experts,
        experts_held=num_experts, first_held=first_held_expert,
        top_k=num_experts_per_token, expert_hidden=moe_intermediate_size,
        shared_hidden=num_shared_experts * moe_intermediate_size,
        routed_scaling_factor=float(routed_scaling_factor),
        norm_topk_prob=moe_renormalize,
        scoring=moe_router_activation_func)

    ids = ff.create_tensor([batch_size, seq_length], dtype="int32",
                           name="input")
    pos = None if mla.nope else ff.create_tensor(
        [batch_size, seq_length], dtype="int32", name="positions")
    t = ff.embedding(ids, vocab_size, hidden_size, name="tok_embed")
    for i, kind in enumerate(kinds):
        a = ff.rms_norm(t, rms_norm_eps, name=f"mixer_norm_{i}")
        if kind == "kda":
            a = ff.kimi_delta_attention(a, kda, name=f"kda_{i}")
        else:
            a = ff.mla_attention(a, pos, mla, name=f"mla_{i}")
        t = ff.add(t, a, name=f"mixer_res_{i}")
        h = ff.rms_norm(t, rms_norm_eps, name=f"ffn_norm_{i}")
        if i < first_k_dense_replace:
            h = ff.gated_mlp(h, intermediate_size, name=f"mlp_{i}")
        else:
            h = ff.routed_experts(h, experts, name=f"moe_{i}")
        t = ff.add(t, h, name=f"ffn_res_{i}")
    t = ff.rms_norm(t, rms_norm_eps, name="final_norm")
    logits = ff.dense(t, vocab_size, use_bias=False, name="lm_head")
    # read by decoding.decoder_recipe: why this model has no twin
    ff.not_served = (
        "kimi_linear is built for training only: its delta-rule layers "
        "carry no per-slot state (Op.slot_state_entries) yet, so no "
        "decode twin of it can be built")
    return logits
