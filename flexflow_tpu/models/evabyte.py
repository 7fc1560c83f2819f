"""The `evabyte` language model (EvaByte 6.5B): a byte-level decoder
whose every layer mixes with EVA chunked attention (ops/eva_attention.py:
one softmax over the query's own window of keys and one pooled summary
per `chunk_size` positions of every earlier window).  Pre-norm blocks,
RMSNorm with a unit offset (`norm_add_unit_offset`: the gain stored
around zero), SwiGLU, an untied head of `num_pred_heads` heads side by
side.  Byte ids in, logits out.

`build_evabyte` takes the keys of the published `config.json` under
their own names.

    x = tok_embed[ids]
    layer i:  x = x + EvaAttention(RMS(x));  x = x + GatedMLP(RMS(x))
    logits = RMS(x) lm_head        [.., num_pred_heads x vocab_size]:
                                   head p, columns p x vocab_size on,
                                   predicts the byte p + 1 positions on

A decode twin of it keeps no paged pool at all: each layer's window and
summary store are `[slots, ...]` arrays (`slot_state_entries`), masked
by position.  The server samples the NEXT byte, head 0: the step
programs return the head's first `vocab_size` columns
(`DecoderRecipe.logit_columns`).  Drafting with heads 1.. and verifying
the draft is a step that yields other than one token a row (ROADMAP
R4), and is not built.
"""
from __future__ import annotations

from ..decoding import DecoderRecipe
from ..model import FFModel
from ..ops.eva_attention import EvaAttentionParams


def build_evabyte(
    ff: FFModel,
    batch_size: int = 1,
    seq_length: int = 1,
    *,
    hidden_size: int = 4096,
    num_hidden_layers: int = 32,
    num_attention_heads: int = 32,
    num_key_value_heads: int = 32,
    intermediate_size: int = 11008,
    vocab_size: int = 320,
    num_pred_heads: int = 8,
    window_size: int = 2048,
    chunk_size: int = 16,
    rope_theta: float = 100000.0,
    rms_norm_eps: float = 1e-5,
    norm_add_unit_offset: bool = True,
    max_position_embeddings: int = 32768,
    decode_max_seq: int = 0,
    kv_page_size: int = 0,
    kv_num_blocks: int = 0,
    kv_kernel: str = "gather",
):
    from ..config import ConfigError

    if decode_max_seq and not kv_page_size:
        raise ConfigError(
            "evabyte does not carry the dense per-slot cache "
            "(decode_max_seq without kv_page_size): its state is a "
            "window and a summary store a slot, driven by the paged "
            "twin's host-owned lengths; build the twin with "
            "kv_page_size > 0")
    if num_key_value_heads != num_attention_heads \
            or hidden_size % num_attention_heads:
        raise ConfigError(
            "evabyte: grouped key/value heads are not built (the "
            "published config has as many as query heads), and "
            "num_attention_heads must divide hidden_size")
    if decode_max_seq % chunk_size:
        raise ConfigError(
            f"evabyte: max_position_embeddings {decode_max_seq} must be "
            f"a multiple of chunk_size {chunk_size} (the summary store "
            "holds a row a chunk)")
    eps = rms_norm_eps
    eva = EvaAttentionParams(
        embed_dim=hidden_size, num_heads=num_attention_heads,
        head_dim=hidden_size // num_attention_heads,
        window_size=window_size, chunk_size=chunk_size,
        rope_theta=float(rope_theta))

    ids = ff.create_tensor([batch_size, seq_length], dtype="int32",
                           name="input")
    t = ff.embedding(ids, vocab_size, hidden_size, name="tok_embed")
    for i in range(num_hidden_layers):
        a = ff.rms_norm(t, eps, name=f"input_norm_{i}",
                        zero_centered=norm_add_unit_offset)
        a = ff.eva_attention(a, eva, name=f"attn_{i}",
                             slot_state=decode_max_seq > 0,
                             max_seq=decode_max_seq)
        t = ff.add(t, a, name=f"attn_res_{i}")
        h = ff.rms_norm(t, eps, name=f"post_norm_{i}",
                        zero_centered=norm_add_unit_offset)
        h = ff.gated_mlp(h, intermediate_size, name=f"mlp_{i}")
        t = ff.add(t, h, name=f"mlp_res_{i}")
    t = ff.rms_norm(t, eps, name="final_norm",
                    zero_centered=norm_add_unit_offset)
    logits = ff.dense(t, vocab_size * num_pred_heads, use_bias=False,
                      name="lm_head")

    # what a decode twin is built from (decoding.make_decoder): this
    # builder again, at seq 1 with per-slot state.  `prefill_pass`:
    # every op of this graph is per-token or takes the step's length
    # from its input (the attention reads the window as it was and the
    # step's own keys beside it).  Not `prefix_cache`, `speculative` or
    # `handoff`: a sequence's state is no set of pages, and nothing
    # snapshots, exports or rolls back a window and its summaries yet
    # (ROADMAP R3, R4)
    ff.decoder_recipe = DecoderRecipe(
        family="evabyte", build=build_evabyte,
        kwargs=dict(
            hidden_size=hidden_size, num_hidden_layers=num_hidden_layers,
            num_attention_heads=num_attention_heads,
            num_key_value_heads=num_key_value_heads,
            intermediate_size=intermediate_size, vocab_size=vocab_size,
            num_pred_heads=num_pred_heads, window_size=window_size,
            chunk_size=chunk_size, rope_theta=rope_theta,
            rms_norm_eps=rms_norm_eps,
            norm_add_unit_offset=norm_add_unit_offset,
            max_position_embeddings=max_position_embeddings),
        dims={"num_layers": num_hidden_layers, "hidden_size": hidden_size,
              "num_heads": num_attention_heads,
              "num_kv_heads": num_key_value_heads, "vocab_size": vocab_size,
              "max_seq": max_position_embeddings,
              "window_size": window_size, "chunk_size": chunk_size},
        carries=frozenset({"paged", "chunked_prefill", "prefill_pass"}),
        logit_columns=vocab_size, head=("final_norm", "lm_head"))
    return logits
