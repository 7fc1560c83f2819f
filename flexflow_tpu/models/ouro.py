"""The `ouro` looped language model (Ouro-1.4B / 2.6B, "LoopLM"): ONE
stack of decoder layers that every token crosses `total_ut_steps`
times, the same weights each time, with an exit gate read after every
pass.  Text ids in, logits out.

`build_ouro` takes the keys of the published `config.json` under their
own names.  Every norm is an RMSNorm with a plain gain of its own; no
projection has a bias; attention is plain multi-head (as many key/value
heads as query heads) with a rotary embedding on all of a head's
channels, first half against second half.

    layer i, pass t (the SAME weights for every t):
        a = RMS(x);  x = x + RMS( Attention_i(a) )       sandwich norm:
        m = RMS(x);  x = x + RMS( GatedMLP_i(m) )        the OUTPUT is normed
    h = tok_embed[ids]
    for t in 0 .. total_ut_steps - 1:
        h = layers_0..L-1(h);  h = RMS_final(h)    (normed, h starts pass t + 1)
        g_t = sigmoid(h w_gate + b_gate)
    logits = h lm_head                             after the last pass

The passes are a region of the graph (`FFModel.repeat`): the layers and
the final norm exist once, the executor scans them, and in a decode
twin each layer's paged pool holds a plane of keys and values a pass.
The exit gate is computed for every pass and never acted on:
`early_exit_threshold` 1.0, the published value, is reached by the exit
distribution's cdf only at the last pass, so every token runs them
all.  Any other threshold is a `ConfigError` (rows that leave a
dispatch early: ROADMAP).
"""
from __future__ import annotations

from ..decoding import DecoderRecipe
from ..model import FFModel

#: the op whose output is the gate's logit after every pass
EXIT_GATE = "early_exit_gate"


def build_ouro(
    ff: FFModel,
    batch_size: int = 1,
    seq_length: int = 1,
    *,
    hidden_size: int = 2048,
    num_hidden_layers: int = 48,
    num_attention_heads: int = 16,
    num_key_value_heads: int = 16,
    head_dim: int = 128,
    intermediate_size: int = 5632,
    rms_norm_eps: float = 1e-6,
    rope_theta: float = 1000000.0,
    total_ut_steps: int = 4,
    early_exit_threshold: float = 1.0,
    vocab_size: int = 49152,
    max_position_embeddings: int = 65536,
    decode_max_seq: int = 0,
    kv_page_size: int = 0,
    kv_num_blocks: int = 0,
    kv_kernel: str = "gather",
):
    from ..config import ConfigError

    if early_exit_threshold != 1.0:
        raise ConfigError(
            f"ouro: early_exit_threshold {early_exit_threshold} is not "
            "built: only 1.0 (every token runs every pass; the gate is "
            "computed and reported, never acted on)")
    if decode_max_seq and not kv_page_size:
        raise ConfigError(
            "ouro does not carry the dense per-slot cache "
            "(decode_max_seq without kv_page_size): a layer caches a "
            "plane of keys and values a pass in the paged pool; build "
            "the twin with kv_page_size > 0")
    if seq_length > max_position_embeddings:
        raise ConfigError(
            f"ouro: seq_length {seq_length} passes "
            f"max_position_embeddings {max_position_embeddings}")
    eps = rms_norm_eps
    attention = dict(
        kdim=num_attention_heads * head_dim,
        vdim=num_attention_heads * head_dim, causal=True,
        num_kv_heads=num_key_value_heads, rotary_dim=head_dim,
        rope_theta=float(rope_theta), paged_read_once=True)

    ids = ff.create_tensor([batch_size, seq_length], dtype="int32",
                           name="input")
    t = ff.embedding(ids, vocab_size, hidden_size, name="tok_embed")
    with ff.repeat(t, total_ut_steps, name="ut_loop") as loop:
        for i in range(num_hidden_layers):
            a = ff.rms_norm(t, eps, name=f"input_norm_{i}")
            a = ff.multihead_attention(
                a, a, a, hidden_size, num_attention_heads,
                name=f"attn_{i}", decode_max_seq=decode_max_seq,
                kv_page_size=kv_page_size, kv_num_blocks=kv_num_blocks,
                kv_kernel=kv_kernel, **attention)
            a = ff.rms_norm(a, eps, name=f"attn_out_norm_{i}")
            t = ff.add(t, a, name=f"attn_res_{i}")
            m = ff.rms_norm(t, eps, name=f"post_norm_{i}")
            m = ff.gated_mlp(m, intermediate_size, name=f"mlp_{i}")
            m = ff.rms_norm(m, eps, name=f"mlp_out_norm_{i}")
            t = ff.add(t, m, name=f"mlp_res_{i}")
        t = ff.rms_norm(t, eps, name="final_norm")
        loop.carry(t)
    ff.dense(loop.passes(), 1, use_bias=True, name=EXIT_GATE)
    logits = ff.dense(t, vocab_size, use_bias=False, name="lm_head")
    ff.set_output(logits)  # the gate is a sink too: read, not consumed

    # what a decode twin is built from (decoding.make_decoder): this
    # builder again, at seq 1 with paged state.  `prefill_pass`: every
    # op is per-token or takes the step's length from its input (the
    # attention's one-view read).  `pallas_read`: one head count, so the
    # in-place kernel reads a plane's pages.  `prefix_cache`: a block of
    # the table holds every plane of its positions, so a page hit
    # brings all of a prefix's state and copy-on-write copies all of it
    ff.decoder_recipe = DecoderRecipe(
        family="ouro", build=build_ouro,
        kwargs=dict(
            hidden_size=hidden_size, num_hidden_layers=num_hidden_layers,
            num_attention_heads=num_attention_heads,
            num_key_value_heads=num_key_value_heads, head_dim=head_dim,
            intermediate_size=intermediate_size, rms_norm_eps=rms_norm_eps,
            rope_theta=rope_theta, total_ut_steps=total_ut_steps,
            early_exit_threshold=early_exit_threshold,
            vocab_size=vocab_size,
            max_position_embeddings=max_position_embeddings),
        dims={"num_layers": num_hidden_layers, "hidden_size": hidden_size,
              "num_heads": num_attention_heads,
              "num_kv_heads": num_key_value_heads, "vocab_size": vocab_size,
              "max_seq": max_position_embeddings,
              "loop_steps": total_ut_steps},
        carries=frozenset({"paged", "chunked_prefill", "prefill_pass",
                           "pallas_read", "prefix_cache"}),
        exit_gate=EXIT_GATE,
        # (the final norm is the region's last op: it runs every pass)
        head=(EXIT_GATE, "lm_head"))
    return logits
