"""Transformer / BERT model builders.

`build_transformer` mirrors the reference's Transformer example
(/root/reference/examples/cpp/Transformer/transformer.cc:112-215 —
create_attention_encoder: multihead_attention + two dense layers, no
norm/residual; default cfg at transformer.cc:79-85).

`build_bert` is the BERT-base north-star config (BASELINE.md): post-LN
encoder blocks (attention + residual, then layernorm; 4x GELU FFN),
which is both the real workload and the TP/SP search target.
"""
from __future__ import annotations

from ..decoding import DecoderRecipe
from ..fftype import ActiMode
from ..model import FFModel


def build_transformer(
    ff: FFModel,
    batch_size: int = 8,
    seq_length: int = 512,
    hidden_size: int = 1024,
    num_layers: int = 12,
    num_heads: int = 16,
):
    """The reference example: N x (attention -> dense(relu) -> dense)."""
    t = ff.create_tensor([batch_size, seq_length, hidden_size], name="input")
    for i in range(num_layers):
        a = ff.multihead_attention(
            t, t, t, hidden_size, num_heads, name=f"attn_{i}"
        )
        h = ff.dense(a, hidden_size, activation=ActiMode.RELU, name=f"ffn1_{i}")
        t = ff.dense(h, hidden_size, name=f"ffn2_{i}")
    out = ff.dense(t, 1, name="lm_head")
    return out


def build_bert(
    ff: FFModel,
    batch_size: int = 32,
    seq_length: int = 128,
    hidden_size: int = 768,
    num_layers: int = 12,
    num_heads: int = 12,
    intermediate_size: int = 3072,
    vocab_size: int = 30522,
    num_classes: int = 2,
    dropout: float = 0.0,
    from_token_ids: bool = False,
):
    """BERT-base encoder stack with a classification head."""
    if from_token_ids:
        ids = ff.create_tensor([batch_size, seq_length], dtype="int32", name="input")
        t = ff.embedding(ids, vocab_size, hidden_size, name="tok_embed")
    else:
        t = ff.create_tensor([batch_size, seq_length, hidden_size], name="input")
    for i in range(num_layers):
        # attention block (post-LN, BERT style)
        a = ff.multihead_attention(
            t, t, t, hidden_size, num_heads, dropout=dropout, name=f"attn_{i}"
        )
        t = ff.add(t, a, name=f"attn_res_{i}")
        t = ff.layer_norm(t, axes=[-1], name=f"attn_ln_{i}")
        # FFN block
        h = ff.dense(t, intermediate_size, activation=ActiMode.GELU, name=f"ffn1_{i}")
        h = ff.dense(h, hidden_size, name=f"ffn2_{i}")
        t = ff.add(t, h, name=f"ffn_res_{i}")
        t = ff.layer_norm(t, axes=[-1], name=f"ffn_ln_{i}")
    # classifier on mean-pooled sequence
    pooled = ff.mean(t, axes=[1], name="pool")
    logits = ff.dense(pooled, num_classes, name="classifier")
    return logits


def bert_tp_strategy(num_devices: int, tp: int = 2, num_layers: int = 12):
    """Hybrid DP x TP strategy for build_bert: attention heads and FFN
    out-channels column-parallel on the model axis, second FFN matmul
    row-parallel automatically, batch data-parallel."""
    from ..ops.op import ShardConfig
    from ..strategy import Strategy

    dp = num_devices // tp
    s = Strategy(mesh_axes={"data": dp, "model": tp})
    s.edge_ops["__inputs__"] = [("repartition", {"dim": 0, "degree": dp})]
    for i in range(num_layers):
        s.shard_configs[f"attn_{i}"] = ShardConfig(channel=tp)
        s.shard_configs[f"ffn1_{i}"] = ShardConfig(channel=tp)
    return s


def bert_sp_strategy(num_devices: int, sp: int = 4):
    """Hybrid DP x SP (context-parallel) strategy: the sequence dim of
    every activation is sharded over the "seq" axis and attention runs
    as ring attention over ICI (parallel/ring_attention.py) — the
    long-context capability slot the reference lacks (SURVEY §5)."""
    from ..strategy import Strategy

    if sp < 1 or num_devices % sp != 0:
        raise ValueError(
            f"num_devices {num_devices} not divisible by sp degree {sp}"
        )
    dp = num_devices // sp
    s = Strategy(mesh_axes={"data": dp, "seq": sp})
    chain = []
    if dp > 1:
        chain.append(("repartition", {"dim": 0, "degree": dp}))
    chain.append(("repartition", {"dim": 1, "degree": sp}))
    s.edge_ops["__inputs__"] = chain
    return s


def build_gpt(
    ff: FFModel,
    batch_size: int = 8,
    seq_length: int = 1024,
    hidden_size: int = 768,
    num_layers: int = 12,
    num_heads: int = 12,
    intermediate_size: int = 3072,
    vocab_size: int = 50257,
    dropout: float = 0.0,
    max_positions: int = 0,
    decode_max_seq: int = 0,
    kv_page_size: int = 0,
    kv_num_blocks: int = 0,
    kv_kernel: str = "gather",
):
    """Decoder-only causal LM (pre-LN GPT-2 shape) — a model family
    BEYOND the reference's zoo (its transformer example is encoder-only,
    examples/cpp/Transformer/transformer.cc): token ids + position ids
    -> embeddings -> N x [LN -> causal attention -> residual;
    LN -> GELU MLP -> residual] -> final LN -> untied LM head.

    Layer names reuse the attn_{i}/ffn1_{i} convention so
    bert_tp_strategy/bert_sp_strategy apply unchanged (causal ring
    attention handles the sharded-sequence case).  Train with
    labels = ids shifted left one position (next-token prediction);
    the sparse-CE loss consumes [b, s, vocab] logits and [b, s] ids.
    """
    ids = ff.create_tensor([batch_size, seq_length], dtype="int32",
                           name="input")
    pos = ff.create_tensor([batch_size, seq_length], dtype="int32",
                           name="positions")
    t = ff.embedding(ids, vocab_size, hidden_size, name="tok_embed")
    # max_positions decouples the position table from the graph's seq
    # length so a seq-1 KV-cache decode graph shares the trained table
    pe = ff.embedding(pos, max_positions or seq_length, hidden_size,
                      name="pos_embed")
    t = ff.add(t, pe, name="embed_sum")
    for i in range(num_layers):
        a = ff.layer_norm(t, axes=[-1], name=f"ln1_{i}")
        a = ff.multihead_attention(
            a, a, a, hidden_size, num_heads, dropout=dropout,
            causal=True, name=f"attn_{i}",
            decode_max_seq=decode_max_seq,
            kv_page_size=kv_page_size, kv_num_blocks=kv_num_blocks,
            kv_kernel=kv_kernel,
        )
        t = ff.add(t, a, name=f"attn_res_{i}")
        h = ff.layer_norm(t, axes=[-1], name=f"ln2_{i}")
        h = ff.dense(h, intermediate_size, activation=ActiMode.GELU,
                     name=f"ffn1_{i}")
        h = ff.dense(h, hidden_size, name=f"ffn2_{i}")
        t = ff.add(t, h, name=f"ffn_res_{i}")
    t = ff.layer_norm(t, axes=[-1], name="final_ln")
    logits = ff.dense(t, vocab_size, use_bias=False, name="lm_head")
    # what a decode twin is built from (decoding.make_decoder): this
    # builder again, without dropout, over the same position table
    max_seq = max_positions or seq_length
    ff.decoder_recipe = DecoderRecipe(
        family="gpt", build=build_gpt,
        kwargs=dict(hidden_size=hidden_size, num_layers=num_layers,
                    num_heads=num_heads,
                    intermediate_size=intermediate_size,
                    vocab_size=vocab_size, dropout=0.0,
                    max_positions=max_seq),
        dims={"num_layers": num_layers, "hidden_size": hidden_size,
              "num_heads": num_heads, "dropout": dropout,
              "vocab_size": vocab_size, "max_seq": max_seq,
              "intermediate_size": intermediate_size},
        carries=frozenset({
            "dense_cache", "paged", "prefix_cache", "chunked_prefill",
            "chunk_twin", "speculative", "tensor_parallel", "handoff",
            "disaggregated", "beam_search", "pallas_read"}))
    return logits


def gpt_generate(ff: FFModel, prompt_ids, max_new_tokens: int,
                 temperature: float = 0.0, seed: int = 0,
                 top_k: int = 0, top_p: float = 0.0):
    """Autoregressive generation with the compiled fixed-shape GPT
    graph: right-pad the prompt to the model's seq_length, re-run the
    forward per emitted token, and feed back the sampled id
    (temperature 0 = greedy argmax).  The causal mask makes padding
    beyond the current position irrelevant to the next-token logits.
    O(T^2) utility loop like models/nmt.greedy_decode — correct, not a
    KV-cache serving path.

    Sampling controls compose the usual way: logits/temperature, then
    top_k (keep the k most likely ids, 0 = off), then top_p nucleus
    filtering (smallest sorted prefix with mass >= top_p, 0 = off);
    both apply only when temperature > 0.

    prompt_ids: [batch, prompt_len] ints.  Returns [batch,
    prompt_len + max_new_tokens] (truncated at the model's seq_length).
    """
    import numpy as np

    prompt_ids = np.asarray(prompt_ids, np.int32)
    validate_sampling(top_k, top_p)
    ids_src = next(op for op in ff.layers.source_ops()
                   if op.name == "input")
    seq_len = ids_src.outputs[0].shape.logical_shape[1]
    prompt_ids = prompt_ids[:, :seq_len]  # docstring contract
    batch, plen = prompt_ids.shape
    if plen < 1:
        raise ValueError("gpt_generate needs a non-empty prompt")
    total = min(seq_len, plen + max_new_tokens)
    buf = np.zeros((batch, seq_len), np.int32)
    buf[:, :plen] = prompt_ids
    pos = np.tile(np.arange(seq_len, dtype=np.int32), (batch, 1))
    rng = np.random.RandomState(seed)
    for t in range(plen, total):
        logits = np.asarray(
            ff.forward({"input": buf, "positions": pos}), np.float32)
        step = logits[:, t - 1]  # next-token distribution at position t-1
        buf[:, t] = sample_next(step, temperature, rng, top_k, top_p)
    return buf[:, :total]


def validate_sampling(top_k: int, top_p: float):
    if top_k < 0 or not 0.0 <= top_p <= 1.0:
        raise ValueError(f"invalid sampling filter: top_k={top_k} "
                         f"top_p={top_p}")


def sample_next(step_logits, temperature: float, rng, top_k: int = 0,
                top_p: float = 0.0):
    """Sample next-token ids from [batch, vocab] logits (numpy host
    path shared by gpt_generate and the KV-cache decoder): temperature,
    then top_k, then top_p nucleus; temperature 0 = greedy."""
    import numpy as np

    if temperature <= 0.0:
        return step_logits.argmax(-1).astype(np.int32)
    # float32, matching the pre-extraction inline path: seeded runs
    # recorded against it stay reproducible (np.random.choice converts
    # p to double internally, so the f32 sum-to-1 rounding is tolerated)
    z = np.asarray(step_logits, np.float32) / temperature
    if top_k and top_k < z.shape[-1]:
        # keep the k most likely ids per row
        kth = np.partition(z, -top_k, axis=-1)[:, -top_k, None]
        z = np.where(z < kth, -np.inf, z)
    z = z - z.max(-1, keepdims=True)
    p = np.exp(z)
    p /= p.sum(-1, keepdims=True)
    if top_p and 0.0 < top_p < 1.0:
        # nucleus: smallest sorted prefix with mass >= top_p
        order = np.argsort(-p, axis=-1)
        sp = np.take_along_axis(p, order, -1)
        drop_sorted = np.cumsum(sp, axis=-1) - sp >= top_p
        drop = np.zeros_like(drop_sorted)
        np.put_along_axis(drop, order, drop_sorted, -1)
        p = np.where(drop, 0.0, p)
        p /= p.sum(-1, keepdims=True)
    return np.array([rng.choice(p.shape[-1], p=p[b])
                     for b in range(p.shape[0])], np.int32)


def gpt_beam_search(ff: FFModel, prompt_ids, max_new_tokens: int,
                    beam_size: int = 4, length_penalty: float = 0.0,
                    eos_id: int = -1):
    """Beam-search decoding on the compiled fixed-shape GPT graph
    (beyond the reference: its legacy nmt/ decoder is greedy-only).

    O(T^2) reference implementation: it re-runs the full forward per
    emitted token and takes one prompt.  The serving path is
    decoding.gpt_beam_search_cached — O(T) on the KV-cache decode twin,
    batched over prompts, equality-tested against this function.

    Beams ride the model's batch dimension: all `beam_size` hypotheses
    of one prompt decode in a single forward per step, so the compiled
    batch size must be >= beam_size (extra rows are padding).  Scores
    are summed token log-probs; `length_penalty` applies the GNMT
    normalization ((5+len)/6)^lp to final scores; `eos_id` >= 0
    freezes finished beams (they compete with their frozen score).

    prompt_ids: [prompt_len] or [1, prompt_len] ints (single prompt).
    Returns (tokens [total_len], score float).
    """
    import numpy as np

    prompt_ids = np.asarray(prompt_ids, np.int32).reshape(1, -1)
    ids_src = next(op for op in ff.layers.source_ops()
                   if op.name == "input")
    model_batch = ids_src.outputs[0].shape.logical_shape[0]
    seq_len = ids_src.outputs[0].shape.logical_shape[1]
    if beam_size > model_batch:
        raise ValueError(
            f"beam_size {beam_size} exceeds compiled batch {model_batch}")
    prompt_ids = prompt_ids[:, :seq_len]
    plen = prompt_ids.shape[1]
    if plen < 1:
        raise ValueError("gpt_beam_search needs a non-empty prompt")
    total = min(seq_len, plen + max_new_tokens)

    buf = np.zeros((model_batch, seq_len), np.int32)
    buf[:beam_size, :plen] = prompt_ids  # every beam starts from the prompt
    pos = np.tile(np.arange(seq_len, dtype=np.int32), (model_batch, 1))
    scores = np.full(beam_size, -np.inf, np.float64)
    scores[0] = 0.0  # step 1: only one distinct hypothesis exists
    alive = np.ones(beam_size, bool)
    gen_len = np.zeros(beam_size, np.int64)  # emitted tokens per beam

    for t in range(plen, total):
        logits = np.asarray(
            ff.forward({"input": buf, "positions": pos}), np.float32)
        step = logits[:beam_size, t - 1]
        z = step - step.max(-1, keepdims=True)
        lp = z - np.log(np.exp(z).sum(-1, keepdims=True))  # [beam, vocab]
        vocab = lp.shape[-1]
        cand = scores[:, None] + np.where(alive[:, None], lp, -np.inf)
        if eos_id >= 0 and not alive.all():
            # a finished beam competes as one stay-put candidate
            cand[~alive, :] = -np.inf
            cand[~alive, 0] = scores[~alive]
        flat = cand.reshape(-1)
        top = np.argsort(-flat)[:beam_size]
        src_beam, tok = top // vocab, (top % vocab).astype(np.int32)
        new_buf = buf[:beam_size][src_beam].copy()
        new_alive = alive[src_beam].copy()
        new_buf[new_alive, t] = tok[new_alive]  # frozen beams keep padding
        gen_len = gen_len[src_beam] + new_alive  # explicit per-beam length
        if eos_id >= 0:
            new_alive &= tok != eos_id
        buf[:beam_size] = new_buf
        scores = flat[top]
        alive = new_alive
        if eos_id >= 0 and not alive.any():
            break
    if length_penalty > 0.0:
        norm = ((5.0 + np.maximum(gen_len, 1).astype(np.float64)) / 6.0) \
            ** length_penalty
        best = int(np.argmax(scores / norm))
    else:
        best = int(np.argmax(scores))
    return buf[best, :total].copy(), float(scores[best])
