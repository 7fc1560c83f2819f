"""KV-cache incremental decoding (TPU-native serving machinery).

The reference has no incremental decoder at all — its legacy nmt/
re-runs the full graph per emitted token and triton/ is an incomplete
prototype.  Here decoding is a first-class graph mode: attention ops
built with decode_max_seq=N carry fixed-shape [b, N, h, d] k/v caches
plus a position counter in the op-state pytree (the same functional
state channel BatchNorm running stats use), so one decode step is a
seq-1 forward that appends to the caches — O(T) generation instead of
the O(T^2) re-forward loop of models.transformer.gpt_generate.

Two drivers:
  * gpt_generate_cached — host loop over FFModel.decode_step (one
    device round trip per token; simple, streams tokens);
  * gpt_generate_scan — the WHOLE generation (prefill + sample loop)
    as ONE jitted lax.scan program: zero host round trips until the
    final token buffer lands: T host dispatches and syncs become 1.

`make_decoder` builds the seq-1 decode twin of a model a `models/`
builder built: the builder the model recorded (`DecoderRecipe`), called
again at seq 1 with cache state, and handed the model's weights (shapes
are seq-independent).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from .fftype import LossType
from .model import FFModel
from .obs import scopes
from .obs.trace import span
from .optimizer import SGDOptimizer


@dataclasses.dataclass(frozen=True)
class DecoderRecipe:
    """What a `models/` builder records on the model it builds
    (`ff.decoder_recipe`), so that a decode twin is THAT BUILDER again
    at seq 1 with cache state, not a graph recognised by its op names.

    `build(ff, batch_size=, seq_length=, **kwargs, decode_max_seq=,
    kv_page_size=, kv_num_blocks=, kv_kernel=)` rebuilds the graph;
    `kwargs` are twin-ready (no dropout, the trained position range);
    `dims` is what the serving tier reads (`num_layers`, `num_heads`,
    `vocab_size`, `max_seq`, ...); `carries` names what the family
    supports beyond being built (`require_carried`), and what its
    graph allows: `prefill_pass` = the seq-1 twin's graph over [b, C]
    inputs is the seq-C forward (build_paged_prefill_pass).
    `exit_gate` names the op, if the family has one, whose output
    `[passes, b, s, 1]` is an exit gate's logit after every pass of a
    repeated region: the decode step program turns it into each row's
    exit pdf (`exit_pdf`) and returns that beside the logits.
    `logit_columns` > 0: the decode step returns the head's first that
    many columns only (a head that predicts several positions side by
    side, of which the server samples the next: `dims["vocab_size"]`).
    `head` names the ops after the last layer (final norm, head, exit
    gate), every one per token: the prefill pass runs them on each
    row's last real position, not on the chunk
    (build_paged_prefill_pass; a family on the pass names them)."""

    family: str
    build: Callable
    kwargs: Dict
    dims: Dict
    carries: frozenset
    exit_gate: str = ""
    logit_columns: int = 0
    head: Tuple[str, ...] = ()


def decoder_recipe(ff: FFModel) -> DecoderRecipe:
    recipe = getattr(ff, "decoder_recipe", None)
    if recipe is None and getattr(ff, "not_served", None):
        from .config import ConfigError

        raise ConfigError(ff.not_served)  # the builder said why, by name
    if recipe is None:
        from .models import SERVED_BUILDERS

        raise ValueError(
            "a decode twin needs a model built by a models/ builder that "
            "records its recipe ("
            + ", ".join(f"models.{b}" for b in SERVED_BUILDERS) + ")")
    return recipe


def _gpt_dims(ff: FFModel) -> Dict[str, int]:
    """The builder's hyperparameters, from the recipe it recorded."""
    return decoder_recipe(ff).dims


def require_carried(ff: FFModel, feature: str, asked_by: str) -> None:
    """ConfigError, by name, where a serving feature was asked of a
    model family that does not carry it: never a silent fallback."""
    recipe = decoder_recipe(ff)
    if feature not in recipe.carries:
        from .config import ConfigError

        raise ConfigError(
            f"{recipe.family} does not carry {feature} ({asked_by}); it "
            f"carries {sorted(recipe.carries)}")


def cache_entries(ff: FFModel) -> Dict[str, tuple]:
    """{op name: its state entries that hold cached keys, values or
    latents}: on a paged twin, the block pools.  THE predicate for
    "this state entry is a paged pool" (block bytes, copy-on-write,
    block export and import, beam reordering): asked of the op, so a
    latent pool is found like a k/v pool."""
    return {op.name: op.cache_entries()
            for op in ff.operators.topo_order() if op.cache_entries()}


def cache_planes(ff: FFModel) -> Dict[str, int]:
    """{op name: planes each of its pools holds} for the ops of
    `cache_entries`: 1, or the passes of the region that runs the op
    (`Op.cache_planes`).  Row `t x num_blocks + b` of a pool is block
    b's page in plane t, so whatever moves a block (copy-on-write,
    export, import) moves that row of every plane."""
    return {op.name: op.cache_planes
            for op in ff.operators.topo_order() if op.cache_entries()}


def exit_pdf(gate_logits):
    """gate_logits [passes, b, s, 1] (any float dtype) -> [b, passes]
    float32 at the step's first position: the probability that a row
    leaves after pass t, `g_t prod_{j<t} (1 - g_j)` with `g = sigmoid`,
    the last pass taking what is left."""
    import jax
    import jax.numpy as jnp

    g = jax.nn.sigmoid(gate_logits[:, :, 0, 0].astype(jnp.float32))
    stay = jnp.cumprod(1.0 - g, axis=0)                  # prod_{j<=t}
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    pdf = jnp.concatenate([(g * before)[:-1], before[-1:]])
    return pdf.T


def _exit_gate_guid(ffd: FFModel) -> Optional[int]:
    """The guid of the recipe's exit-gate output in the compiled twin,
    None for a family without one."""
    name = decoder_recipe(ffd).exit_gate
    if not name:
        return None
    (op,) = [op for op in ffd.operators.topo_order() if op.name == name]
    return op.outputs[0].guid


def slot_state_entries(ff: FFModel) -> Dict[str, tuple]:
    """{op name: its state entries that are per slot and fixed size}
    (`Op.slot_state_entries`): a recurrent layer's state, allocated
    `[slots, ...]` beside the pools.  THE predicate for "the scheduler
    zeroes this at admission" (`build_slot_state_reset`); disjoint from
    `cache_entries`, so nothing that handles pages touches it.  A twin
    that has any also takes `row_tokens` in its step programs."""
    return {op.name: op.slot_state_entries()
            for op in ff.operators.topo_order() if op.slot_state_entries()}


def gpt_decode_tp_strategy(tp: int, num_layers: int):
    """Head-tensor-parallel strategy for a decode twin: one replica
    spans tp chips on a {"data": 1, "model": tp} mesh — attention
    heads and FFN out-channels column-parallel on the model axis
    (ffn2 row-parallel automatically), and every paged KV pool's head
    dim rides the same axis (ops/attention._paged_state_specs), so
    per-chip KV bytes are 1/tp.  The bert_tp_strategy shape with the
    data axis degenerate: decode batches are slot-owned, never
    repartitioned."""
    from .ops.op import ShardConfig
    from .strategy import Strategy

    s = Strategy(mesh_axes={"data": 1, "model": int(tp)})
    for i in range(num_layers):
        s.shard_configs[f"attn_{i}"] = ShardConfig(channel=tp)
        s.shard_configs[f"ffn1_{i}"] = ShardConfig(channel=tp)
    return s


def make_decoder(ff_train: FFModel, batch_size: Optional[int] = None,
                 devices=None, kv_page_size: int = 0,
                 kv_num_blocks: int = 0,
                 step_tokens: int = 1,
                 kv_kernel: str = "gather",
                 tp: int = 1) -> FFModel:
    """Build + compile the cached decode twin of a model a `models/`
    builder built (whatever the family: the twin is the builder the
    model recorded, `ff.decoder_recipe`, at seq `step_tokens` with
    cache state) and hand it the model's weights.  decode_max_seq = the
    model's position range.  What a family does not carry (a dense
    cache, the chunk twin, tp > 1) is a ConfigError here, by name.

    A model compiled with `defer_weights=True` (a server's holder:
    weights set once, in the precision they are served in) gets a twin
    compiled the same way: nothing is drawn at random only to be
    overwritten, and the twin takes the holder's arrays as they are,
    so the weights are resident once.

    kv_page_size > 0 builds the PAGED twin (serving/scheduler.py):
    every attention layer's k/v cache is a [kv_num_blocks,
    kv_page_size, h, d] block pool with a host-owned per-slot block
    table + seq_lens instead of a dense per-slot [b, max_seq, h, d]
    buffer — continuous batching's allocation substrate.

    step_tokens > 1 (paged mode only) builds the [b, C] CHUNKED twin:
    one step scatters C tokens at each row's own positions and attends
    causally within the chunk — the multi-token prefill shape
    (build_paged_chunk_step).  Its state pytree is congruent with the
    seq-1 twin's (pools, tables and seq_lens are all seq-independent),
    so both programs thread one shared state.  That twin returns
    logits and is GPT's (`chunk_twin`).  The ENGINE's chunked prefill
    needs no second twin: GPT scans this seq-1 twin's step
    (build_paged_prefill_step, byte-equal to one-token prefill); a
    family whose recipe carries `prefill_pass` (kimi_k2) runs this
    same twin once over [b, C] (build_paged_prefill_pass), so its
    weights stay resident once and are streamed once a dispatch.

    kv_kernel is the paged READ formulation (docs/SERVING.md "Fused
    paged attention"), already decided, as the ops take it: "gather"
    (default) is the dense block-gather oracle; "pallas" streams blocks
    in place through the fused kernel.  The engine decides it
    (serving/scheduler.py pick_paged_read).

    tp > 1 compiles the twin over a tp-chip {"data": 1, "model": tp}
    replica mesh under GSPMD (docs/SERVING.md "Tensor-parallel
    replicas"): heads, FFN channels and the KV pools' head dims shard
    over the model axis, per-chip KV bytes drop to 1/tp, and greedy
    decoding stays token-identical to the tp=1 twin.  The strategy is
    served through the strategy store keyed by the decode graph x the
    replica mesh fingerprint (store/key.py) — the same consult-then-
    publish path training compiles use at spin-up.  Validated against
    the head count and visible devices HERE (resolve_serving_tp) —
    never a mid-compile shape error."""
    from .config import FFConfig, resolve_serving_tp

    recipe = decoder_recipe(ff_train)
    if step_tokens < 1:
        raise ValueError(f"step_tokens must be >= 1, got {step_tokens}")
    if step_tokens > 1 and not kv_page_size:
        raise ValueError(
            "step_tokens > 1 needs the paged twin (kv_page_size > 0): "
            "the dense cache's scalar position counter cannot express "
            "per-row chunk positions")
    if kv_kernel not in ("gather", "pallas"):
        raise ValueError(
            f"kv_kernel must be 'gather' or 'pallas', got {kv_kernel!r}")
    if kv_kernel != "gather" and not kv_page_size:
        raise ValueError(
            f"kv_kernel={kv_kernel!r} needs the paged twin "
            "(kv_page_size > 0): the dense cache has no block table "
            "to stream through")
    dims = recipe.dims
    if not kv_page_size:
        require_carried(ff_train, "dense_cache", "kv_page_size=0")
    if step_tokens > 1:
        require_carried(ff_train, "chunk_twin", f"step_tokens={step_tokens}")
    tp = resolve_serving_tp(
        tp, num_heads=dims["num_heads"],
        visible_devices=len(devices) if devices is not None else None,
    )
    if tp > 1:
        require_carried(ff_train, "tensor_parallel", f"--serving-tp {tp}")
    b = batch_size or ff_train.config.batch_size
    cfg = FFConfig(
        batch_size=b, num_devices=tp,
        compute_dtype=ff_train.config.compute_dtype,
        only_data_parallel=(tp == 1),
        # replica cold start (docs/STORE.md): the twin's compile keeps
        # the train model's artifact-store wiring, so its decode step
        # reloads from the XLA persistent cache on spin-up instead of
        # recompiling (tp=1 never searches — the compilation cache is
        # the piece that matters there; tp>1 additionally restores its
        # sharding strategy through the store below)
        strategy_store=ff_train.config.strategy_store,
        compilation_cache=ff_train.config.compilation_cache,
    )
    ffd = FFModel(cfg)
    recipe.build(
        ffd, batch_size=b, seq_length=step_tokens, **recipe.kwargs,
        decode_max_seq=dims["max_seq"],
        kv_page_size=kv_page_size, kv_num_blocks=kv_num_blocks,
        kv_kernel=kv_kernel,
    )
    strategy = None
    if tp > 1:
        # consult-then-publish through the strategy store, keyed by the
        # DECODE graph x the replica's tp-chip mesh fingerprint — a new
        # replica at the same tp restores the layout instead of
        # rebuilding it (FFModel.compile skips the store for explicit
        # strategies, so the decoder routes through it here)
        from .store import cached_search

        strategy = cached_search(
            ffd, tp,
            lambda: gpt_decode_tp_strategy(tp, dims["num_layers"]),
        )
    ffd.compile(
        optimizer=SGDOptimizer(lr=0.0),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        strategy=strategy,
        devices=devices,
        defer_weights=ff_train.weights_deferred,
    )
    # weight transfer by (op, spec) name — all shapes are
    # seq-independent, so the trained pytree drops straight in.
    # Each entry is device_put onto the DECODE twin's sharding (the
    # compile-initialized placeholder carries it): on a tp replica
    # mesh this shards the trained weights over the model axis; at
    # tp=1 it is the identity placement.
    with span("serve.copy_weights"):
        ffd._weights = _transfer_weights(ffd, ff_train, tp)
    return ffd


def _transfer_weights(ffd: FFModel, ff_train: FFModel, tp: int) -> Dict:
    """The decode twin's weight pytree filled from the trained model's,
    each entry on the twin's own sharding.  A twin whose weights are
    deferred takes every entry in the dtype it is held in (the
    precision the server computes in; a router kept float32)."""
    import jax

    if ff_train._weights is None:
        raise ValueError(
            "the model was compiled with defer_weights=True and has no "
            "weights yet: set_weights() before building a server")
    deferred = ffd.weights_deferred
    missing = []
    new_w = {}
    for op_name, entries in ffd.executor.abstract_weights().items():
        src = ff_train._weights.get(op_name)
        new_entries = {}
        for k, v in entries.items():
            if src is None or k not in src:
                missing.append(f"{op_name}.{k}")
                new_entries[k] = v
                continue
            sv = src[k]
            if tuple(sv.shape) != tuple(v.shape):
                raise ValueError(
                    f"decode weight {op_name}.{k}: trained shape "
                    f"{tuple(sv.shape)} != decode shape {tuple(v.shape)}"
                )
            if not deferred and sv.dtype != v.dtype:
                sv = sv.astype(v.dtype)
            if tp > 1:
                sv = jax.device_put(np.asarray(sv), v.sharding)
            new_entries[k] = sv
        new_w[op_name] = new_entries
    if missing:
        raise ValueError(f"decode graph weights missing in trained "
                         f"model: {missing}")
    return new_w


def gpt_generate_cached(ffd: FFModel, prompt_ids, max_new_tokens: int,
                        temperature: float = 0.0, seed: int = 0,
                        top_k: int = 0, top_p: float = 0.0) -> np.ndarray:
    """Host-loop KV-cache generation on a make_decoder model:
    prefill feeds prompt tokens one per step (caches fill as a side
    effect), then each sampled token feeds back.  Exactly matches
    gpt_generate's outputs at temperature 0 (same model, same math,
    one attention row at a time)."""
    from .models.transformer import sample_next, validate_sampling

    validate_sampling(top_k, top_p)
    prompt_ids = np.asarray(prompt_ids, np.int32)
    dims = _gpt_dims(ffd)
    max_seq = dims["max_seq"]
    batch, plen = prompt_ids.shape
    if plen < 1:
        raise ValueError("gpt_generate_cached needs a non-empty prompt")
    if batch != ffd.config.batch_size:
        raise ValueError(
            f"prompt batch {batch} != decoder batch {ffd.config.batch_size}"
        )
    total = min(max_seq, plen + max_new_tokens)
    ffd.reset_decode_state()
    buf = np.zeros((batch, total), np.int32)
    buf[:, :plen] = prompt_ids[:, :total]
    rng = np.random.RandomState(seed)
    # the token at total-1 is the last ever written, so its decode step
    # (whose logits nothing consumes) is never run
    for t in range(total - 1):
        logits = np.asarray(
            ffd.decode_step({
                "input": buf[:, t:t + 1],
                "positions": np.full((batch, 1), t, np.int32),
            }),
            np.float32,
        )
        if t + 1 < plen:
            continue  # prefill: the next token is given
        buf[:, t + 1] = sample_next(logits[:, 0], temperature, rng,
                                    top_k, top_p)
    return buf


def _reorder_cache_rows(ffd: FFModel, perm: np.ndarray):
    """Gather KV-cache batch rows by `perm` (beam-hop bookkeeping: row
    i's history becomes row perm[i]'s).  cache_pos is identical across
    rows and untouched; placement is preserved per entry."""
    import jax
    import jax.numpy as jnp

    if np.array_equal(perm, np.arange(len(perm))):
        return
    idx = jnp.asarray(perm)
    caches = cache_entries(ffd)
    new_state = {}
    for op, entries in ffd._state.items():
        ne = {}
        for k, v in entries.items():
            if k in caches.get(op, ()):
                ne[k] = jax.device_put(jnp.take(v, idx, axis=0), v.sharding)
            else:
                ne[k] = v
        new_state[op] = ne
    ffd._state = new_state


def gpt_beam_search_cached(ffd: FFModel, prompt_ids, max_new_tokens: int,
                           beam_size: int = 4, length_penalty: float = 0.0,
                           eos_id: int = -1):
    """KV-cached, batched beam search on a make_decoder model
    (VERDICT r4 #3: the O(T) replacement for
    models.transformer.gpt_beam_search, which re-runs the full forward
    per token and takes a single prompt).

    Beams ride the decoder's batch dimension: `num_prompts * beam_size`
    must equal the compiled decode batch.  Each selection step gathers
    the KV-cache rows by source beam (_reorder_cache_rows) so every
    row's cache always matches its hypothesis history.  Scoring is
    identical to the full-forward path: summed token log-probs, GNMT
    ((5+len)/6)^lp length normalization, eos freezing with frozen
    beams competing at their final score.

    prompt_ids: [num_prompts, prompt_len] ints.
    Returns (tokens [num_prompts, total_len], scores [num_prompts]).
    """
    prompt_ids = np.asarray(prompt_ids, np.int32)
    if prompt_ids.ndim == 1:
        prompt_ids = prompt_ids[None]
    require_carried(ffd, "beam_search", "gpt_beam_search_cached")
    dims = _gpt_dims(ffd)
    max_seq = dims["max_seq"]
    P, plen = prompt_ids.shape
    K = beam_size
    if plen < 1:
        raise ValueError("gpt_beam_search_cached needs a non-empty prompt")
    if P * K != ffd.config.batch_size:
        raise ValueError(
            f"num_prompts*beam_size = {P}*{K} != decoder batch "
            f"{ffd.config.batch_size}"
        )
    total = min(max_seq, plen + max_new_tokens)
    B = P * K

    ffd.reset_decode_state()
    buf = np.zeros((B, total), np.int32)
    buf[:, :plen] = np.repeat(prompt_ids[:, :total], K, axis=0)
    scores = np.full((P, K), -np.inf, np.float64)
    scores[:, 0] = 0.0  # one distinct hypothesis per prompt at step 1
    alive = np.ones((P, K), bool)
    gen_len = np.zeros((P, K), np.int64)

    for t in range(total - 1):
        logits = np.asarray(
            ffd.decode_step({
                "input": buf[:, t:t + 1],
                "positions": np.full((B, 1), t, np.int32),
            }),
            np.float32,
        )
        if t + 1 < plen:
            continue  # prefill: every row follows its prompt
        step = logits[:, 0].reshape(P, K, -1)
        z = step - step.max(-1, keepdims=True)
        lp = z - np.log(np.exp(z).sum(-1, keepdims=True))  # [P, K, vocab]
        vocab = lp.shape[-1]
        cand = scores[..., None] + np.where(alive[..., None], lp, -np.inf)
        for p in range(P):
            if eos_id >= 0 and not alive[p].all():
                cand[p, ~alive[p], :] = -np.inf
                cand[p, ~alive[p], 0] = scores[p, ~alive[p]]
        flat = cand.reshape(P, -1)
        top = np.argsort(-flat, axis=-1)[:, :K]  # [P, K]
        src_beam, tok = top // vocab, (top % vocab).astype(np.int32)
        perm = (np.arange(P)[:, None] * K + src_beam).reshape(-1)
        _reorder_cache_rows(ffd, perm)
        new_buf = buf[perm].copy()
        new_alive = np.take_along_axis(alive, src_beam, -1)
        write = new_alive.reshape(-1)
        new_buf[write, t + 1] = tok.reshape(-1)[write]
        gen_len = np.take_along_axis(gen_len, src_beam, -1) + new_alive
        if eos_id >= 0:
            new_alive &= tok != eos_id
        buf = new_buf
        scores = np.take_along_axis(flat, top, -1)
        alive = new_alive
        if eos_id >= 0 and not alive.any():
            break
    if length_penalty > 0.0:
        norm = ((5.0 + np.maximum(gen_len, 1).astype(np.float64)) / 6.0) \
            ** length_penalty
        best = np.argmax(scores / norm, axis=-1)
    else:
        best = np.argmax(scores, axis=-1)
    rows = np.arange(P) * K + best
    return buf[rows].copy(), scores[np.arange(P), best].astype(float)


def gpt_generate_scan(ffd: FFModel, prompt_ids, max_new_tokens: int,
                      temperature: float = 0.0, seed: int = 0) -> np.ndarray:
    """Whole-generation-as-one-XLA-program: a jitted lax.scan over the
    decode step with on-device greedy/temperature sampling.  No host
    round trips between tokens — the natural TPU serving shape."""
    import jax
    import jax.numpy as jnp

    prompt_ids = np.asarray(prompt_ids, np.int32)
    dims = _gpt_dims(ffd)
    max_seq = dims["max_seq"]
    batch, plen = prompt_ids.shape
    if plen < 1:
        raise ValueError("gpt_generate_scan needs a non-empty prompt")
    if batch != ffd.config.batch_size:
        raise ValueError(
            f"prompt batch {batch} != decoder batch {ffd.config.batch_size}"
        )
    total = int(min(max_seq, plen + max_new_tokens))
    prompt_pad = np.zeros((batch, total), np.int32)
    prompt_pad[:, :plen] = prompt_ids[:, :total]
    out = run_generate_scan(ffd, prompt_pad,
                            np.full(batch, plen, np.int32), temperature,
                            seed)
    out[:, :plen] = prompt_ids[:, :total]  # prompt verbatim
    return out


def run_generate_scan(ffd: FFModel, prompt_pad: np.ndarray,
                      plens: np.ndarray, temperature: float = 0.0,
                      seed: int = 0) -> np.ndarray:
    """Core scan generator over a row-padded prompt buffer.

    prompt_pad: [batch, total] int32, row i's prompt in [:plens[i]].
    Per-row prompt lengths are a traced [batch] operand, so ONE
    compiled program serves any mix of prompt lengths at a given total
    — the shape contract generation serving needs (each row prefills to
    its own boundary, then samples to `total`).  The compile cache is
    keyed by (total, temperature) and FIFO-bounded as a backstop
    against many totals."""
    import jax
    import jax.numpy as jnp

    batch, total = prompt_pad.shape
    if batch != ffd.config.batch_size:
        raise ValueError(
            f"prompt batch {batch} != decoder batch {ffd.config.batch_size}"
        )
    ffd.reset_decode_state()
    ex = ffd.executor

    cache_key = (total, float(temperature))
    fns = getattr(ffd, "_scan_gen_cache", None)
    if fns is None:
        fns = ffd._scan_gen_cache = {}
    if cache_key not in fns:

        def generate(weights, state, prompt, plen_t, key):
            def body(carry, t):
                state, tok = carry
                logits, new_state, _, _ = ex.run_forward(
                    weights, state,
                    {"input": tok[:, None],
                     "positions": jnp.full((batch, 1), t, jnp.int32)},
                    training=False, rng=None,
                )
                step = logits[:, 0]
                if temperature > 0.0:
                    nxt = jax.random.categorical(
                        jax.random.fold_in(key, t), step / temperature
                    ).astype(jnp.int32)
                else:
                    nxt = jnp.argmax(step, axis=-1).astype(jnp.int32)
                # during each row's prefill the next token is its given
                # prompt id (plen_t is per-row)
                nxt = jnp.where(t + 1 < plen_t,
                                prompt[:, (t + 1) % total], nxt)
                return (new_state, nxt), nxt

            (state, _), toks = jax.lax.scan(
                body, (state, prompt[:, 0]), jnp.arange(total - 1)
            )
            # final state is dropped: one generate call = one sequence
            return jnp.swapaxes(toks, 0, 1)  # [batch, total-1]

        while len(fns) >= 8:
            fns.pop(next(iter(fns)))
        with ex.mesh:
            fns[cache_key] = jax.jit(generate)

    key = jax.random.key(seed)
    toks = np.asarray(fns[cache_key](
        ffd._weights, ffd._state, jnp.asarray(prompt_pad),
        jnp.asarray(plens, np.int32), key))
    out = np.zeros((batch, total), np.int32)
    out[:, 0] = prompt_pad[:, 0]
    out[:, 1:] = toks
    return out


def _host_owned(state, block_table, seq_lens, row_tokens=None):
    """The state pytree with every paged op's host-owned entries
    (`block_table`, `seq_lens`) replaced by the dispatch's own: inside
    the trace, so the per-step override costs nothing at run time and
    the host never rebuilds the state dict.  `row_tokens` (a twin with
    per-slot recurrent state: `slot_state_entries`) is how many of the
    step's tokens each row really advances by, replaced likewise."""
    owned = {"block_table": block_table, "seq_lens": seq_lens}
    if row_tokens is not None:
        owned["row_tokens"] = row_tokens
    return {op: {k: owned.get(k, v) for k, v in entries.items()}
            for op, entries in state.items()}


def _sampled_outputs(ffd: FFModel):
    """finish(logits [b, 1, width], new_state, env) -> what a step
    program hands the sampler, by the recipe: (logits [b, vocab],
    new_state), the head's first `logit_columns` only where the recipe
    says so, and each row's `exit_pdf` third for a family with an exit
    gate."""
    gate = _exit_gate_guid(ffd)
    columns = decoder_recipe(ffd).logit_columns

    def finish(logits, new_state, env):
        with scopes.scope(scopes.LOGITS):
            if gate is not None:
                return logits[:, 0], new_state, exit_pdf(env[gate])
            if columns:
                return logits[:, 0, :columns], new_state
            return logits[:, 0], new_state

    return finish


def _column0(tokens, prev_ids, take_prev):
    """`tokens[b]`, a dispatch's first token a row, with row i's taken
    from `prev_ids[i]` (the last sampling dispatch's `ids`, still on the
    device) where `take_prev[i]` is set, else the host's."""
    import jax.numpy as jnp

    return jnp.where(take_prev > 0, prev_ids.astype(tokens.dtype), tokens)


def _greedy_ids(logits):
    """ids [b] int32: the greedy choice over exactly the logits a row
    of the program returns, the first of equal maxima as `np.argmax`
    takes it on the host's float32 copy (a cast that keeps order and
    ties)."""
    import jax.numpy as jnp

    with scopes.scope(scopes.LOGITS):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _counted(ffd: FFModel) -> Dict[str, list]:
    """{count entry: the ops that leave it in their state, in graph
    order}: the routed layers' `moe_stats`, and `moe_zero` of those with
    identity experts."""
    return {entry: [op for op, entries in ffd._state.items()
                    if entry in entries]
            for entry in ("moe_stats", "moe_zero")}


def _counts_behind(out: tuple, new_state, counted) -> tuple:
    """`out` with the routed layers' counts IN the logits' buffer
    (`out[0]`): `r` more rows behind the `b` rows of logits hold every
    layer's `moe_stats` and then every identity-expert layer's
    `moe_zero`, in graph order, as the logits' dtype (small whole
    numbers, exact), zero-padded to the row; `split_pass_counts` takes
    them off on the host.  One buffer comes back, not one more a layer
    and entry, and none of them is a buffer of the state, which the
    next dispatch is donated."""
    import jax.numpy as jnp

    with scopes.scope(scopes.LOGITS):
        width = out[0].shape[1]
        flat = jnp.concatenate([
            new_state[op][entry] for entry, ops in counted.items()
            for op in ops]).astype(out[0].dtype)
        rows = -(-flat.shape[0] // width)
        packed = jnp.pad(flat, (0, rows * width - flat.shape[0]))
        return (jnp.concatenate([out[0], packed.reshape(rows, width)]),
                *out[1:])


def build_paged_decode_step(ffd: FFModel):
    """ONE compiled step function for continuous batching on a paged
    decode twin (make_decoder with kv_page_size > 0):

        step(weights, state, tokens[b], positions[b], block_table
             [, row_tokens[b][, prev_ids[b], take_prev[b]]])
            -> (logits [b, vocab], new_state[, exit_pdf [b, passes]]
                [, ids [b]])

    The third output exists for a family with an exit gate only
    (`DecoderRecipe.exit_gate`): each row's `exit_pdf`, float32.

    `prev_ids` / `take_prev` are passed by the engine of a family whose
    sampling programs keep the greedy id on the device (one on the
    one-pass prefill program: PagedKVDecodeModel `keeps_ids`):
    row i is fed `prev_ids[i]`, the `ids` of the sampling dispatch
    before this one (never fetched in between), where `take_prev[i]` is
    set, and the host's `tokens[i]` otherwise; the last output is then
    `ids`, the argmax of each returned row of logits.  So the host can
    enqueue a dispatch before it has fetched the one before
    (serving/scheduler.py "one dispatch of lookahead").  Left out, the
    program is the one it always was (`row_tokens` is then None for a
    twin without per-slot state).  With them, a graph with routed
    layers also returns their counts as rows behind the logits'
    (`_counts_behind`), as the one-pass prefill program does: a flight's
    counts must outlive the state they were left in, which the next
    dispatch is donated.

    `row_tokens` is passed by the engine of a twin with per-slot
    recurrent state only (1 for a live row, 0 for an idle slot, whose
    state then stays as it is); left out, the program is the one it
    always was.

    Unlike the full-generation scan (whose program is keyed by total
    length), the continuous scheduler steps every in-flight sequence by
    one token per call with per-row positions — the shapes never change,
    so this single program serves the engine's entire lifetime with
    zero recompiles.  The scheduler owns the state pytree and threads
    it through explicitly; nothing here touches ffd._state.

    Hot-path discipline (this runs once per generated token):
      * block_table/seq_lens are jit ARGUMENTS substituted into the
        attention op states inside the trace — the per-step override
        costs nothing at run time and the host never rebuilds the
        state dict;
      * the state pytree is DONATED, so each step's k/v pool scatter
        updates the buffers in place instead of copying every layer's
        pool per token (XLA honors this on TPU; on CPU it degrades to
        a copy, harmlessly)."""
    import jax
    import jax.numpy as jnp

    ex = ffd.executor
    finish = _sampled_outputs(ffd)
    counted = _counted(ffd)

    def step(weights, state, tokens, positions, block_table,
             row_tokens=None, prev_ids=None, take_prev=None):
        state = _host_owned(state, block_table, positions, row_tokens)
        with scopes.scope(scopes.FEED):
            if prev_ids is not None:
                tokens = _column0(tokens, prev_ids, take_prev)
            inputs = {"input": tokens[:, None],
                      "positions": positions[:, None].astype(jnp.int32)}
        logits, new_state, _, env = ex.run_forward(
            weights, state, inputs, training=False, rng=None,
        )
        out = finish(logits, new_state, env)
        if prev_ids is None:
            return out
        out = (*out, _greedy_ids(out[0]))
        return (_counts_behind(out, new_state, counted)
                if counted["moe_stats"] else out)

    with ex.mesh:
        return jax.jit(step, donate_argnums=(1,))


def build_paged_prefill_step(ffd: FFModel, chunk: int):
    """ONE compiled [slots, C] CHUNKED-PREFILL program for the paged
    decode twin (the second step program of the continuous engine,
    built alongside build_paged_decode_step):

        prefill(weights, state, tokens[b, C], positions[b], block_table)
            -> new_state

    Feeds each row C consecutive prompt tokens starting at its own
    position (row i's token j lands at positions[i] + j), filling the
    KV pool C tokens per dispatch — a P-token prompt costs ~P/C steps
    instead of P.  Logits are not returned: prefill ignores them (the
    final prompt token runs through the decode program, whose logits
    seed sampling), and rows past their real token count just write
    overwritten-before-attended garbage (see the scheduler).

    BIT-IDENTITY DISCIPLINE: internally this is a lax.scan of the
    SEQ-1 decode graph over the chunk, not a seq-C forward.  Every op
    in the scan body has exactly the decode program's shapes, so the
    K/V bytes it writes are bit-identical to one-token-at-a-time
    prefill — XLA:CPU lowers same-shape dots identically, but NOT
    matmuls whose leading dim changed (a [b*C, e] FFN matmul is not
    rowwise-bitwise-equal to its [b, e] slice), which rules out the
    fused seq-C graph (build_paged_chunk_step) wherever the dense
    gather oracle's byte-identity guarantee must hold."""
    import jax
    import jax.numpy as jnp

    if chunk < 2:
        raise ValueError(f"chunk must be >= 2, got {chunk}")
    ex = ffd.executor
    max_seq = _gpt_dims(ffd)["max_seq"]

    def prefill(weights, state, tokens, positions, block_table):
        def body(carry, xs):
            tok, j = xs
            # a row's trailing PAD tokens can run past the position
            # table (a near-max_seq prompt whose last chunk is mostly
            # padding).  Route those writes to scratch (zeroed table
            # row) and clamp the position in-range EXPLICITLY: today
            # jax's fill-mode gather turns the out-of-range block-id
            # lookup into an out-of-range scatter that XLA drops, but
            # that is a mode default (plain `arr[idx]` gathers CLAMP
            # instead), not a contract — an attention rewrite or
            # indexing-mode change must not be able to turn a pad
            # write into a clamped overwrite of the row's last real
            # block.  tests/test_serving_continuous.py pins the
            # byte-level contract either way.
            with scopes.scope(scopes.FEED):
                pos_j = (positions + j).astype(jnp.int32)
                bt_j = jnp.where((pos_j < max_seq)[:, None], block_table, 0)
                pos_j = jnp.minimum(pos_j, max_seq - 1)
                inputs = {"input": tok[:, None], "positions": pos_j[:, None]}
            st = _host_owned(carry, bt_j, pos_j)
            _, new_state, _, _ = ex.run_forward(
                weights, st, inputs, training=False, rng=None,
            )
            return new_state, None

        state, _ = jax.lax.scan(
            body, state,
            (jnp.swapaxes(tokens, 0, 1),
             jnp.arange(chunk, dtype=jnp.int32)),
        )
        return state

    with ex.mesh:
        return jax.jit(prefill, donate_argnums=(1,))


def build_paged_prefill_pass(ffd: FFModel, chunk: int):
    """The chunked-prefill program of a family whose recipe carries
    `prefill_pass` (PagedKVDecodeModel chooses by that, never by a flag
    or a name), as ONE forward of the twin over [slots, C] with the
    decode step's outputs:

        prefill(weights, state, tokens[b, C], positions[b], block_table,
                row_tokens[b][, prev_ids[b], take_prev[b]])
            -> (logits [b (+ r), vocab], new_state[, exit_pdf [b, passes]]
                [, ids [b]])

    A dispatch streams the weights once and builds each layer's
    gathered view once, where build_paged_prefill_step's scan does both
    C times.  `row_tokens[i]` is how many of the chunk's C tokens row i
    really has (0 for an idle slot): as far as per-slot state advances
    (`_host_owned`), and `logits[i]` are the model's at the row's LAST
    real token, position `positions[i] + row_tokens[i] - 1`.  So a row
    past its prompt rides the pass with its pending token at column 0
    and `row_tokens` 1, and is sampled from it as from the decode step
    (serving/scheduler.py `plan_chunk_rows`); the jitted function keeps
    the name `prefill`.  The recipe's `head` ops (final norm, head, exit
    gate) read that one position of each row, gathered before them:
    the head multiplies [b, hidden], not [b, C, hidden].
    `logit_columns` and the exit gate's `exit_pdf` are applied as
    build_paged_decode_step applies them, and `prev_ids` / `take_prev`
    / `ids` mean what they mean there: column 0 of row i is
    `prev_ids[i]` where `take_prev[i]` is set, `ids` the argmax of the
    `b` rows of logits.

    The routed layers of the graph (`moe_stats` in their state) count
    the REAL tokens of the pass alone, `column < row_tokens[row]`: the
    mask goes to them from this closure (`run_forward`'s `count_rows`),
    never through a state entry, which would make a twin "carry
    per-slot state" and hand the decode step an argument.  Their output
    is every column's as before (a pad column multiplies like any
    other).  Such a graph's counts ride IN the logits' buffer
    (`_counts_behind`).  One buffer comes back, as before: every
    further buffer a fetch brings costs the host a tenth of a
    millisecond or two on the v5e, in the `device_get` or beside it
    (PERF.md, PR 53), and this is every token's path.

    The twin's graph is interpreted over [b, C] inputs as it stands:
    the recipe's claim is that every op of it is per-token or takes
    the step's length from its input (ops/mla.py `_attend_paged_chunk`,
    which also keeps the pad contract: a row's columns past its real
    tokens write past its own frontier, positions >= max_seq write
    scratch).  What the claim gives up is the scan's byte equality with
    seq-1 stepping (a [b*C, e] product is not rowwise-bitwise a [b, e]
    one); such a family's outputs are held to its reference by
    tolerance, and it carries neither `speculative` nor `handoff`."""
    import jax
    import jax.numpy as jnp

    from .config import ConfigError

    if chunk < 2:
        raise ValueError(f"chunk must be >= 2, got {chunk}")
    require_carried(ffd, "prefill_pass", "build_paged_prefill_pass")
    ex = ffd.executor
    recipe = decoder_recipe(ffd)
    max_seq = recipe.dims["max_seq"]
    finish = _sampled_outputs(ffd)
    head = [op for op in ffd.operators.topo_order() if op.name in recipe.head]
    if not head:
        raise ConfigError(
            f"{recipe.family} carries prefill_pass but its recipe names no "
            "`head` ops: the pass cannot tell where the last layer ends")
    made = {t.guid for op in head for t in op.outputs}
    # what the head reads of the layers: [..., C, hidden] tensors
    cut = {t.guid for op in head for t in op.inputs} - made
    counted = _counted(ffd)

    def prefill(weights, state, tokens, positions, block_table, row_tokens,
                prev_ids=None, take_prev=None):
        with scopes.scope(scopes.FEED):
            if prev_ids is not None:
                tokens = tokens.at[:, 0].set(
                    _column0(tokens[:, 0], prev_ids, take_prev))
            positions = positions.astype(jnp.int32)
            row_tokens = row_tokens.astype(jnp.int32)
        state = _host_owned(state, block_table, positions, row_tokens)
        with scopes.scope(scopes.FEED):
            grid = positions[:, None] + jnp.arange(chunk, dtype=jnp.int32)
            inputs = {"input": tokens,
                      "positions": jnp.minimum(grid, max_seq - 1)}
            last = jnp.clip(row_tokens - 1, 0, chunk - 1)[:, None, None]
            real = (jnp.arange(chunk, dtype=jnp.int32) < row_tokens[:, None]
                    if counted["moe_stats"] else None)

        def last_token(x):  # [..., b, C, hidden] -> [..., b, 1, hidden]
            with scopes.scope(scopes.LOGITS):
                return jnp.take_along_axis(
                    x, last.reshape((1,) * (x.ndim - 3) + last.shape),
                    axis=-2)

        logits, new_state, _, env = ex.run_forward(
            weights, state, inputs, training=False, rng=None,
            narrow=dict.fromkeys(cut, last_token), count_rows=real,
        )
        out = finish(logits, new_state, env)
        if prev_ids is not None:
            out = (*out, _greedy_ids(out[0]))
        return out if real is None else _counts_behind(out, new_state,
                                                        counted)

    with ex.mesh:
        return jax.jit(prefill, donate_argnums=(1,))


def split_pass_counts(packed, slots: int, layers: int, zero_layers: int):
    """(logits [slots, vocab], {"moe_stats": int [layers, 4], "moe_zero":
    int [zero_layers, 3]}) from the host copy of what
    `build_paged_prefill_pass` returns for a graph with `layers` routed
    layers, `zero_layers` of them with identity experts."""
    from .ops.routed_experts import MOE_STATS, MOE_ZERO_STATS

    flat = np.rint(packed[slots:].reshape(-1)).astype(np.int64)
    n = layers * len(MOE_STATS)
    return packed[:slots], {
        "moe_stats": flat[:n].reshape(layers, len(MOE_STATS)),
        "moe_zero": flat[n:n + zero_layers * len(MOE_ZERO_STATS)].reshape(
            zero_layers, len(MOE_ZERO_STATS))}


def build_paged_verify_step(ffd: FFModel, chunk: int):
    """ONE compiled [slots, C] speculative-VERIFY program for the paged
    decode twin (docs/SERVING.md "Speculative decoding"):

        verify(weights, state, tokens[b, C], positions[b], counts[b],
               block_table)
            -> (logits [b, C, vocab], new_state)

    Row i feeds tokens[i, :counts[i]] at positions[i] .. positions[i] +
    counts[i] - 1 — its pending next token followed by counts[i]-1
    draft tokens — and gets the model's logits at EVERY fed position
    back, so the scheduler can accept the longest greedy-matching draft
    prefix plus the first corrected token from a single dispatch.
    Steps j >= counts[i] are routed to the scratch block (zeroed table
    row, clamped position) exactly like chunked prefill's pad tokens,
    so short rows ride a long row's round without touching their own
    pool bytes; counts is a traced argument, so ONE program serves
    every per-round draft-length mix.

    BIT-IDENTITY DISCIPLINE: same as build_paged_prefill_step — a
    lax.scan of the SEQ-1 decode graph, every op at the decode
    program's shapes, so both the K/V bytes written and the per-step
    logits are bit-identical to feeding the same tokens one decode
    step at a time.  Greedy acceptance over bit-identical logits makes
    speculative output token-identical to the plain engine BY
    CONSTRUCTION (Leviathan et al., arXiv:2211.17192, the temperature
    0 case), under both the gather and Pallas kernel formulations."""
    import jax
    import jax.numpy as jnp

    if chunk < 2:
        raise ValueError(f"chunk must be >= 2, got {chunk}")
    ex = ffd.executor
    max_seq = _gpt_dims(ffd)["max_seq"]

    def verify(weights, state, tokens, positions, counts, block_table):
        def body(carry, xs):
            tok, j = xs
            # pad steps (j >= counts[i]) write to scratch at a clamped
            # position — same contract as prefill's trailing pads: the
            # row's real blocks must be unreachable from a pad step no
            # matter the gather/scatter out-of-range mode.
            with scopes.scope(scopes.FEED):
                pos_j = (positions + j).astype(jnp.int32)
                live = (j < counts) & (pos_j < max_seq)
                bt_j = jnp.where(live[:, None], block_table, 0)
                pos_j = jnp.where(live, pos_j, 0)
                inputs = {"input": tok[:, None], "positions": pos_j[:, None]}
            st = _host_owned(carry, bt_j, pos_j)
            logits, new_state, _, _ = ex.run_forward(
                weights, st, inputs, training=False, rng=None,
            )
            with scopes.scope(scopes.LOGITS):
                return new_state, logits[:, 0]

        state, logits = jax.lax.scan(
            body, state,
            (jnp.swapaxes(tokens, 0, 1),
             jnp.arange(chunk, dtype=jnp.int32)),
        )
        return jnp.swapaxes(logits, 0, 1), state

    with ex.mesh:
        return jax.jit(verify, donate_argnums=(1,))


def build_paged_chunk_step(ffd: FFModel):
    """Step function for a CHUNKED paged twin built with
    make_decoder(step_tokens=C): one true seq-C forward per call,

        step(weights, state, tokens[b, C], positions[b], block_table)
            -> (logits [b, C, vocab], new_state)

    The attention paged path scatters each row's C tokens at its own
    positions and attends causally within the chunk (per-position
    gathers, ops/attention.py).  This is the TPU-native prefill shape
    — the MXU sees [b*C, e] matmuls instead of C seq-1 slivers — but
    its FFN/vocab matmuls are NOT rowwise-bitwise-equal to the seq-1
    program's, so the continuous engine's byte-identity oracle uses
    build_paged_prefill_step instead; this program is for
    throughput-first deployments and is the fused Pallas kernel's
    natural host-side twin (make_decoder(kv_kernel="pallas",
    step_tokens=C) runs the whole chunk's attention as ONE kernel
    dispatch per layer — ops/pallas/paged_attention.py)."""
    import jax
    import jax.numpy as jnp

    ex = ffd.executor

    def step(weights, state, tokens, positions, block_table):
        chunk = tokens.shape[1]
        with scopes.scope(scopes.FEED):
            positions = positions.astype(jnp.int32)
            pos_grid = positions[:, None] + jnp.arange(chunk,
                                                       dtype=jnp.int32)
        state = _host_owned(state, block_table, positions)
        logits, new_state, _, _ = ex.run_forward(
            weights, state,
            {"input": tokens, "positions": pos_grid},
            training=False, rng=None,
        )
        return logits, new_state

    with ex.mesh:
        return jax.jit(step, donate_argnums=(1,))


def build_slot_state_reset(ffd: FFModel):
    """Compiled reset of ONE slot's recurrent state:

        reset(state, slot) -> new_state

    zeroes row `slot` of every `slot_state_entries` array (scalar int32
    id; state donated, so on TPU it is an in-place write).  The
    scheduler runs it when it gives the slot to a request, so a slot's
    second request starts where a fresh server's first does.  State
    that is masked by the sequence's own positions
    (`Op.slot_state_resets` False) is left as it is; None where that
    leaves nothing to zero."""
    import jax

    ex = ffd.executor
    mine = {op.name: op.slot_state_entries()
            for op in ffd.operators.topo_order()
            if op.slot_state_entries() and op.slot_state_resets}
    if not mine:
        return None

    def reset(state, slot):
        return {
            op: {
                k: (v.at[slot].set(0) if k in mine.get(op, ()) else v)
                for k, v in entries.items()
            }
            for op, entries in state.items()
        }

    with ex.mesh:
        return jax.jit(reset, donate_argnums=(0,))


def build_paged_copy_block(ffd: FFModel):
    """Compiled one-block copy-on-write for the paged pools:

        copy(state, src, dst) -> new_state

    copies physical block `src`'s page to block `dst` in EVERY layer's
    pools, every plane of them (k/v or latent: `cache_entries`,
    `cache_planes`; scalar int32 ids; state
    donated, so on TPU the copy is
    in-place scatter, not a pool clone).  The prefix cache's COW path
    (serving/kv_pool.py ensure_writable) runs this before a full-hit
    request's first write, so shared blocks stay immutable while the
    request gets a bit-exact private tail."""
    import jax
    import jax.numpy as jnp

    ex = ffd.executor
    pools = cache_entries(ffd)
    planes = cache_planes(ffd)

    def copied(op, v, src, dst):
        if planes[op] == 1:
            return v.at[dst].set(v[src])
        # the block's page in every plane: rows t x num_blocks + b
        rows = jnp.arange(planes[op], dtype=jnp.int32) \
            * (v.shape[0] // planes[op])
        return v.at[dst + rows].set(v[src + rows])

    def copy(state, src, dst):
        return {
            op: {
                k: (copied(op, v, src, dst)
                    if k in pools.get(op, ()) else v)
                for k, v in entries.items()
            }
            for op, entries in state.items()
        }

    with ex.mesh:
        return jax.jit(copy, donate_argnums=(0,))
