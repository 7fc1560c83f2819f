"""The `laguna` family behind the serving front, at a toy size on the
CPU in float32 (window 8, page 4, 5 layers = a dense one and a period
of three window layers and a full one, 2 key/value heads under 6 and 8
query heads, 8 of 16 experts held, YaRN factor 4;
`benchmarks/configs/toy-laguna.json`): the stateless program against
the reference on every position; chunked prefill then decode THROUGH
THE RINGS AND THE POOL against the reference's full forward, on rows
whose lengths differ and that wrap the ring several times, under the
gather and under the Pallas read; three controls that have to FAIL the
same tolerance; the share test; what a window layer holds; what the
family refuses, by name.

The reference (`benchmarks/families/laguna.py`) shares no code with
`flexflow_tpu/ops/attention.py`.  LOGIT_TOL is 2e-5 of the largest
logit: both sides are float32 sums of a few hundred terms in other
orders.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _family import Recorder, close, config
from benchmarks import reference as ref
from benchmarks.families import laguna as fam
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.config import ConfigError
from flexflow_tpu.models.laguna import build_laguna, window_ring_rows
from flexflow_tpu.ops.attention import window_rows_live

CFG = config("toy-laguna.json")
D = fam.dims(CFG)
SEED = 11
KEY = ref.seed_key(SEED)
OP_TOL, LOGIT_TOL = 1e-5, 2e-5
SLOTS, PAGE = 3, CFG["deployment"]["kv_page_size"]
W = D.window


def holder(cfg=CFG, **ffconfig):
    """The served model's holder with the seed's weights set."""
    dep = cfg["deployment"]
    ffconfig.setdefault("prefix_cache", False)
    ff = FFModel(FFConfig(
        batch_size=1, num_devices=1, compute_dtype=cfg["precision"],
        serving_slots=dep["serving_slots"], kv_page_size=dep["kv_page_size"],
        kv_pool_blocks=dep["kv_pool_blocks"], **ffconfig))
    build_laguna(ff, 1, cfg["n_positions"], **fam.published(cfg))
    ff.compile(devices=jax.devices()[:1], defer_weights=True)
    ff.set_weights(fam.make_weights(cfg, SEED, "program"))
    return ff


def with_chunk(chunk):
    """The toy configuration with rings sized for `prefill_chunk`."""
    return dict(CFG, deployment=dict(CFG["deployment"], prefill_chunk=chunk))


@pytest.fixture(scope="module")
def model():
    return holder()


def reference_logits(tokens):
    return np.asarray(fam.logits_fn(
        fam.make_weights(CFG, SEED, "reference"), np.asarray(tokens),
        "float32"))


def prompts_of(lengths, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, D.v, n).tolist() for n in lengths]


# -- 1. the stateless program, every position ---------------------------------------
S = 5 * W  # five windows


def stateless_logits(tokens, weights=None, wrap=None, **overrides):
    """The stateless program's logits over `tokens`, the builder's
    published arguments overridden by name; `wrap(ff)` may change the
    graph's builder calls before it is built."""
    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    if wrap is not None:
        wrap(ff)
    build_laguna(ff, 1, len(tokens), **{**fam.published(CFG), **overrides})
    ff.compile(devices=jax.devices()[:1], defer_weights=True)
    ff.set_weights(weights or fam.make_weights(CFG, SEED, "program"))
    return np.asarray(ff.forward(
        {"input": np.asarray(tokens, np.int32)[None]}))[0]


@pytest.fixture(scope="module")
def whole():
    (tokens,) = prompts_of((S,), seed=3)
    return tokens, reference_logits(tokens)


def test_stateless_program_equals_the_reference_on_every_position(whole):
    tokens, want = whole
    close(stateless_logits(tokens), want, LOGIT_TOL)


def no_yarn():
    rope = {k: dict(v) if isinstance(v, dict) else v
            for k, v in CFG["rope_parameters"].items()}
    rope[fam.FULL] = dict(rope[fam.FULL], rope_type="default")
    return dict(rope_parameters=rope)


def gate_a_channel():
    """`gating` built as the gate a channel that `wq` projects
    (`output_gate`), its half of `wq` drawn from the seed like `wg`."""
    def wrap(ff):
        plain = ff.multihead_attention

        def channel_gate(*args, **kw):
            kw["output_gate"], kw["head_gate"] = kw.pop("head_gate"), False
            return plain(*args, **kw)

        ff.multihead_attention = channel_gate

    weights = fam.make_weights(CFG, SEED, "program")
    for i in range(D.L):
        w = dict(weights[f"attn_{i}"])
        gate = fam.leaf(KEY, "attn", "wg_channel", w["wq"].shape, i)
        w["wq"] = jnp.concatenate([w["wq"], gate], axis=-1)
        del w["wg"]
        weights[f"attn_{i}"] = w
    return dict(wrap=wrap, weights=weights)


CONTROLS = {
    "window_minus_one": lambda: dict(sliding_window=W - 1),
    "window_plus_one": lambda: dict(sliding_window=W + 1),
    "no_yarn": no_yarn,
    "gate_a_channel": gate_a_channel,
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_controls_fail_the_tolerance_the_program_meets(whole, control):
    """A window off by one, plain RoPE on the full layers and a gate a
    channel are each another function: the comparison that passes the
    program has to tell."""
    tokens, want = whole
    got = stateless_logits(tokens, **CONTROLS[control]())
    err = float(np.max(np.abs(got - want))) / float(np.max(np.abs(want)))
    assert err > 10 * LOGIT_TOL, err


def test_flash_kernel_is_refused_by_a_window_layer_and_taken_by_a_full_one():
    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    build_laguna(ff, 1, 4096, **fam.published(with_chunk(4)))
    ops = {op.name: op for op in ff.layers.topo_order()}
    assert ops["attn_1"].params.sliding_window == W
    assert ops["attn_1"].core_plan(2) == "dense"  # never the wrong mask
    assert ops["attn_0"].core_plan(2) != "dense"


# -- 2. prefill in chunks, then decode, through the scheduler -------------------------
#: prompt lengths: inside the first window; a prompt that ends on a page's
#: last position; two windows and a bit; five windows (the ring of 12 or
#: 16 rows wraps four times before the first sampled token)
PROMPTS = (5, 12, 19, 41)
NEW = 14  # the shortest row decodes across the window's end and the ring's
#: (`prefill_chunk`, paged read): one token a step; a chunk that divides
#: nothing; the largest the ring of `W + 4` rows allows; the same under
#: the Pallas read, interpreted; rings sized for a chunk of 8 at that chunk
PLANS = ((0, "gather", 4), (3, "gather", 4), (4, "gather", 4),
         (4, "pallas", 4), (8, "pallas", 8))


def serve(model, chunk, kernel, prompts, new_tokens, slots=SLOTS):
    """One scheduler over the toy model: every prompt at once (more
    than `slots` queue for one): (recorded rows, handles, stats)."""
    from flexflow_tpu.serving.scheduler import ContinuousScheduler

    sched = ContinuousScheduler.from_trained(
        model, batch_slots=slots, page_size=PAGE, prefill_chunk=chunk,
        prefix_cache=False, paged_kernel=kernel, devices=jax.devices()[:1])
    rec = Recorder(sched)
    try:
        handles = [sched.generate_async(p, n, 0.0)
                   for p, n in zip(prompts, new_tokens)]
        for h in handles:
            h.wait(300)
        stats = sched.stats()
    finally:
        sched.close(10)
    return rec.rows, handles, stats


@pytest.fixture(scope="module", params=PLANS,
                ids=lambda p: f"chunk{p[0]}-{p[1]}-ring{p[2]}")
def served(request, model):
    chunk, kernel, sized = request.param
    m = model if sized == 4 else holder(with_chunk(sized))
    return serve(m, chunk, kernel, prompts_of(PROMPTS), [NEW] * 4), kernel


@pytest.mark.parametrize("which", range(len(PROMPTS)))
def test_served_logits_equal_the_reference_full_forward(served, which):
    (rows, handles, stats), kernel = served
    h = handles[which]
    want = reference_logits(h.result)
    mine = [(pos, logits) for req, pos, logits in rows if req is h]
    assert len(mine) >= NEW and max(p for p, _ in mine) == len(h.result) - 2
    for pos, logits in mine:
        close(logits, want[pos], LOGIT_TOL)
    assert stats["paged_kernel"]["formulation"] == kernel


def test_a_reused_slot_serves_what_a_fresh_server_serves(model):
    """A long sequence and then a short one in the SAME slot: the short
    one's logits equal the reference's, which rows of the first
    tenant's rings, left where they were (nothing zeroes them), would
    not give if they could be read."""
    rows, handles, stats = serve(model, 4, "gather", prompts_of((50, 6)),
                                 [6, 9], slots=1)
    assert stats["requests_done"] == 2
    for h in handles:
        want = reference_logits(h.result)
        for req, pos, logits in rows:
            if req is h:
                close(logits, want[pos], LOGIT_TOL)


# -- 3. what a window layer holds, and what the pool counts ----------------------------
def test_window_state_is_the_same_at_two_and_at_twenty_windows():
    """A window layer's state is its slot's ring whatever the length; the
    pool's blocks grow with the length and count the full layers alone."""
    cfg = dict(CFG, n_positions=32 * W)
    m = holder(cfg)
    row = 2 * D.kvh * D.hd * 4  # keys and values of a position, float32
    seen = {}
    for length in (2 * W, 20 * W):
        _, (h,), stats = serve(m, 4, "gather", prompts_of((length - 4,)),
                               [4], slots=2)
        assert len(h.result) == length
        seen[length] = stats
        assert stats["swa"]["state_bytes"] == (
            2 * D.window_layers * window_ring_rows(W, 4, PAGE) * row)
        assert stats["kv_pool"]["bytes_per_token"] == D.full_layers * row
    assert (seen[2 * W]["swa"]["state_bytes"]
            == seen[20 * W]["swa"]["state_bytes"])
    assert seen[20 * W]["kv_pool"]["peak_used_blocks"] >= 20 * W // PAGE \
        > seen[2 * W]["kv_pool"]["peak_used_blocks"]


def test_state_predicates_keep_pages_and_rings_apart(model):
    from flexflow_tpu.decoding import cache_entries, slot_state_entries
    from flexflow_tpu.serving.scheduler import PagedKVDecodeModel

    twin = PagedKVDecodeModel(model, batch_slots=2, page_size=PAGE,
                              num_blocks=9, prefix_cache=False,
                              prefill_chunk=4, devices=jax.devices()[:1])
    pools, rings = cache_entries(twin.ffd), slot_state_entries(twin.ffd)
    assert set(pools) == {f"attn_{i}" for i in range(D.L) if D.is_full(i)}
    assert set(rings) == {f"attn_{i}" for i in range(D.L)
                          if not D.is_full(i)}
    assert all(v == ("win_k", "win_v") for v in rings.values())
    ring = window_ring_rows(W, 4, PAGE)
    assert twin._state["attn_1"]["win_k"].shape == (2, D.kvh, ring, D.hd)
    assert "block_table" not in twin._state["attn_1"]
    assert twin.groups["swa"].geometry == {
        "window": W, "ring": ring, "layers": D.window_layers,
        "state_bytes": fam.swa_state_bytes(CFG, ring) * 2
        // CFG["deployment"]["serving_slots"]}
    assert list(twin.groups) == ["swa"]
    assert twin.rstate_bytes == 0 and twin.has_slot_state
    assert twin.kv_block_bytes == fam.latent_block_bytes(CFG)
    assert twin._reset_slot_fn is None  # masked by position: nothing to zero


def test_state_sizes_at_published_widths():
    """From the ops' specs with the catalog's widths (no array is made):
    16,384 B of pooled keys and values a token over the four full
    layers, a ring of 576 rows a slot a window layer."""
    cfg = config("laguna-xs2-ep8-serve.json")
    d = fam.dims(cfg)
    ff = FFModel(FFConfig(batch_size=32, num_devices=1))
    build_laguna(ff, 32, 1, **fam.published(cfg), decode_max_seq=16384,
                 kv_page_size=16, kv_num_blocks=16385)
    ops = {op.name: op for op in ff.layers.topo_order()}

    def state(op):
        return {s.name: s.shape.logical_shape
                for s in op.weight_specs[op.num_trainable_weights():]}

    ring = window_ring_rows(512, cfg["deployment"]["prefill_chunk"], 16)
    assert state(ops["attn_1"])["win_k"] == (32, 8, ring, 128)
    assert state(ops["attn_0"])["k_cache"] == (16385, 8, 16, 128)  # head-major
    assert (d.full_layers, d.window_layers) == (4, 9)
    assert d.heads == (48, 64, 64, 64) * 3 + (48,)
    assert fam.latent_block_bytes(cfg) == 16 * 16384
    assert fam.swa_state_bytes(cfg, 576) == 9 * 32 * 576 * 4096
    c = fam.parameter_counts(d)
    total = (c["attention"] + c["dense_mlp"] + c["norms"] + c["router"]
             + c["shared"] + c["held_experts"] * c["one_expert"]
             + c["table"] + c["head"])
    assert abs(total / 1e6 - 2172) < 1.0


def test_row_counts_are_arithmetic_from_the_lengths():
    # window 8: a decode step of rows at 3 and 20 sees 4 and 8 rows
    assert window_rows_live(8, [3, 20, 0], [1, 1, 0]) == 12
    # a pass of 4 from 6: positions 6..9 see positions 0..9 between them
    assert window_rows_live(8, [6, 0], [4, 0]) == 10
    # and from 20: positions 13..23, the window and the chunk less one
    assert window_rows_live(8, [20], [4]) == 11


def test_dispatch_spans_carry_the_counters_and_the_twin_its_bytes():
    from flexflow_tpu.obs.trace import next_span_id, spans
    from flexflow_tpu.serving import build_front

    first = next_span_id()
    front = build_front(holder(prefill_chunk=4))
    try:
        out = front.generate(list(range(1, 20)), 4, 0.0)
        replicas = front.stats()["replicas"]
    finally:
        front.close()
    assert len(out) == 23
    slots = CFG["deployment"]["serving_slots"]
    ring = window_ring_rows(W, 4, PAGE)
    mine = [r for r in spans() if r.span_id > first]
    twin = next(r for r in mine if r.name == "serve.build_twin")
    assert twin.args["swa_state_bytes"] == fam.swa_state_bytes(CFG, ring)
    assert "rstate_bytes" not in twin.args
    decode = [r.args for r in mine if r.name == "sched.decode.dispatch"]
    prefill = [r.args for r in mine if r.name == "sched.prefill.dispatch"]
    assert decode and prefill
    at = 0
    for a in prefill:
        n = a["tokens"]
        assert a["swa_rows_live"] == D.window_layers * window_rows_live(
            W, [at], [n])
        at += n
    assert all(a["swa_rows_read"] == D.window_layers * slots * ring
               for a in decode + prefill)
    assert all("rstate_rows_live" not in a for a in decode + prefill)
    assert decode[-1]["swa_rows_live"] == D.window_layers * W  # position 21
    (r,) = replicas
    assert r["swa"]["state_bytes"] == fam.swa_state_bytes(CFG, ring)
    assert (r["swa"]["decode_dispatches"], r["swa"]["prefill_dispatches"]) \
        == (len(decode), len(prefill))
    assert r["swa"]["prefill_swa_rows_live"] == sum(
        a["swa_rows_live"] for a in prefill)
    assert (r["swa"]["window"], r["swa"]["ring"]) == (W, ring)
    # what the readers' floors count
    assert fam.swa_read_bytes(CFG, 3) == 3 * 2 * D.kvh * D.hd * 4


# -- 4. the share test -----------------------------------------------------------
def test_every_share_of_an_expert_layer_adds_up_to_the_uncut_layer():
    """The routed parts that all eight shares of two experts give, with
    the shared expert counted once, are the uncut reference's whole
    layer: through the PROGRAM's op for each share, against the
    reference given every expert."""
    x = np.asarray(jax.random.normal(jax.random.key(7), (2, 12, D.e)))
    w = fam.make_leaves(KEY, D, "moe", 1)
    with jax.default_matmul_precision("highest"):
        whole = np.stack([sum(fam.experts(
            jnp.asarray(row), w, fam.held_experts(KEY, D, 1, (0, D.total)),
            D, lambda v: v, first=0)) for row in x])
        shared = np.stack([fam.gated(
            jnp.asarray(row), w["shared_gate"], w["shared_up"],
            w["shared_down"], lambda v: v) for row in x])
    total = np.zeros_like(whole)
    held = D.total // 8
    for first in range(0, D.total, held):
        cfg = dict(CFG, num_experts=held, deployment=dict(
            CFG["deployment"], first_held_expert=first))
        graph = FFModel(FFConfig(batch_size=1, num_devices=1))
        build_laguna(graph, 1, 8, **fam.published(cfg))
        params = next(op for op in graph.layers.topo_order()
                      if op.name == "moe_1").params
        assert (params.experts_held, params.first_held,
                params.experts_total) == (held, first, D.total)
        ff = FFModel(FFConfig(batch_size=2, num_devices=1))
        out = ff.routed_experts(ff.create_tensor([2, 12, D.e], name="x"),
                                params, name="op")
        ff.compile(devices=jax.devices()[:1], defer_weights=True)
        ff.set_weights({out.owner_op.name: jax.tree.map(
            np.asarray, fam.make_op(KEY, 1, d=fam.dims(cfg), kind="moe",
                                    heads=0, dtype=jnp.dtype("float32")))})
        total += np.asarray(ff.forward({"x": x})) - shared
    close(total + shared, whole, OP_TOL)


# -- 5. what the family refuses, by name -----------------------------------------------
def _front(**ffconfig):
    from flexflow_tpu.serving import build_front

    return build_front(holder(**ffconfig))


def _dense_cache():
    from flexflow_tpu.decoding import make_decoder

    return make_decoder(holder(), batch_size=2, devices=jax.devices()[:1])


NOT_CARRIED = {
    "prefix_cache": lambda: _front(prefix_cache=True),
    "speculative": lambda: _front(spec_decode="ngram"),
    "handoff": lambda: _front(serving_handoff=True),
    "tensor_parallel": lambda: _front(serving_tp=2),
    "dense_cache": _dense_cache,
}


@pytest.mark.parametrize("feature", sorted(NOT_CARRIED))
def test_feature_not_carried_is_a_config_error_by_name(feature):
    with pytest.raises(ConfigError) as err:
        NOT_CARRIED[feature]()
    assert "laguna does not carry" in str(err.value)
    assert feature in str(err.value)


def test_a_prefill_chunk_past_the_ring_is_refused_by_name():
    with pytest.raises(ConfigError) as err:
        _front(prefill_chunk=6)  # the rings were sized for 4
    assert "prefill_chunk 6" in str(err.value)
    assert "ring 12" in str(err.value) and "sliding_window 8" in str(err.value)


def test_per_layer_lists_must_be_as_long_as_the_depth():
    kw = fam.published(CFG)
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        ff = FFModel(FFConfig(batch_size=1, num_devices=1))
        with pytest.raises(ConfigError, match=key):
            build_laguna(ff, 1, 8, **{**kw, key: kw[key][:-1]})


def test_a_twin_ring_shorter_than_window_and_step_is_refused():
    from flexflow_tpu.ops.op import ShapeError

    ff = FFModel(FFConfig(batch_size=2, num_devices=1))
    x = ff.create_tensor([2, 4, D.e], name="x")
    with pytest.raises(ShapeError, match="window_ring"):
        ff.multihead_attention(
            x, x, x, D.e, 8, kdim=8 * D.hd, vdim=8 * D.hd, causal=True,
            num_kv_heads=D.kvh, sliding_window=W, name="op",
            decode_max_seq=64, kv_page_size=4, kv_num_blocks=9,
            window_ring=W + 2)


def test_served_builders_are_named_in_the_recipe_error():
    from flexflow_tpu.decoding import decoder_recipe
    from flexflow_tpu.models import SERVED_BUILDERS
    import importlib

    for name in SERVED_BUILDERS:
        module, fn = name.split(".")
        assert callable(getattr(importlib.import_module(
            f"flexflow_tpu.models.{module}"), fn))
    with pytest.raises(ValueError) as err:
        decoder_recipe(FFModel(FFConfig(batch_size=1, num_devices=1)))
    assert "models.laguna.build_laguna" in str(err.value)
    assert "models.ouro.build_ouro" in str(err.value)
