"""What a CPU sandbox can hold the chip bring-up to (PR 21):

  * every Pallas entry point chip_smoke.py reaches lowers for TPU at
    the smoke's own shapes (`lowering_platforms=("tpu",)`), so a
    block-shape refusal is caught here, not on the chip — and, where
    libtpu's compile-only topology is available, also COMPILES for a
    v5e (Mosaic included);
  * the compile cache is placed from outside when
    $JAX_COMPILATION_CACHE_DIR is set, at the fixed checkout path when
    it is not, and an explicit directory is still honoured;
  * an accelerator nobody has entered peaks for is an error, never a
    default roofline;
  * a flash shape the tiling cannot cover is visible on TPU.
"""
import importlib.util
import os
import tempfile
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flexflow_tpu.config import FFConfig
from flexflow_tpu.ops.pallas import flash_attention as fa
from flexflow_tpu.ops.pallas import gated_delta_rule as gdr
from flexflow_tpu.ops.pallas import paged_attention as pk

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


chip_smoke = _load_chip_smoke()
FULL = chip_smoke.FULL


# -- (a) Pallas entry points at the smoke's shapes ----------------------

def _flash_cases():
    s = FULL["bert_long"]
    bh, seq = s["batch"] * s["heads"], s["seq"]
    d = s["hidden"] // s["heads"]
    x = jax.ShapeDtypeStruct((bh, seq, d), jnp.dtype(FULL["dtype"]))
    lse = jax.ShapeDtypeStruct((bh, seq), jnp.float32)

    def fwd(q, k, v):
        return fa._flash_fwd_pallas(
            q, k, v, 0.125, False, *fa._pick_blocks("fwd", seq, seq))

    def bwd(q, k, v, o, lse, do):
        return fa._flash_bwd_pallas(
            q, k, v, o, lse, do, 0.125, False,
            *fa._pick_blocks("dq", seq, seq),
            dkv_blocks=fa._pick_blocks("dkv", seq, seq))

    return [("flash_fwd", fwd, (x, x, x), 1),
            ("flash_dq_dkv", bwd, (x, x, x, x, lse, x), 2)] \
        + _latent_flash_cases() + _one_tile_cases()


def _latent_flash_cases():
    """The long-row kernels as a latent-attention training step hands
    them over (PR 43): keys of 192 padded to 256 lanes against values of
    128, 8,192 positions, causal: K and V stay resident past the
    default VMEM (`_resident_vmem`)."""
    bh, seq = 32, 8192
    qk = jax.ShapeDtypeStruct((bh, seq, fa.lane_width(192)), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((bh, seq, 128), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((bh, seq), jnp.float32)

    def fwd(q, k, v):
        return fa._flash_fwd_pallas(
            q, k, v, 192 ** -0.5, True, *fa._pick_blocks("fwd", seq, seq))

    def bwd(q, k, v, o, lse, do):
        return fa._flash_bwd_pallas(
            q, k, v, o, lse, do, 192 ** -0.5, True,
            *fa._pick_blocks("dq", seq, seq),
            dkv_blocks=fa._pick_blocks("dkv", seq, seq))

    return [("latent_flash_fwd", fwd, (qk, qk, v), 1),
            ("latent_flash_dq_dkv", bwd, (qk, qk, v, v, lse, v), 2)]


def _one_tile_cases():
    """The short-row tiling at BERT-large's seq-512 step (the training
    cells' shapes: 16 heads of 64, two a 128-lane block) and at its
    longest row in float32, causal, where the tile is largest."""
    cases = []
    for name, b, s, h, d, dtype, causal in (
            ("cell", 8, 512, 16, 64, jnp.bfloat16, False),
            ("longest", 2, fa.ONE_TILE_MAX_KV, 4, 128, jnp.float32, True)):
        x = jax.ShapeDtypeStruct((b, s, h * d), dtype)
        w = max(d, 128)
        lse = jax.ShapeDtypeStruct((b, h * d // w, w // d, s), jnp.float32)
        kw = dict(d=d, scale=float(d) ** -0.5, causal=causal)

        def fwd(q, k, v, kw=kw):
            return fa._one_tile_fwd(q, k, v, **kw)

        def bwd(q, k, v, o, lse, do, kw=kw):
            return fa._one_tile_bwd(q, k, v, o, lse, do, **kw)

        cases += [(f"one_tile_fwd_{name}", fwd, (x, x, x), 1),
                  (f"one_tile_bwd_{name}", bwd, (x, x, x, x, lse, x), 1)]
    return cases


#: benchmarks/configs/gpt2-medium-serve.json as its cell runs it: 16
#: slots, page 16, 16 heads x 64, table width 64, 513 blocks
CELL_GEOMETRY = (16, 16, 16, 64, 64, 513)
#: benchmarks/configs/ouro-2.6b-serve.json: 16 heads x 128, table
#: width 20, four planes of 321 blocks a layer
LOOP_CELL_GEOMETRY = (16, 16, 16, 128, 20, 4 * 321)


def _paged_args(s, dtype, sharding=lambda *_: None, geometry=None):
    slots, page, h, d, tw, nb = (geometry
                                 or chip_smoke.paged_geometry(FULL))

    def S(shape, dt, kind):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding(kind))

    return (S((slots, s, h, d), dtype, "heads"),
            S((nb, page, h, d), dtype, "heads"),
            S((nb, page, h, d), dtype, "heads"),
            S((slots, tw), jnp.int32, "rep"),
            S((slots,), jnp.int32, "rep"))


def _paged(q, k, v, bt, sl):
    return pk.paged_attention(q, k, v, bt, sl, 0.125, interpret=False)


def _paged_cases():
    cases = []
    for s in (1, FULL["chunk"]):
        for dtype in (jnp.float32, jnp.bfloat16):
            cases.append((f"paged_s{s}_{jnp.dtype(dtype).name}", _paged,
                          _paged_args(s, dtype), 1))
    # the seq-1 read the serving cells' step programs run and the
    # seq-8 chunk twin (the loop cell's one-pass prefill), at each
    # cell's own widths
    for s in (1, 8):
        cases.append((f"paged_cell_s{s}", _paged, _paged_args(
            s, jnp.bfloat16, geometry=CELL_GEOMETRY), 1))
        cases.append((f"paged_loop_cell_s{s}", _paged, _paged_args(
            s, jnp.bfloat16, geometry=LOOP_CELL_GEOMETRY), 1))
    return cases


def _paged_tp2(mesh):
    """The --serving-tp dispatch: shard_map over the head axis, each
    shard sees the local [nb, page, h/2, d] pool."""
    heads = P(None, None, "model", None)

    def f(q, k, v, bt, sl):
        return jax.shard_map(
            _paged, mesh=mesh,
            in_specs=(heads, heads, heads, P(None, None), P(None)),
            out_specs=heads, check_vma=False)(q, k, v, bt, sl)

    def sharding(kind):
        return NamedSharding(mesh, heads if kind == "heads" else P())

    return [(f"paged_tp2_s{s}", f,
             _paged_args(s, jnp.bfloat16, sharding), 1)
            for s in (1, FULL["chunk"])]


def _gdn_cases():
    """The delta rule's kernel at the qwen3-next serving cell's widths
    (64 slots, 32 value heads of 128 x 128, float32): its decode step,
    its prefill chunk of 8, and the longest step the picker lets in."""
    b, h, dk, dv = 64, 32, 128, 128

    def S(*shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt)

    def rule(*args):
        return gdr.gated_delta_rule(*args, interpret=False)

    return [(f"gated_delta_rule_s{s}", rule,
             (S(b, h, dk, dv), S(b, s, h, dk), S(b, s, h, dk),
              S(b, s, h, dv), S(b, s, h), S(b, s, h), S(b, dt=jnp.int32)), 1)
            for s in (1, 8, gdr.MAX_STEP_TOKENS)]


def _lower(fn, args):
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))


def test_pallas_entry_points_lower_for_tpu(devices8):
    mesh = Mesh(np.array(devices8[:2]).reshape(1, 2), ("data", "model"))
    for name, fn, args, n_calls in (_flash_cases() + _paged_cases()
                                    + _gdn_cases() + _paged_tp2(mesh)):
        text = _lower(fn, args).as_text()
        assert text.count("tpu_custom_call") >= n_calls, name


@pytest.fixture(scope="module")
def v5e():
    """libtpu's compile-only `v5e:2x2` topology: needs no chip; tests
    that use it are skipped where it cannot be created."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, no topology
        pytest.skip(f"no compile-only TPU topology here: {e!r}")


@pytest.fixture(scope="module")
def v5e_chip(v5e):
    """One described chip of it."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e.devices[0])


def test_pallas_entry_points_compile_for_v5e(v5e, v5e_chip):
    """Stronger than lowering: the TPU compiler itself (Mosaic, VMEM
    allocation) accepts every kernel for a v5e 2x2 host."""
    topo, one = v5e, v5e_chip

    def on(args, sh):
        return tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
                     for a in args)

    for name, fn, args, _ in (_flash_cases() + _paged_cases()
                              + _gdn_cases()):
        _lower(fn, on(args, one)).compile()
    mesh = Mesh(np.array(topo.devices[:2]).reshape(1, 2),
                ("data", "model"))
    for name, fn, args, _ in _paged_tp2(mesh):
        _lower(fn, args).compile()


#: benchmarks/configs/laguna-xs2-ep8-serve.json: a head-major pool of 8
#: key/value heads x 128 a full layer, 32 slots, table width 1,024
MIXED_CELL_GEOMETRY = (32, 16, 8, 128, 1024, 16385)


@pytest.mark.parametrize("chunk", [1, 16])
@pytest.mark.parametrize("heads", [48, 64])
def test_head_major_walk_compiles_for_v5e(heads, chunk, v5e_chip):
    """The head-major read at the mixed-context cell's shapes: Mosaic
    accepts the hand-issued copies from the HBM-resident pool (a block
    `[8, 16, 128]` bf16 is one lane-aligned slab) and the tiles the
    launch chooses from its shapes fit the kernel's VMEM, under the
    decode step's one query a row and the pass's chunk, at both of the
    configuration's head ratios."""
    slots, page, h, d, tw, nb = MIXED_CELL_GEOMETRY

    def S(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def read(q, k, v, bt, sl):
        return pk.paged_attention(q, k, v, bt, sl, d ** -0.5,
                                  interpret=False, head_major=True)

    lowered = _lower(read, (S(slots, chunk, heads, d), S(nb, h, page, d),
                            S(nb, h, page, d), S(slots, tw, dtype=jnp.int32),
                            S(slots, dtype=jnp.int32)))
    assert lowered.as_text().count("tpu_custom_call") == 1
    lowered.compile()


#: benchmarks/configs/glm52-ep16-serve.json: latent pools of 25,601 pages
#: `[16, 640]` (576 padded to whole lane tiles), 32 slots, 64 heads, a
#: table of 800 pages, latent rank 512
SELECTED_CELL_GEOMETRY = (32, 16, 64, 640, 512, 800, 25601)


@pytest.mark.parametrize("chunk", [1, 16])
def test_selected_walk_compiles_for_v5e(chunk, v5e_chip):
    """The selected read in place at the long-context cell's shapes:
    Mosaic accepts the hand-issued copies of `[16, 640]` bf16 pages out
    of the HBM-resident latent pool, the one-byte mask a tile at a time,
    the count of a key's picks transposed by a product, and the
    launch's own tile and fold fit the VMEM it asks for, under the
    decode step's one query a row and the pass's chunk."""
    from flexflow_tpu.ops.pallas import selected_attention as sa

    slots, page, heads, width, rank, tw, nb = SELECTED_CELL_GEOMETRY
    assert sa.walk_fits(chunk, page, width)

    def S(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def read(q, pool, bt, sl, keep):
        return sa.selected_latent_attention(q, pool, bt, sl, keep,
                                            width ** -0.5, rank,
                                            interpret=False)

    lowered = _lower(read, (S(slots, chunk, heads, width),
                            S(nb, page, width), S(slots, tw, dtype=jnp.int32),
                            S(slots, dtype=jnp.int32),
                            S(slots, chunk, tw * page, dtype=jnp.bool_)))
    assert lowered.as_text().count("tpu_custom_call") == 1
    lowered.compile()


#: a routed layer of the two training cells: (usual slots, hidden,
#: expert width, held, rows a group expects)
GROUPED_LAYERS = {"cell6_lfm2": (12288, 2048, 1792, 8, 1024.0),
                  "cell8_kimi": (3072, 2304, 1024, 8, 256.0)}


@pytest.mark.parametrize("product", ["gate_up", "down", "d_act", "d_xs",
                                     "d_w_gate_up", "d_w_down"])
@pytest.mark.parametrize("cell", sorted(GROUPED_LAYERS))
def test_grouped_products_compile_for_v5e(cell, product, v5e_chip,
                                          monkeypatch):
    """Each of the six grouped products of a routed layer's forward and
    backward, at the training cells' real shapes and the tiling
    `pick_grouped_tiling` gives a TPU: Mosaic accepts it within the
    default fast memory, and the weight enters as it is stored (the
    compiled program makes no transposed copy of it)."""
    from flexflow_tpu.ops import routed_experts as rx

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    m, e, f, g, rows = GROUPED_LAYERS[cell]
    fn, lhs, rhs = {
        "gate_up": (rx.grouped_matmul, (m, e), (g, e, f)),
        "down": (rx.grouped_matmul, (m, f), (g, f, e)),
        "d_act": (rx.grouped_matmul_into_lhs, (m, e), (g, f, e)),
        "d_xs": (rx.grouped_matmul_into_lhs, (m, f), (g, e, f)),
        "d_w_gate_up": (rx.grouped_matmul_into_rhs, (m, e), (m, f)),
        "d_w_down": (rx.grouped_matmul_into_rhs, (m, f), (m, e)),
    }[product]

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    text = _lower(lambda a, b, sizes: fn(a, b, sizes, rows),
                  (S(lhs), S(rhs), S((g,), jnp.int32))).compile().as_text()
    assert "tpu_custom_call" in text and "ragged-dot" not in text
    weight = "bf16[%d,%d,%d]" % (g, rhs[-1], rhs[-2])  # its transpose
    assert not [line for line in text.splitlines() if weight in line
                and (" copy(" in line or " transpose(" in line)]


def test_paged_kernel_never_interpreted_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = [jnp.zeros(a.shape, a.dtype) for a in
            _paged_args(1, jnp.float32)]
    with pytest.raises(ValueError, match="must run compiled"):
        pk.paged_attention(*args, 0.125, interpret=True)


def test_delta_rule_state_is_updated_in_place_on_tpu():
    """The kernel's state output aliases its state input in the lowered
    module: with the step programs' donation a row's `S` is written
    where it was read, and a row the grid skips is never copied."""
    name, fn, args, _ = _gdn_cases()[1]
    text = jax.jit(fn, donate_argnums=(0,)).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tf.aliasing_output = 0" in text  # the donated S -> result 0
    assert "output_operand_aliases" in text  # the custom call's own alias


def test_flash_unsupported_shape_is_visible_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ok = jax.ShapeDtypeStruct((4, 2048, 64), jnp.bfloat16)
    odd = jax.ShapeDtypeStruct((4, 2000, 64), jnp.bfloat16)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert fa._use_pallas(ok, ok)
    with pytest.warns(UserWarning, match=r"no Pallas tiling.*2000"):
        assert not fa._use_pallas(odd, odd)


# -- (b) compile-cache precedence ---------------------------------------

class _ConfigRecorder:
    def __init__(self, monkeypatch, backend):
        self.updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: self.updates.append((k, v)))
        monkeypatch.setattr(jax, "default_backend", lambda: backend)

    def dirs(self):
        return [v for k, v in self.updates
                if k == "jax_compilation_cache_dir"]


def test_cache_env_var_places_it_from_outside(monkeypatch, tmp_path):
    from flexflow_tpu.store import enable_compilation_cache

    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    rec = _ConfigRecorder(monkeypatch, "tpu")
    assert enable_compilation_cache(FFConfig()) == env_dir
    # a disagreeing --compilation-cache is ignored, not applied
    other = FFConfig(compilation_cache=str(tmp_path / "other"))
    assert enable_compilation_cache(other) == env_dir
    auto = FFConfig(compilation_cache="auto")  # no store: must not raise
    assert enable_compilation_cache(auto) == env_dir
    assert rec.dirs() == []
    assert not os.path.exists(tmp_path / "other")
    # the accelerator-side thresholds still apply
    assert ("jax_persistent_cache_min_compile_time_secs", 0) in rec.updates
    assert ("jax_persistent_cache_min_entry_size_bytes", -1) in rec.updates


def test_cache_default_is_the_fixed_checkout_path(monkeypatch):
    from flexflow_tpu.store import (DEFAULT_COMPILATION_CACHE_DIR,
                                    enable_compilation_cache)

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert DEFAULT_COMPILATION_CACHE_DIR == want
    assert tempfile.gettempdir() not in want
    existed = os.path.isdir(want)
    rec = _ConfigRecorder(monkeypatch, "tpu")
    try:
        assert enable_compilation_cache(FFConfig()) == want
        assert enable_compilation_cache(FFConfig()) == want
        assert rec.dirs() == [want, want]
    finally:
        if not existed and os.path.isdir(want) and not os.listdir(want):
            os.rmdir(want)
    # the CPU backend keeps the default off
    rec_cpu = _ConfigRecorder(monkeypatch, "cpu")
    assert enable_compilation_cache(FFConfig()) is None
    assert rec_cpu.updates == []


def test_cache_explicit_dir_still_honoured(monkeypatch, tmp_path):
    from flexflow_tpu.store import enable_compilation_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    rec = _ConfigRecorder(monkeypatch, "cpu")
    explicit = str(tmp_path / "explicit")
    cfg = FFConfig(compilation_cache=explicit)
    assert enable_compilation_cache(cfg) == explicit
    assert rec.dirs() == [explicit] and os.path.isdir(explicit)


# -- (c) device specs ------------------------------------------------------

class _FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_detect_device_spec_refuses_unknown_accelerators(monkeypatch):
    from flexflow_tpu.sim import machine_model as mm

    assert mm.detect_device_spec() is mm.CPU_BACKEND_DEVICE  # live: cpu
    monkeypatch.setattr(
        jax, "devices", lambda *a: [_FakeDevice("tpu", "TPU v5 lite")])
    assert mm.detect_device_spec() is mm.V5E_DEVICE
    monkeypatch.setattr(
        jax, "devices", lambda *a: [_FakeDevice("tpu", "TPU v9 mega")])
    with pytest.raises(ValueError, match="TPU v9 mega"):
        mm.detect_device_spec()

    def boom(*a):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        mm.detect_device_spec()
