"""The by-scope reduction of a profiler trace (benchmarks/device_scopes.py):
its walk of the xplane's wire format on the small traces recorded on a
TPU v5e, its arithmetic on a made-up plane, and the per-layer readers
that sum a traced stretch by it, end to end on a trace recorded with the
grammar (`benchmarks/tools/record_device_scopes.py`)."""
import json
import os
import shutil
import struct

import pytest

from benchmarks import device_scopes as ds
from benchmarks import reduce_trace as rt
from benchmarks.run import Context, load_module
from flexflow_tpu.obs.scopes import element, parse

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
#: recorded before the grammar: bare op names (`jit(step)/attn_0/..`)
BARE = os.path.join(BENCH, "tests", "recorded_spans.xplane.pb")
#: recorded with it: four steps of the toy lfm2_moe trainer
SCOPED = os.path.join(BENCH, "tests", "recorded_scopes.xplane.pb")


# -- the wire format, on traces from the chip ----------------------------------
def test_walk_reads_the_metadata_profile_data_hides():
    (plane,) = ds.read_planes(BARE)
    assert plane["chip"] == 0 and len(plane["ops"]) > 1000
    used = {m for m, _, _ in plane["ops"]}
    metas = [plane["meta"][m] for m in used]
    assert any(m.tf_op.startswith("jit(step)/attn_0/") for m in metas)
    assert any(m.flops > 0 for m in metas)
    assert any(m.bytes > 0 for m in metas)
    assert {"convolution fusion", "loop fusion"} <= {m.category for m in metas}
    assert all(m.program_id for m in metas)


@pytest.mark.parametrize("path", [BARE, SCOPED], ids=["bare", "scoped"])
def test_seconds_sum_to_reduce_traces_to_the_nanosecond(path):
    rows, dispatches = ds.reduce(path)
    (plane,) = rt.read_planes(path)
    want = sum(e - s for name, s, e in plane["ops"]
               if rt.stem(name) not in rt.ENVELOPES)
    assert abs(ds.total(rows).seconds - want) < 1e-9
    assert ds.total(rows).events == sum(
        rt.stem(name) not in rt.ENVELOPES for name, _, _ in plane["ops"])
    # per program too: the dispatches are `reduce_trace`'s
    modules = rt.reduce(path)["modules"]
    assert dispatches == {ds.program_of(k): len(v)
                          for k, v in modules.items()}


def test_bare_names_are_unnamed_not_a_crash():
    rows, _ = ds.reduce(BARE)
    assert {k.kind for k in rows} == {ds.UNNAMED}
    assert {k.program for k in rows} == {"step", "prefill"}
    # the part column then holds the instruction's stem
    assert any(k.part == "fusion" for k in rows)


# -- a made-up plane --------------------------------------------------------------
def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, float):
        return varint(number << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


STATS = {1: "tf_op", 2: "hlo_category", 3: "flops", 4: "bytes_accessed",
         5: "program_id"}


def metadata(mid, name, tf_op=None, category="loop fusion", flops=0,
             nbytes=0):
    stats = field(5, field(1, 2) + field(5, category)) \
        + field(5, field(1, 3) + field(4, flops)) \
        + field(5, field(1, 4) + field(3, nbytes)) \
        + field(5, field(1, 5) + field(3, 17))
    if tf_op is not None:
        stats += field(5, field(1, 1) + field(5, tf_op))
    return field(4, field(1, mid) + field(2, field(1, mid) + field(2, name)
                                          + stats))


def line(name, timestamp_ns, events):
    body = field(2, name) + field(3, timestamp_ns)
    for mid, offset_ps, duration_ps in events:
        body += field(4, field(1, mid) + field(2, offset_ps)
                      + field(3, duration_ps)
                      + field(4, field(1, 9) + field(3, 5)))  # own stat
    return field(3, body)


def made_up(tmp_path):
    attn = element("MultiHeadAttention", "attn_0")
    dense = element("Linear", "d0")
    step = "jit(step)/"
    plane = field(2, "/device:TPU:0")
    plane += "".encode().join([
        metadata(1, "%fusion.1 = f32[] fusion()",
                 f"{step}jvp({attn})/core/dot_general:",
                 "convolution fusion", 1000, 100),
        # two origins, one place: placed, without a name
        metadata(2, "%fusion.2 = f32[] fusion()",
                 f"{step}jvp({attn})/proj/mul;"
                 f"{step}jvp({element('MultiHeadAttention', 'attn_1')})"
                 "/proj/add:"),
        # two origins that disagree: `mixed`
        metadata(3, "%divide_subtract_fusion.7 = f32[] fusion()",
                 f"{step}transpose(jvp({dense}))/dot_general;"
                 f"{step}optimizer/sub:", "convolution fusion"),
        metadata(4, "%while.3 = (f32[]) while()", f"{step}while:"),
        metadata(5, "%copy.4 = f32[] copy()", None, "data formatting"),
        metadata(6, "%fusion.9 = f32[] fusion()",
                 f"{step}optimizer/sub:", flops=7, nbytes=64),
        metadata(7, "jit_step(123)"),
        # `lax.cond`'s instruction: no `reduce_trace` envelope by its
        # stem, one by its category
        metadata(8, "%cond.3.clone = (f32[]) conditional()", None,
                 "conditional"),
        # `lax.ragged_dot` as XLA's TPU pipeline names it
        metadata(9, "%ragged-dot-none.2 = bf16[] custom-call()",
                 "ragged-dot-none:", "custom-call", 500, 50),
        # layout copies of two arguments: a pool of an op the plane's
        # scoped instructions know, a weight of one they do not
        metadata(10, "%copy.5 = f32[] copy()", "state['attn_0']['k_cache']:",
                 "data formatting"),
        metadata(11, "%copy.6 = f32[] copy()", "weights['ffn_9']['kernel']:",
                 "data formatting"),
    ])
    plane += "".encode().join(
        field(5, field(1, k) + field(2, field(1, k) + field(2, v)))
        for k, v in STATS.items())
    plane += line("XLA Modules", 1, [(7, 0, 10_000_000), (7, 20_000_000,
                                                          10_000_000)])
    plane += line("XLA Ops", 1, [
        (4, 0, 9_000_000),            # the envelope spans its body
        (1, 1_000_000, 2_000_999),    # 2,000 ns: whole nanoseconds
        (2, 3_500_000, 1_000_000),
        (3, 5_000_000, 3_000_000),
        (5, 8_500_000, 500_000),
        (10, 9_000_000, 250_000),
        (11, 9_250_000, 250_000),
        (8, 20_500_000, 8_000_000),   # spans the three after it
        (1, 21_000_000, 2_000_000),
        (9, 23_000_000, 1_000_000),
        (6, 24_000_000, 4_000_000),
        (5, 40_000_000, 1_000_000),   # after the last dispatch ended
    ])
    plane += line("Steps", 1, [(7, 0, 30_000_000)])  # a line nobody asks for
    host = field(2, "/host:CPU") + line("python", 1, [(1, 0, 5)] * 50)
    # a second chip, written first: the reduction is the FIRST chip's
    other = field(2, "/device:TPU:1") + metadata(
        1, "%fusion.1 = f32[] fusion()", f"{step}{dense}/dot_general:") \
        + line("XLA Ops", 1, [(1, 0, 7_000_000)])
    path = tmp_path / "made_up.xplane.pb"
    path.write_bytes(field(1, host) + field(1, other) + field(1, plane))
    return str(path)


CASES = {
    "placed": (ds.Key("step", "MultiHeadAttention", "core", "forward",
                      "convolution fusion"), 4e-6, 2, 2000.0, 200.0),
    "joined_one_place": (ds.Key("step", "MultiHeadAttention", "proj",
                                "forward", "loop fusion"), 1e-6, 1, 0, 0),
    "joined_two_places": (ds.Key("step", "mixed", None, None,
                                 "convolution fusion"), 3e-6, 1, 0, 0),
    "unnamed_in_a_dispatch": (ds.Key("step", ds.UNNAMED, "copy", "forward",
                                     "data formatting"), 5e-7, 1, 0, 0),
    "unnamed_outside_any": (ds.Key("no program", ds.UNNAMED, "copy",
                                   "forward", "data formatting"),
                            1e-6, 1, 0, 0),
    "optimizer": (ds.Key("step", "optimizer", None, "forward",
                         "loop fusion"), 4e-6, 1, 7.0, 64.0),
    "envelope_by_category": (ds.Key("step", ds.ENVELOPE, "cond.3.clone",
                                    "forward", "conditional"),
                             8e-6, 1, 0, 0),
    "argument_of_a_known_op": (ds.Key("step", "MultiHeadAttention",
                                      "arg_layout", None,
                                      "data formatting"), 2.5e-7, 1, 0, 0),
    "argument_of_an_unknown_op": (ds.Key("step", ds.UNNAMED, "copy", None,
                                         "data formatting"),
                                  2.5e-7, 1, 0, 0),
    "renamed_by_xla": (ds.Key("step", "RoutedExperts", "products", None,
                              "custom-call"), 1e-6, 1, 500.0, 50.0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_made_up_plane_lands_in_the_right_rows(case, tmp_path):
    rows, dispatches = ds.reduce(made_up(tmp_path))
    key, seconds, events, flops, nbytes = CASES[case]
    assert dispatches == {"step": 2}
    assert set(rows) == {c[0] for c in CASES.values()}  # no envelope row
    row = rows[key]
    assert row.seconds == pytest.approx(seconds, abs=1e-12)
    assert (row.events, row.flops, row.bytes) == (events, flops, nbytes)


def test_made_up_plane_reads_like_profile_data(tmp_path):
    """The file the cases are made of is an xplane `ProfileData` reads
    too, and both readings agree."""
    path = made_up(tmp_path)
    plane, other = rt.read_planes(path)
    assert (plane["chip"], other["chip"]) == (0, 1)
    assert [p["chip"] for p in ds.read_planes(path)] == [0, 1]
    assert len(plane["ops"]) == 12 and len(plane["modules"]) == 2
    rows, _ = ds.reduce(path)
    want = sum(e - s for name, s, e in plane["ops"]
               if rt.stem(name) not in rt.ENVELOPES)
    assert ds.total(rows).seconds == pytest.approx(want, abs=1e-9)


def test_total_grouped_and_table(tmp_path):
    rows, dispatches = ds.reduce(made_up(tmp_path))
    assert ds.total(rows, kind=("mixed", ds.UNNAMED)).events == 4
    # every share is a share of the work: the envelope spans its body
    assert ds.total(rows).seconds == pytest.approx(23e-6)
    assert ds.total(ds.work(rows)).seconds == pytest.approx(15e-6)
    assert ds.total(rows, kind="optimizer", category="loop fusion").flops == 7
    by_kind = ds.grouped(ds.work(rows), "kind")   # largest first
    assert list(by_kind)[0] == ("MultiHeadAttention",)
    peak = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}
    lines = ds.table(rows, dispatches, peak)
    assert "ONE tf_op" in lines[0] and "convolution fusion" in lines[0]
    text = "\n".join(lines)
    assert "program step" in text and "over 2 (2 in the stretch)" in text
    # 2,000 FLOPs in 4 us over two dispatches: 1 us a dispatch at the peak
    assert "MultiHeadAttention | core | forward | convolution fusion | " \
           "0.002 ms" in text and "floor 0.001 ms" in text
    assert "envelope | cond.3.clone" in text
    assert ds.share(ds.total(rows, kind="mixed"), ds.total(ds.work(rows))) \
        == pytest.approx(100 * 3e-6 / 15e-6)
    unplaced = __import__("collections").defaultdict(float)
    ds.reduce(made_up(tmp_path), unplaced=unplaced)
    assert ("copy", "data formatting", "weights['ffn_9']['kernel']:",
            "f32[]") in unplaced
    assert ds.unnamed_line(unplaced).startswith("unnamed, the largest")
    assert ds.result_shape(
        "%copy-start.53 = (f32[4,16,32]{2,1,0:T(8,128)}, u32[]{:S(2)}) "
        "copy-start(f32[4,16,32]{2,1,0} %get-tuple-element.878)") \
        == "f32[4,16,32]"


# -- the readers, on a trace recorded with the grammar ---------------------------
@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A Context as `run.py` leaves it after a traced run of a training
    cell whose stretch is the recorded trace."""
    trace_dir = tmp_path_factory.mktemp("trace")
    shutil.copy(SCOPED, trace_dir / "recorded.xplane.pb")
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peak = json.load(f)["devices"]["TPU v5 lite"]
    lines = []
    ctx = Context(peak=peak, cell={"name": "toy"})
    ctx.out = lines.append
    ctx.trace_dir = str(trace_dir)
    ctx.trace_summary = rt.reduce(SCOPED, 1)
    ctx.counters["traced_steps"] = len(ctx.trace_summary["modules"]["jit_step"])
    return ctx, lines


def read(ctx, name):
    return load_module("readers", name).read(ctx, {"name": name})


def test_recorded_stretch_is_named(traced):
    ctx, lines = traced
    assert ctx.counters["traced_steps"] == 4
    unnamed = read(ctx, "scope.unnamed_share.train")
    assert 0 <= unnamed < 40, lines   # toy sizes: `copy-done` weighs
    rows, per = ds.scope_view(ctx)
    assert per["step"] == 4
    kinds = {k.kind for k in rows}
    assert {"RoutedExperts", "ShortConv", "MultiHeadAttention", "optimizer",
            "loss", "RMSNorm"} <= kinds, kinds
    assert {k.phase for k in rows} >= {"forward", "backward", "recompute"}
    # the table went out once, with the limit stated where it is printed
    assert sum("device time by scope" in line for line in lines) == 1
    # and it sums to reduce_trace's op total of the same plane
    (plane,) = rt.read_planes(SCOPED)
    want = sum(e - s for name, s, e in plane["ops"]
               if rt.stem(name) not in rt.ENVELOPES)
    assert abs(ds.total(rows).seconds - want) < 1e-9


@pytest.mark.parametrize("metric,low,high", [
    ("optimizer.device_ms", 0.0, 10.0),
    ("recompute.device_ms", 0.0, 10.0),
    ("experts.device_share.train", 1.0, 99.0),
    ("experts.products_share.train", 1.0, 99.0),
    ("mixer.device_share.capacity", 1.0, 99.0),
])
def test_readers_on_the_recorded_stretch(traced, metric, low, high):
    ctx, lines = traced
    value = read(ctx, metric)
    assert value is not None and low < value < high, (value, lines[-3:])
    # a reader's number is a sum of the table's rows
    rows, per = ds.scope_view(ctx)
    if metric == "optimizer.device_ms":
        assert value == pytest.approx(
            1e3 * ds.total(rows, kind="optimizer").seconds / per["step"])
    if metric == "recompute.device_ms":
        assert value == pytest.approx(
            1e3 * ds.total(rows, phase="recompute").seconds / per["step"])


def test_readers_leave_their_metric_out_without_the_grammar(
        traced, monkeypatch):
    """The parent of the PR that brought the grammar has no
    `flexflow_tpu.obs.scopes`: every reader gives None and raises
    nothing."""
    import builtins

    real = builtins.__import__

    def no_scopes(name, *args, **kwargs):
        if name == "flexflow_tpu.obs.scopes":
            raise ImportError(name)
        return real(name, *args, **kwargs)

    ctx = Context(peak=traced[0].peak, cell={"name": "toy"})
    ctx.trace_dir, ctx.trace_summary = traced[0].trace_dir, \
        traced[0].trace_summary
    ctx.counters["traced_steps"] = 4
    ctx.out = lambda line: None
    monkeypatch.setattr(builtins, "__import__", no_scopes)
    for name in ("scope.unnamed_share.train", "scope.unnamed_share.capacity",
                 "optimizer.device_ms", "recompute.device_ms",
                 "experts.device_share.train", "experts.products_share.train",
                 "experts.device_share.capacity", "mixer.device_share.capacity"):
        assert read(ctx, name) is None


def test_readers_leave_their_metric_out_without_a_trace():
    ctx = Context(peak=None, cell={"name": "toy"})
    ctx.out = lambda line: None
    assert read(ctx, "optimizer.device_ms") is None
    assert read(ctx, "scope.unnamed_share.capacity") is None


def test_parse_is_the_readers_one_reader():
    """What `reduce` is handed by default is the program's own parser."""
    rows, _ = ds.reduce(SCOPED)
    assert rows.keys() == ds.reduce(SCOPED, parse)[0].keys()
