"""A traced stretch's device time, summed by the names the PROGRAM gave
its work (`flexflow_tpu/obs/scopes.py`: op kind, op name, part, loss,
optimizer, weight casts), not by the names XLA made up.

    python -m benchmarks.device_scopes <file.xplane.pb>

Every event of a TPU plane's ``XLA Ops`` line points at an event
METADATA record of the plane, and that record carries the stats
``tf_op`` (the instruction's `op_name`: the scope path it was traced
under), ``hlo_category`` (``convolution fusion``, ``loop fusion``,
``custom-call`` ...), ``flops``, ``bytes_accessed`` and ``program_id``.
`jax.profiler.ProfileData` (what `reduce_trace.read_planes` uses) shows
an event's own stats only, so `read_planes` here walks the protobuf wire
format itself: the planes' names, the TPU planes' two metadata maps and
their ``XLA Ops`` and ``XLA Modules`` lines; every other line and plane
(the host's million Python events) is skipped by its length.  Field
numbers are tsl/profiler/protobuf/xplane.proto's.

`reduce(path)` joins the first chip's events to their metadata, leaves
out the envelopes `reduce_trace.ENVELOPES` leaves out (so its seconds
sum to `reduce_trace`'s op total of the same plane) and gives per
(program, kind, part, phase, hlo_category): seconds, events, FLOPs,
bytes.  The program is the ``XLA Modules`` dispatch the event began in;
kind, part and phase are `scopes.parse(tf_op)`.  An event whose `tf_op`
the parser cannot place (none at all: a copy the compiler inserted; a
bare name from before the grammar) is `UNNAMED`, with its instruction's
stem where the part would stand; one fused from differently placed
origins is `mixed`.  Neither is ever laid at a neighbour.  Two kinds of
instruction lost their path to the compiler and are placed by what is
left of it (`scopes.parse` says how): a primitive XLA rewrote under a
name of its own (`ragged-dot-none`: kind and part, no phase), and an
instruction made from one of the program's ARGUMENTS (the layout copy
of a weight or a pool, named `state['attn_0']['k_cache']`: the op's
name, whose kind the plane's scoped instructions give; part
`arg_layout`).

**The limit.**  A fusion has ONE `tf_op`, and which of its origins' it
is is the compiler's choice: on the v5e a ``convolution fusion`` carries
its PRODUCT's.  XLA fuses Adam's update into the weight-gradient
matmuls, so that update's time lies in the layer's own ``backward |
convolution fusion`` row (read it off the row's GB/s, far above what the
product alone moves, and off its distance from the floor), and
`optimizer` holds only the update XLA did NOT fuse (``loop fusion``: the
weights whose gradient is no plain product, e.g. the routed experts').
Time INSIDE a fusion cannot be split.

`scope_view(ctx)` reads a traced run's stretch once for all readers
(`readers/scope.*.py`, `optimizer.device_ms.py`, ...) and prints the
table through ``ctx.out``; on a tree without the grammar it is None and
every such reader leaves its metric out.
"""
from __future__ import annotations

import bisect
import json
import os
import re
import struct
import sys
import time
from collections import defaultdict, namedtuple

from benchmarks.reduce_trace import (DEVICE_PLANE, ENVELOPES, MODULES_LINE,
                                     OPS_LINE, module_name, stem)

UNNAMED = "unnamed"
#: a control-flow instruction whose stem `reduce_trace.ENVELOPES` does
#: not know (`cond.3.clone` of `lax.cond`, category `conditional`): its
#: event spans its body's events, which are listed.  Shown, so that the
#: table still sums to `reduce_trace`'s total, and left out of every
#: share (`work`)
ENVELOPE = "envelope"
ENVELOPE_CATEGORIES = ("conditional", "while", "call")
WANTED = ("tf_op", "hlo_category", "flops", "bytes_accessed", "program_id")

_RESULT_SHAPE = re.compile(r"= \(?(\w+\[[\d,]*\])")

OpMeta = namedtuple("OpMeta", "name tf_op category flops bytes program_id")
Key = namedtuple("Key", "program kind part phase category")


class Row:
    __slots__ = ("seconds", "events", "flops", "bytes")

    def __init__(self):
        self.seconds = self.flops = self.bytes = 0.0
        self.events = 0

    def add(self, other):
        self.seconds += other.seconds
        self.events += other.events
        self.flops += other.flops
        self.bytes += other.bytes


# -- the wire format ---------------------------------------------------------
def _varint(buf, i):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf, i, end):
    """(field, wire type, value) of one message: an int for a varint,
    (start, end) for a length-delimited or a fixed field."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = (i, i + n), i + n
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, wire, value


def _text(buf, span):
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf, span):
    """(key, value span) of a protobuf map entry with an integer key."""
    key = value = None
    for f, _, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stat(buf, span, stat_names):
    """(stat name, value) of one XStat."""
    name = value = None
    for f, wire, v in _fields(buf, *span):
        if f == 1:
            name = stat_names.get(v)
        elif f == 2:
            value = struct.unpack("<d", buf[v[0]:v[1]])[0]
        elif f in (3, 4):
            value = v
        elif f in (5, 6):
            value = _text(buf, v)
        elif f == 7:    # a string kept once, as a stat's name
            value = stat_names.get(v, "")
    return name, value


def _events(buf, line):
    """[(metadata id, start_ps, duration_ps)] of one XLine, on the
    plane's clock.  A traced stretch holds 10^5 to 10^6 of them: the one
    loop here that is written for speed (one-byte keys decoded in line,
    an event's own stats skipped by their length)."""
    base_ps, out = 0, []
    i, end = line
    while i < end:
        key, i = _varint(buf, i)
        if key == 0x22:                     # field 4: an XEvent
            n, i = _varint(buf, i)
            stop = i + n
            got = [0, 0, 0, 0]              # by field: metadata, offset, duration
            while i < stop:
                k = buf[i]
                i += 1
                if k & 7 == 0:
                    c = buf[i]
                    i += 1
                    x = c & 0x7F
                    shift = 7
                    while c >= 0x80:
                        c = buf[i]
                        i += 1
                        x |= (c & 0x7F) << shift
                        shift += 7
                    if k <= 0x18:
                        got[k >> 3] = x
                elif k & 7 == 2:
                    n = buf[i]
                    i += 1
                    if n >= 0x80:
                        n, i = _varint(buf, i - 1)
                    i += n
                else:
                    i += 8 if k & 7 == 1 else 4
            out.append((got[1], got[2], got[3]))
        elif key & 7 == 0:
            value, i = _varint(buf, i)
            if key == 0x18:                 # field 3: timestamp_ns
                base_ps = 1000 * value
        elif key & 7 == 2:
            n, i = _varint(buf, i)
            i += n
        else:
            i += 8 if key & 7 == 1 else 4
    return [(m, base_ps + offset, dur) for m, offset, dur in out]


def read_planes(path: str):
    """Yields {chip, ops, modules, meta} per TPU plane that holds
    operations, lowest chip first, each decoded only when asked for (a
    four-chip capture is read for its first chip alone): ``ops`` and
    ``modules`` as [(metadata id, start_ps, duration_ps)], ``meta`` as
    {metadata id: OpMeta}."""
    with open(path, "rb") as f:
        buf = f.read()
    device_planes = []
    for f, wire, span in _fields(buf, 0, len(buf)):
        if f != 1 or wire != 2:
            continue
        top = defaultdict(list)
        for f2, wire2, v in _fields(buf, *span):
            if wire2 == 2:
                top[f2].append(v)
        m = DEVICE_PLANE.match(_text(buf, top[2][0])) if top[2] else None
        if m:
            device_planes.append((int(m.group(1)), top))
    for chip, top in sorted(device_planes, key=lambda p: p[0]):
        lines = {}
        for line in top[3]:
            name = next((_text(buf, v) for f3, w3, v in _fields(buf, *line)
                         if f3 == 2 and w3 == 2), "")
            if name in (OPS_LINE, MODULES_LINE):
                lines[name] = _events(buf, line)
        if not lines.get(OPS_LINE):
            continue
        stat_names = {}
        for entry in top[5]:
            key, value = _map_entry(buf, entry)
            stat_names[key] = next(
                (_text(buf, v) for f3, w3, v in _fields(buf, *value)
                 if f3 == 2 and w3 == 2), "")
        meta = {}
        for entry in top[4]:
            key, value = _map_entry(buf, entry)
            name, stats = "", {}
            for f3, w3, v in _fields(buf, *value):
                if f3 == 2 and w3 == 2:
                    name = _text(buf, v)
                elif f3 == 5 and w3 == 2:
                    k, val = _stat(buf, v, stat_names)
                    if k in WANTED:
                        stats[k] = val
            meta[key] = OpMeta(name, stats.get("tf_op") or "",
                               stats.get("hlo_category") or "",
                               float(stats.get("flops") or 0),
                               float(stats.get("bytes_accessed") or 0),
                               stats.get("program_id"))
        yield {"chip": chip, "ops": lines[OPS_LINE],
               "modules": lines.get(MODULES_LINE, []), "meta": meta}


# -- the reduction ------------------------------------------------------------
def program_of(module: str) -> str:
    """``jit_step(5197994995008307429)`` -> ``step``."""
    name = module_name(module)
    return name[4:] if name.startswith("jit_") else name


def reduce_plane(plane, parse, unplaced=None):
    """({Key: Row}, {program: dispatches}) of one plane; ``parse`` is
    `flexflow_tpu.obs.scopes.parse`.  A dict given as ``unplaced`` is
    added the `UNNAMED` seconds by (stem, hlo_category, tf_op, result
    shape)."""
    meta = plane["meta"]
    runs = sorted((start, start + dur, program_of(meta[m].name))
                  for m, start, dur in plane["modules"] if m in meta)
    starts = [r[0] for r in runs]
    ends = [r[1] for r in runs]
    programs = [r[2] for r in runs]
    dispatches = defaultdict(int)
    for program in programs:
        dispatches[program] += 1
    # nanoseconds and events per (instruction, program it ran in), then
    # per row: an instruction is placed once, however often it ran
    seen = defaultdict(lambda: [0, 0])
    find = bisect.bisect_right
    for m, start, dur in plane["ops"]:
        i = find(starts, start) - 1
        cell = seen[m, programs[i] if i >= 0 and start < ends[i] else None]
        # whole nanoseconds, as `ProfileData` cuts them: the sums are
        # `reduce_trace`'s to the nanosecond
        cell[0] += dur // 1000
        cell[1] += 1
    blank = OpMeta("", "", "", 0.0, 0.0, None)
    placed = {m: parse(meta.get(m, blank).tf_op) for m, _ in seen}
    # an instruction the compiler made from an ARGUMENT carries the op's
    # name alone (`state['attn_0']['k_cache']`): its kind is the one the
    # plane's scoped instructions give that name
    kind_of = {s.name: s.kind for s in placed.values() if s.kind and s.name}
    rows = defaultdict(Row)
    for (m, program), (ns, events) in seen.items():
        om = meta.get(m, blank)
        if stem(om.name) in ENVELOPES:
            continue
        s = placed[m]
        kind, part = s.kind or kind_of.get(s.name), s.part
        if om.category in ENVELOPE_CATEGORIES:
            kind, part = ENVELOPE, stem(om.name)
        elif kind is None:
            kind, part = UNNAMED, stem(om.name)
            if unplaced is not None:
                unplaced[part, om.category, om.tf_op,
                         result_shape(om.name)] += ns * 1e-9
        # an event that began in no dispatch keeps the program its name
        # says, if it says one
        row = rows[Key(program or s.program or "no program", kind, part,
                       s.phase, om.category)]
        row.seconds += ns * 1e-9
        row.events += events
        row.flops += events * om.flops
        row.bytes += events * om.bytes
    return dict(rows), dict(dispatches)


def reduce(path: str, parse=None, unplaced=None):
    """`reduce_plane` of the first chip that ran operations."""
    if parse is None:
        from flexflow_tpu.obs.scopes import parse
    plane = next(read_planes(path), None)
    if plane is None:
        raise ValueError("the trace holds no operation on any TPU plane")
    return reduce_plane(plane, parse, unplaced)


def total(rows, **where) -> Row:
    """The sum of the rows whose key matches ``where``: each value one
    key field's wanted value, or a tuple of wanted values."""
    out = Row()
    for key, row in rows.items():
        if all(getattr(key, f) in (want if isinstance(want, tuple)
                                   else (want,))
               for f, want in where.items()):
            out.add(row)
    return out


def work(rows):
    """The rows without the envelopes: the device's operation time,
    each nanosecond once; what every share is a share of."""
    return {k: r for k, r in rows.items() if k.kind != ENVELOPE}


def grouped(rows, *fields):
    """{tuple of the key's ``fields``: Row}, largest first."""
    out = defaultdict(Row)
    for key, row in rows.items():
        out[tuple(getattr(key, f) for f in fields)].add(row)
    return dict(sorted(out.items(), key=lambda kv: -kv[1].seconds))


# -- the table ----------------------------------------------------------------
LIMIT = ("a fusion has ONE tf_op, on this compiler a `convolution "
         "fusion`'s is its PRODUCT's: Adam's update fused into a weight-"
         "gradient product lies in that layer's `backward | convolution "
         "fusion` row (see its GB/s), `optimizer` holds the update XLA did "
         f"not fuse; `{UNNAMED}` = no scope reached it (part = the "
         "instruction's stem), `mixed` = fused from differently placed "
         f"origins, `{ENVELOPE}` = spans its listed body, in no share")


def _line(cells, row, per, whole_s, peak):
    ms = 1e3 * row.seconds / per
    out = (f"  {' | '.join(str(c or '-') for c in cells)} | {ms:.3f} ms | "
           f"{100 * row.seconds / whole_s:.1f} % | {row.events / per:.1f} ev")
    if row.seconds and (row.flops or row.bytes):
        out += (f" | {row.flops / row.seconds / 1e12:.1f} TFLOP/s | "
                f"{row.bytes / row.seconds / 1e9:.0f} GB/s")
        if peak:
            floor = max(row.flops / peak["bf16_flops_per_s"],
                        row.bytes / peak["hbm_bytes_per_s"])
            out += f" | floor {1e3 * floor / per:.3f} ms"
    return out


def table(rows, dispatches, peak=None, per=None, least_share=0.003):
    """The lines of the by-scope table: per program, per dispatch of it
    (``per``: {program: divisor}, default the trace's own dispatches),
    first kind x phase, then every (kind, part, phase, category) above
    ``least_share`` of the program's time."""
    lines = ["device time by scope; " + LIMIT]
    for (program,), whole in grouped(work(rows), "program").items():
        n = (per or {}).get(program) or dispatches.get(program) or 1
        mine = {k: r for k, r in rows.items() if k.program == program}
        lines.append(
            f"program {program}: {1e3 * whole.seconds / n:.3f} ms a "
            f"dispatch over {n} ({dispatches.get(program, 0)} in the "
            f"stretch), {whole.events} events")
        lines.append("  kind | phase | ms | share | events | achieved | floor")
        for cells, row in grouped(mine, "kind", "phase").items():
            lines.append(_line(cells, row, n, whole.seconds, peak))
        lines.append("  kind | part | phase | hlo_category | ms | share | "
                     "events | achieved | floor")
        rest = Row()
        for cells, row in grouped(mine, "kind", "part", "phase",
                                  "category").items():
            if row.seconds >= least_share * whole.seconds:
                lines.append(_line(cells, row, n, whole.seconds, peak))
            else:
                rest.add(row)
        if rest.events:
            lines.append(_line(("(smaller rows)",), rest, n, whole.seconds,
                               peak))
    return lines


def result_shape(instruction: str) -> str:
    """``%copy.5 = bf16[16,1026,64]{2,1,0:T(8,128)} copy(..)`` ->
    ``bf16[16,1026,64]`` (a tuple's first element): with no name from
    the program, an instruction's shape is what says whose it is."""
    m = _RESULT_SHAPE.search(instruction)
    return m.group(1) if m else ""


def unnamed_line(unplaced, most=8) -> str:
    """What the `UNNAMED` rows are made of, largest first."""
    return ("unnamed, the largest (stem | hlo_category | tf_op | result "
            "shape | ms): "
            + "; ".join(" | ".join(c or "-" for c in key)
                        + f" | {1e3 * seconds:.3f}"
                        for key, seconds in sorted(
                            unplaced.items(), key=lambda kv: -kv[1])[:most]))


# -- a traced run's view --------------------------------------------------------
def scope_view(ctx):
    """(rows, per) of the traced stretch's first chip, read and printed
    once a run; None without a trace, on a CPU rehearsal or on a tree
    without the grammar.  ``rows`` are the table's without the
    envelopes (`work`): what the readers' shares are shares of.
    ``per`` is what one step or dispatch of each program divides by: the
    driver's `traced_steps` for the train step, as `step.device_ms`
    does, else the stretch's own dispatches."""
    if getattr(ctx, "scope_view_read", False):
        return ctx.scope_view
    ctx.scope_view_read, ctx.scope_view = True, None
    try:
        from flexflow_tpu.obs.scopes import parse
    except ImportError:
        return None
    if not ctx.trace_summary or not ctx.trace_dir:
        return None
    from benchmarks.run import find_xplane

    xplane = find_xplane(ctx.trace_dir)
    if xplane is None:
        return None
    t0 = time.monotonic()
    unplaced = defaultdict(float)
    rows, dispatches = reduce(xplane, parse, unplaced)
    per = dict(dispatches)
    if ctx.counters.get("traced_steps"):
        per["step"] = ctx.counters["traced_steps"]
    for line in table(rows, dispatches, ctx.peak, per):
        ctx.out(line)
    ctx.out(unnamed_line(unplaced))
    ctx.out(f"device_scopes: read in {time.monotonic() - t0:.2f} s")
    ctx.scope_view = (work(rows), per)
    return ctx.scope_view


def share(part: Row, whole: Row):
    """100 x part / whole by seconds; None where the whole is empty."""
    return 100.0 * part.seconds / whole.seconds if whole.seconds else None


def main(argv) -> int:
    unplaced = defaultdict(float)
    rows, dispatches = reduce(argv[1], unplaced=unplaced)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        peak = next(iter(json.load(f)["devices"].values()))
    print("\n".join(table(rows, dispatches, peak)))
    print(unnamed_line(unplaced))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
