"""From a profiler trace (``*.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` reads the file with nothing but jax: planes
(one per device, ``/device:TPU:<n>``, and the host's), their lines, and
events with a start and a duration in nanoseconds.  On a TPU plane the
line ``XLA Ops`` holds every operation that ran on the device and
``XLA Modules`` one event per dispatched program; both are on the
device's own clock.

`reduce` gives, averaged over the chips used:

* ``busy_s``: the union of the intervals in which an operation ran;
* ``window_s``: first operation's start to last operation's end (the
  stretch the profiler saw; idle share = 1 - busy_s / window_s);
* ``top_ops``: operations by total time, ``[[name, seconds], ...]``,
  the per-layer copies of one fusion (``name.<n>``) summed under ``name``,
  control-flow envelopes (``while``) left out since their bodies are listed;
* ``idle_gaps``: the longest gaps between operations, named by the
  program that ran before and after (``"after <module> / before <module>"``);
* ``modules``: per program name, its dispatches ``[(start_s, end_s,
  busy_s), ...]`` on the first chip: the per-dispatch reduction the
  step and scheduler readers use;
* ``collective_s`` and ``collective_exposed_s``: time in collective
  operations, and the part of it during which no other operation ran on
  that chip.

Kept as code with the benchmark so that every PR computes the same
number the same way; `tests/test_reduce_trace.py` checks it on a small
recorded trace.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# control-flow operations span the operations of their bodies, which the
# trace lists too: they count toward the busy union, not as an operation
ENVELOPES = {"while", "conditional", "call"}
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute",
    re.I)


def union_seconds(intervals) -> float:
    """Total length of the union of [(start, end), ...]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def subtract_seconds(intervals, cover) -> float:
    """Length of the part of ``intervals`` (merged) not covered by
    ``cover`` (merged)."""
    left = 0.0
    j = 0
    for s, e in intervals:
        pos = s
        while j < len(cover) and cover[j][1] <= pos:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            if cover[k][0] > pos:
                left += cover[k][0] - pos
            pos = max(pos, cover[k][1])
            k += 1
        if pos < e:
            left += e - pos
    return left


def short_name(name: str) -> str:
    """``%fusion.123 = ...`` / ``fusion.123`` -> ``fusion.123``."""
    name = name.split(" = ")[0].strip()
    return name.lstrip("%")


def stem(name: str) -> str:
    """``%multiply_convert_fusion.23 = ...`` -> ``multiply_convert_fusion``:
    the compiler numbers the copies of one fusion per layer, and a
    breakdown by copy says nothing."""
    return re.sub(r"\.\d+$", "", short_name(name))


def module_name(name: str) -> str:
    """``jit_step(5197994995008307429)`` -> ``jit_step``: the number is
    the program's fingerprint and changes with every edit."""
    return re.sub(r"\(\d+\)$", "", name.strip())


def read_planes(path: str):
    """[{chip, ops: [(name, start_s, end_s)], modules: [...]}] per TPU."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {}
        for line in plane.lines:
            if line.name in (OPS_LINE, MODULES_LINE):
                lines[line.name] = [
                    (ev.name, ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in line.events]
        planes.append({"chip": int(m.group(1)),
                       "ops": lines.get(OPS_LINE, []),
                       "modules": lines.get(MODULES_LINE, [])})
    return sorted(planes, key=lambda p: p["chip"])


def structure(path: str) -> dict:
    """Plane and line names with event counts: for looking at a trace
    by hand before trusting the reduction."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        out[plane.name] = {}
        for line in plane.lines:
            events = list(line.events)
            names = defaultdict(int)
            for ev in events:
                names[ev.name] += 1
            out[plane.name][line.name] = {
                "events": len(events),
                "names": sorted(names.items(), key=lambda kv: -kv[1])[:12],
            }
    return out


def reduce_planes(planes, chips: int) -> dict:
    planes = [p for p in planes if p["ops"]][:chips]
    if not planes:
        raise ValueError("the trace holds no operation on any TPU plane")
    busy = window = coll = exposed = 0.0
    op_totals = defaultdict(float)
    for p in planes:
        spans = [(s, e) for _, s, e in p["ops"]]
        busy += union_seconds(spans)
        window += max(e for _, e in spans) - min(s for s, _ in spans)
        for name, s, e in p["ops"]:
            if stem(name) not in ENVELOPES:
                op_totals[stem(name)] += (e - s) / len(planes)
        c = merged([(s, e) for n, s, e in p["ops"] if COLLECTIVE.search(n)])
        other = merged([(s, e) for n, s, e in p["ops"]
                        if not COLLECTIVE.search(n)])
        coll += sum(e - s for s, e in c)
        exposed += subtract_seconds(c, other)
    n = len(planes)

    first = planes[0]
    mods = sorted(((module_name(nm), s, e) for nm, s, e in first["modules"]),
                  key=lambda m: m[1])
    ops = merged([(s, e) for _, s, e in first["ops"]])
    modules = defaultdict(list)
    j = 0
    for name, s, e in mods:  # dispatches run one after another
        while j < len(ops) and ops[j][1] <= s:
            j += 1
        k, inside = j, 0.0
        while k < len(ops) and ops[k][0] < e:
            inside += min(ops[k][1], e) - max(ops[k][0], s)
            k += 1
        modules[name].append((s, e, inside))
    starts = [s for _, s, _ in mods]

    def module_at(t):
        i = bisect.bisect_right(starts, t) - 1
        return mods[i][0] if i >= 0 and t <= mods[i][2] else "no program"

    gaps = defaultdict(float)
    for (_, e0), (s1, _) in zip(ops, ops[1:]):
        gaps[f"after {module_at(e0)} / before {module_at(s1)}"] += s1 - e0
    return {
        "busy_s": busy / n, "window_s": window / n, "chips": n,
        "top_ops": [[k, v] for k, v in sorted(op_totals.items(),
                                              key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[k, v] for k, v in sorted(gaps.items(),
                                                key=lambda kv: -kv[1])[:10]],
        "modules": dict(modules),
        "collective_s": coll / n, "collective_exposed_s": exposed / n,
    }


def mean_dispatch_busy_ms(summary, program: str):
    """Mean device busy time inside one dispatch of ``program``, ms;
    None where the trace holds none."""
    runs = summary["modules"].get(program) if summary else None
    if not runs:
        return None
    return 1e3 * sum(busy for _, _, busy in runs) / len(runs)


def reduce(path: str, chips: int = 1) -> dict:
    return reduce_planes(read_planes(path), chips)


if __name__ == "__main__":
    import json
    import sys

    print(json.dumps(structure(sys.argv[1]), indent=1))
