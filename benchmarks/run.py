#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, in this process, on this machine.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, family,
driver or per-layer metric is a file found by name (benchmarks/README.md):

    BENCHMARK.json workloads[name]  -> config, traffic, chips
    benchmarks/configs/<config>.json   "family" -> benchmarks/families/<family>.py
    benchmarks/traffic/<traffic>.json  "driver" -> benchmarks/drivers/<driver>.py
    per_layer[name]                    -> benchmarks/readers/<name>.py, or
                                          the driver's counter of that name

The last line of stdout is the result object.  With ``--trace 0`` its
metrics are the cell's end-to-end metrics; with ``--trace 1`` a short
stretch of the window runs under ``jax.profiler`` and its metrics are the
cell's per-layer metrics.  A CPU backend is refused (exit 2, no result
line) unless ``--rehearse-cpu`` is given; a rehearsal walks the same code
at whatever size its files say and prints no device metric.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

DEVICE_SOURCES = ("device_trace",)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmarks/<kind>/<name>.py; a dotted metric name falls back to
    its stem (``decode.device_ms.latency`` -> ``decode.device_ms``)."""
    parts = name.split(".")
    for n in range(len(parts), 0, -1):
        path = os.path.join(HERE, kind, ".".join(parts[:n]) + ".py")
        if os.path.isfile(path):
            spec = importlib.util.spec_from_file_location(
                f"benchmarks.{kind}.{'_'.join(parts[:n]).replace('-', '_')}",
                path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no {kind} file for {name!r} under "
                            f"{os.path.join(HERE, kind)}")


def read_metric(ctx, metric):
    """A per-layer metric's value: from its reader file, or, where it
    has none, the driver's counter of the same name (or of its stem:
    ``ttft_p95_ms.overload`` -> ``ttft_p95_ms``).  None leaves the
    metric out of the line."""
    try:
        return load_module("readers", metric["name"]).read(ctx, metric)
    except FileNotFoundError:
        parts = metric["name"].split(".")
        for n in range(len(parts), 0, -1):
            if ".".join(parts[:n]) in ctx.counters:
                return ctx.counters[".".join(parts[:n])]
        return None


class CompileWatch:
    """What jax itself reports (jax.monitoring): programs lowered,
    persistent-cache hits and misses, seconds in trace/lower/compile.
    (Copied from chip_smoke.py, PR 21.)"""

    _LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _TIMED = ("/jax/core/compile/jaxpr_trace_duration", _LOWER,
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon

        self.lowerings = self.hits = self.misses = 0
        self.seconds = 0.0
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_):
        self.hits += event == "/jax/compilation_cache/cache_hits"
        self.misses += event == "/jax/compilation_cache/cache_misses"

    def _on_duration(self, event: str, secs: float, **_):
        if event in self._TIMED:
            self.seconds += secs
        self.lowerings += event == self._LOWER

    def snapshot(self) -> dict:
        return {"lowered": self.lowerings, "cache_hits": self.hits,
                "cache_misses": self.misses, "compile_s": self.seconds}


class Context:
    """What a driver and a reader get to see."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.spans = {}      # name -> seconds, the benchmark's own clock
        self.counters = {}   # name -> number, read from the program
        self.trace_dir = None
        self.trace_window_s = None
        self.trace_summary = None

    def out(self, msg: str = "") -> None:
        print(msg, flush=True)

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.spans[name] = (self.spans.get(name, 0.0)
                                + time.monotonic() - t0)

    # the profiler window a driver opens over a stretch of steady work
    def trace_start(self) -> None:
        import jax

        self.trace_dir = os.path.join(HERE, "out", "trace",
                                      self.cell["name"])
        if os.path.isdir(self.trace_dir):
            import shutil

            shutil.rmtree(self.trace_dir)
        os.makedirs(self.trace_dir)
        jax.profiler.start_trace(self.trace_dir)
        self._trace_t0 = time.monotonic()

    def trace_stop(self) -> None:
        import jax

        self.trace_window_s = time.monotonic() - self._trace_t0
        jax.profiler.stop_trace()


def find_xplane(trace_dir: str):
    found = [os.path.join(r, f) for r, _, fs in os.walk(trace_dir)
             for f in fs if f.endswith(".xplane.pb")]
    return max(found, key=os.path.getmtime) if found else None


def refuse(msg: str):
    print("benchmarks/run.py: " + msg, file=sys.stderr)
    raise SystemExit(2)


def make_context(argv=None):
    """Parse the command, find the cell's files, check the machine:
    (Context, driver module).  Exits with 2 where the run is refused."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="walk the control flow on a CPU backend; prints "
                         "no device metric and proves nothing about speed")
    ap.add_argument("--controls", action="store_true",
                    help="also read the check's statistics for the control "
                         "one precision down, on earlier lines (the "
                         "builder's sweeps; decides nothing)")
    ap.add_argument("--benchmark", default=os.path.join(ROOT,
                                                        "BENCHMARK.json"),
                    help="another BENCHMARK.json (tests and rehearsals)")
    args = ap.parse_args(argv)

    bench = load_json(args.benchmark)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        refuse(f"no workload {args.workload!r}; have {sorted(cells)}")
    cell = cells[args.workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform == "cpu" and not args.rehearse_cpu:
        refuse("jax found no accelerator (platform cpu); a benchmark number "
               "comes only from the chip.  --rehearse-cpu walks the control "
               "flow.")
    if len(devices) < cell["chips"]:
        refuse(f"{args.workload} needs {cell['chips']} chip(s), jax sees "
               f"{len(devices)}")
    devices = devices[:cell["chips"]]
    rehearsal = platform == "cpu"
    if not rehearsal:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            # the same fixed place the program picks for itself
            # (flexflow_tpu.store): <checkout>/.jax_cache
            cache = os.path.join(ROOT, ".jax_cache")
            os.makedirs(cache, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    peaks = load_json(os.path.join(HERE, "peaks.json"))
    kind = devices[0].device_kind
    if not rehearsal and kind not in peaks["devices"]:
        refuse(f"device kind {kind!r} is not in benchmarks/peaks.json; add "
               "it with its source")

    ctx = Context(
        args=args, bench=bench, cell=cell, cfg=cfg,
        traffic=traffic, devices=devices, rehearsal=rehearsal,
        peak=peaks["devices"].get(kind), watch=CompileWatch(),
        t_process_start=T_PROCESS_START, trace=bool(args.trace),
        seed=args.seed, seconds=args.seconds,
        family=load_module("families", cfg["family"]),
    )
    ctx.out(f"cell={cell['name']} seed={args.seed} seconds={args.seconds} "
            f"trace={args.trace} platform={platform} device_kind={kind!r} "
            f"chips={len(devices)}" + ("  [REHEARSAL cpu]" if rehearsal else ""))

    return ctx, load_module("drivers", traffic["driver"])


def main(argv=None) -> int:
    ctx, driver = make_context(argv)
    args, bench, cfg, devices = ctx.args, ctx.bench, ctx.cfg, ctx.devices
    rehearsal = ctx.rehearsal
    platform, kind = devices[0].platform, devices[0].device_kind
    result = driver.run(ctx)

    from benchmarks import check

    correct = check.verdict(result["stats"], cfg["tolerance"], ctx.out)
    ctx.out("spans " + json.dumps({k: round(v, 3)
                                   for k, v in ctx.spans.items()}))
    ctx.out("compile_watch " + json.dumps(ctx.watch.snapshot()))
    ctx.out(f"compiles_inside_window={result['compiles_in_window']}")

    device = {"platform": platform, "kind": kind, "count": len(devices)}
    stats = [d.memory_stats() for d in devices]
    if all(s and "peak_bytes_in_use" in s for s in stats):
        device["memory_peak_bytes"] = max(int(s["peak_bytes_in_use"])
                                          for s in stats)
    elif not rehearsal:
        raise RuntimeError("the device reports no peak_bytes_in_use")

    line = {"correct": bool(correct), "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": {}, "device": device}
    if not args.trace:
        for m in bench["end_to_end"]:
            if args.workload in m.get("workloads", [args.workload]):
                value = result["end_to_end"].get(m["name"])
                if value is not None and not (rehearsal and
                                              m["source"] in DEVICE_SOURCES):
                    line["metrics"][m["name"]] = {"value": value,
                                                  "unit": m["unit"]}
    else:
        xplane = find_xplane(ctx.trace_dir) if ctx.trace_dir else None
        if xplane is not None and not rehearsal:
            from benchmarks import reduce_trace

            ctx.trace_summary = reduce_trace.reduce(xplane, len(devices))
            device["busy_s"] = ctx.trace_summary["busy_s"]
            # the window the profiler was open (host clock), or first to
            # last device operation where that is longer
            device["window_s"] = max(ctx.trace_summary["window_s"],
                                     ctx.trace_window_s)
            line["breakdown"] = {
                "device_ops": ctx.trace_summary["top_ops"][:10],
                "idle_gaps": ctx.trace_summary["idle_gaps"][:10],
            }
        reported = {m["name"] for m in bench["end_to_end"]
                    if args.workload in m.get("workloads", [args.workload])}
        for m in bench["per_layer"]:
            if args.workload not in m.get("workloads", [args.workload]):
                continue
            if m["moves"] not in reported:
                continue
            if rehearsal and m["source"] in DEVICE_SOURCES:
                continue
            value = read_metric(ctx, m)
            if value is not None:
                line["metrics"][m["name"]] = {"value": float(value),
                                              "unit": m["unit"]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
