#!/usr/bin/env python
"""One `paged_attention` launch alone at a serving cell's shapes (PR 42).

Run by hand on the chip; no switch in the program reads anything here.

    chiprun --chips 1 -- python3 scripts/paged_read_probe.py \
        [--cell ouro-2.6b-serve.loop-decode,gpt2-medium-serve.above-knee] \
        [--pages-per-step 8,10,20]

For each named cell: the kernel at the cell's rows, table width, page,
heads and head dim over a bf16 pool of the cell's size, with the pages
a launch folds at their least (every row parked on scratch: one page a
row), at the cell's mean in its traced window, and with every page of
the table live; once with one query a row (the decode step, and each
pass of cell 3's scanned prefill) and once with the cell's chunk (the
one-pass prefill of cell 7: the feeding rows at their mean, the rest
parked).  Prints ms a launch, and from the least and the full case the
launch's fixed part and its cost a page, and GB/s over the folded
pages' keys and values; the largest difference from the gather read's
math in float32 beside them.  `--pages-per-step` (PR 43) runs each
case once per listed number of pages a grid program folds, in place of
the kernel's own choice (`paged_attention.pages_per_program`): what a
launch ALONE gains from fewer grid steps, which its program may not
keep (a decode step lost what the probe promised, PERF.md PR 43).

Timing: `--iters` launches chained in one jitted `lax.fori_loop` with
real dataflow (the context feeds the next launch's queries through
`0 * sum`), scripts/flash_ceiling_probe.py's discipline.  Writes the
lines under chiprun_out/paged_read_probe/.

A cell with a head-major pool (PR 56: `laguna-xs2-ep8-serve.mixed-context`,
`group` query heads to a key/value head) also reads a `mix` case, the
rows' lengths log-normal as its traffic's and summing to the traced
mean, and takes two throw-away forms of the head-major walk, made HERE
by patching the module for one compile and never in the program:
`--variants fetch` is the walk with the fold skipped (every copy
issued and waited for, nothing multiplied), `--variants fold` the fold
over whatever the tiles' buffers hold with no copy issued: which of the
two sets the kernel's pace.  `--tree DIR` times the package of another
checkout (the parent commit's kernel, unpacked under `.chipcheck/`),
`--group` one of the cell's head ratios.
"""
import argparse
import functools
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.join(_HERE, "..")
OUT = os.path.join(_ROOT, "chiprun_out", "paged_read_probe")

HBM_BYTES_PER_S = 819e9

#: a cell's launch: rows (slots), table width, page, heads, head dim,
#: blocks of one layer's pool (cell 7: 4 planes of 16 x 20 + 1), the
#: chunk of its prefill program's launch (cell 3 scans the seq-1 step),
#: and of its traced window (ledger, PR 41) the rows and pages live in
#: a decode launch and the rows feeding and their pages in a prefill one
CELLS = {
    "ouro-2.6b-serve.loop-decode": dict(
        rows=16, width=20, page=16, heads=16, d=128, blocks=4 * 321,
        chunk=8, decode=(14, 165), prefill=(2, 8)),
    "gpt2-medium-serve.above-knee": dict(
        rows=16, width=64, page=16, heads=16, d=64, blocks=513,
        chunk=1, decode=(2, 22)),
    # a head-major pool of 8 key/value heads under 48 query heads (its
    # four full layers; 64 is the window layers' ratio, read for the
    # shape's sake); every token of its window comes out of the [32, 16]
    # pass: 8,855 live blocks a launch (ledger, PR 55)
    "laguna-xs2-ep8-serve.mixed-context": dict(
        rows=32, width=1024, page=16, heads=8, d=128, blocks=16385,
        chunk=16, decode=(32, 8855), prefill=(32, 8855),
        head_major=True, group=(6, 8), mix=dict(sigma=1.1), iters=50),
}


def positions(c, live_rows, live_pages, chunk):
    """Row positions (the last `live_rows` rows share `live_pages`
    pages as evenly as they divide, each ending on a partial page; the
    rest park on scratch at position 0) and the pages the launch folds."""
    import numpy as np

    pos = np.zeros(c["rows"], np.int32)
    for j in range(live_rows):
        pages = live_pages // live_rows + (j < live_pages % live_rows)
        pos[c["rows"] - 1 - j] = max(pages * c["page"] - 5 - (chunk - 1), 0)
    return pos, folded_pages(c, pos, chunk)


def folded_pages(c, pos, chunk):
    import numpy as np

    return int((np.minimum(pos + chunk - 1, c["width"] * c["page"] - 1)
                // c["page"] + 1).sum())


def mixed_positions(c, live_pages, chunk, rng):
    """Every row live, the lengths log-normal with the traffic's sigma
    and scaled to `live_pages` in all (a row's progress through its
    request spreads them further; the sum is the traced one)."""
    import numpy as np

    top = c["width"] * c["page"] - chunk
    w = rng.lognormal(0.0, c["mix"]["sigma"], c["rows"])
    pos = w / w.sum() * live_pages * c["page"]
    for _ in range(8):  # (a clipped row hands its excess to the others)
        pos = np.minimum(pos, top)
        pos[pos < top] *= (live_pages * c["page"] - pos[pos >= top].sum()) \
            / pos[pos < top].sum()
    pos = np.clip(pos, 1, top).astype(np.int32)
    return pos, folded_pages(c, pos, chunk)


class _NoCopy:
    """`make_async_copy`'s stand-in for `--variants fold`."""

    def start(self):
        pass

    wait = start


def variant(kernel, name):
    """Patch `kernel` (the module) into a throw-away form of the
    head-major walk; returns what undoes it."""
    if name == "fetch":
        was = kernel._fold_head_major
        kernel._fold_head_major = lambda *a, **k: None
        return lambda: setattr(kernel, "_fold_head_major", was)
    if name == "fold":
        was = kernel.pltpu

        class Patched:
            make_async_copy = staticmethod(lambda *a, **k: _NoCopy())

            def __getattr__(self, attr):
                return getattr(was, attr)

        kernel.pltpu = Patched()
        return lambda: setattr(kernel, "pltpu", was)
    assert name == "kernel", name
    return lambda: None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default=",".join(CELLS))
    ap.add_argument("--iters", type=int, default=200,
                    help="launches a timed call (a cell may name fewer)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pages-per-step", default="",
                    help="comma list: pages a grid program folds (a tile "
                    "of the head-major walk holds), in place of the "
                    "kernel's own choice (PR 43)")
    ap.add_argument("--variants", default="kernel",
                    help="comma list of kernel, fetch, fold (PR 56)")
    ap.add_argument("--group", type=int, default=0,
                    help="query heads a key/value head, where the cell "
                    "has several (default: its first)")
    ap.add_argument("--chunks", default="",
                    help="comma list in place of 1 and the cell's chunk")
    ap.add_argument("--tree", default=_ROOT,
                    help="the checkout whose package is timed")
    ap.add_argument("--tag", default="", help="names the lines' file")
    args = ap.parse_args()
    sys.path.insert(0, args.tree)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.pallas import paged_attention as kernel

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")

    @functools.partial(jax.jit, static_argnames=("scale", "head_major"))
    def oracle(q, kp, vp, table, pos, scale, head_major):
        """The gather read's math in float32 (tests/test_paged_kernel.py
        `_gather_oracle`), all of a chunk's queries at once; grouped
        query heads read their key/value head."""
        b, s, hq, _ = q.shape
        if head_major:
            kp, vp = (p.transpose(0, 2, 1, 3) for p in (kp, vp))
        h = kp.shape[2]
        n = table.shape[1] * kp.shape[1]
        k, v = (jnp.take(p, table, axis=0).reshape(b, n, h, -1)
                .astype(jnp.float32) for p in (kp, vp))
        sc = jnp.einsum("bqhgd,bkhd->bhgqk",
                        q.astype(jnp.float32).reshape(b, s, h, hq // h, -1),
                        k, precision="highest") * scale
        keep = (jnp.arange(n)[None, None, :]
                <= pos[:, None, None] + jnp.arange(s)[None, :, None])
        sc = jnp.where(keep[:, None, None], sc, -jnp.inf)
        return jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(sc, axis=-1),
                          v, precision="highest").reshape(b, s, hq, -1)

    def max_err(got, q, kp, vp, table, pos, scale, head_major):
        """Four rows at a time: a wide table's dense view is large."""
        return max(float(jnp.max(jnp.abs(
            got[i:i + 4].astype(jnp.float32) - oracle(
                q[i:i + 4], kp, vp, table[i:i + 4], pos[i:i + 4], scale,
                head_major)))) for i in range(0, q.shape[0], 4))

    def timed(paged_attention, iters, q, kp, vp, table, pos, scale):
        def run(q, kp, vp, table, pos):
            def body(_, carry):
                q, acc = carry
                o = paged_attention(q, kp, vp, table, pos, scale)
                t = jnp.sum(o.astype(jnp.float32))
                return q + (0.0 * t).astype(q.dtype), acc + t
            return jax.lax.fori_loop(0, iters, body, (q, jnp.float32(0)))
        f = jax.jit(run)
        jax.block_until_ready(f(q, kp, vp, table, pos))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(f(q, kp, vp, table, pos))
            best = min(best, time.perf_counter() - t0)
        return best / iters * 1e3

    os.makedirs(OUT, exist_ok=True)
    lines = []
    sweep = [int(p) for p in args.pages_per_step.split(",") if p] or [None]
    for name, pages_per_step, form in (
            (n, p, f) for n in args.cell.split(",") for p in sweep
            for f in args.variants.split(",")):
        c = CELLS[name]
        major = bool(c.get("head_major"))
        group = args.group or c.get("group", (1,))[0]
        iters = min(args.iters, c.get("iters", args.iters))
        kw = {"head_major": True} if major else {}
        if pages_per_step is not None:
            kw["pages_per_step"] = pages_per_step
        paged_attention = functools.partial(kernel.paged_attention, **kw)
        r = np.random.default_rng(args.seed)
        shape = (c["blocks"], c["page"], c["heads"], c["d"])
        if major:
            shape = (c["blocks"], c["heads"], c["page"], c["d"])
        kp, vp = (jnp.asarray(r.normal(size=shape), jnp.bfloat16)
                  for _ in "kv")
        # scattered blocks, none of them scratch block 0 (cell 3's pool
        # holds half its tables' footprint: rows then share blocks)
        table = jnp.asarray(1 + r.permutation(
            c["rows"] * c["width"]).reshape(c["rows"], c["width"])
            % (c["blocks"] - 1), jnp.int32)
        scale = c["d"] ** -0.5
        page_bytes = 2 * c["page"] * c["heads"] * c["d"] * 2
        chunks = [int(x) for x in args.chunks.split(",") if x] \
            or sorted({1, c["chunk"]})
        undo = variant(kernel, form)
        jax.clear_caches()  # (`_paged_launch` is jitted: its own cache)
        for chunk in chunks:
            q = jnp.asarray(r.normal(size=(
                c["rows"], chunk, c["heads"] * group, c["d"])), jnp.bfloat16)
            mean = c["decode"] if chunk == 1 else c["prefill"]
            line = {"cell": name, "chunk": chunk, "group": group,
                    "form": form, "pages_per_step": pages_per_step,
                    "tree": os.path.relpath(args.tree, _ROOT),
                    "device": {"platform": dev.platform,
                               "kind": dev.device_kind}}
            cases = [("least", positions(c, 0, 0, chunk)),
                     ("mean", positions(c, *mean, chunk)),
                     ("all", positions(c, c["rows"],
                                       c["rows"] * c["width"], chunk))]
            if "mix" in c:
                cases.insert(2, ("mix", mixed_positions(
                    c, mean[1], chunk, np.random.default_rng(args.seed))))
            for case, (pos, folded) in cases:
                tab = jnp.where(jnp.asarray(pos > 0)[:, None], table, 0)
                pos = jnp.asarray(pos)
                got = paged_attention(q, kp, vp, tab, pos, scale)
                ms = timed(paged_attention, iters, q, kp, vp, tab, pos, scale)
                line[case] = {
                    "pages": folded, "ms": round(ms, 5),
                    "GB_per_s": round(folded * page_bytes / ms / 1e6, 1),
                    "bytes_ms": round(
                        folded * page_bytes / HBM_BYTES_PER_S * 1e3, 5)}
                if form == "kernel":  # (a throw-away form reads nothing true)
                    line[case]["max_err"] = max_err(
                        got, q, kp, vp, tab, pos, scale, major)
            lo, hi = line["least"], line["all"]
            per_page = (hi["ms"] - lo["ms"]) / (hi["pages"] - lo["pages"])
            line["us_a_page"] = round(1e3 * per_page, 4)
            line["fixed_ms"] = round(lo["ms"] - lo["pages"] * per_page, 5)
            print(json.dumps(line), flush=True)
            lines.append(line)
        undo()
    with open(os.path.join(OUT, f"probe{args.tag}.json"), "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
