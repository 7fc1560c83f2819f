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
"""
import argparse
import functools
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.join(_HERE, "..")
sys.path.insert(0, _ROOT)
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
    folded = np.minimum(pos + chunk - 1, c["width"] * c["page"] - 1) \
        // c["page"] + 1
    return pos, int(folded.sum())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default=",".join(CELLS))
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pages-per-step", default="",
                    help="comma list: pages a grid program folds, in "
                    "place of the kernel's own choice (PR 43)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.pallas import paged_attention as kernel

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")

    def oracle(q, kp, vp, table, pos, scale):
        """The gather read's math in float32 (tests/test_paged_kernel.py
        `_gather_oracle`), all of a chunk's queries at once."""
        b, s, h, _ = q.shape
        n = table.shape[1] * kp.shape[1]
        k, v = (jnp.take(p, table, axis=0).reshape(b, n, h, -1)
                .astype(jnp.float32) for p in (kp, vp))
        sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k,
                        precision="highest") * scale
        keep = (jnp.arange(n)[None, None, :]
                <= pos[:, None, None] + jnp.arange(s)[None, :, None])
        sc = jnp.where(keep[:, None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1),
                          v, precision="highest")

    def timed(q, kp, vp, table, pos, scale):
        def run(q, kp, vp, table, pos):
            def body(_, carry):
                q, acc = carry
                o = paged_attention(q, kp, vp, table, pos, scale)
                t = jnp.sum(o.astype(jnp.float32))
                return q + (0.0 * t).astype(q.dtype), acc + t
            return jax.lax.fori_loop(0, args.iters, body,
                                     (q, jnp.float32(0)))
        f = jax.jit(run)
        jax.block_until_ready(f(q, kp, vp, table, pos))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(f(q, kp, vp, table, pos))
            best = min(best, time.perf_counter() - t0)
        return best / args.iters * 1e3

    os.makedirs(OUT, exist_ok=True)
    lines = []
    sweep = [int(p) for p in args.pages_per_step.split(",") if p] or [None]
    for name, pages_per_step in ((n, p) for n in args.cell.split(",")
                                 for p in sweep):
        paged_attention = kernel.paged_attention if pages_per_step is None \
            else functools.partial(kernel.paged_attention,
                                   pages_per_step=pages_per_step)
        c = CELLS[name]
        r = np.random.default_rng(args.seed)
        shape = (c["blocks"], c["page"], c["heads"], c["d"])
        kp, vp = (jnp.asarray(r.normal(size=shape), jnp.bfloat16)
                  for _ in "kv")
        # scattered blocks, none of them scratch block 0 (cell 3's pool
        # holds half its tables' footprint: rows then share blocks)
        table = jnp.asarray(1 + r.permutation(
            c["rows"] * c["width"]).reshape(c["rows"], c["width"])
            % (c["blocks"] - 1), jnp.int32)
        scale = c["d"] ** -0.5
        page_bytes = 2 * c["page"] * c["heads"] * c["d"] * 2
        for chunk in sorted({1, c["chunk"]}):
            q = jnp.asarray(r.normal(size=(c["rows"], chunk, c["heads"],
                                           c["d"])), jnp.bfloat16)
            mean = c["decode"] if chunk == 1 else c["prefill"]
            line = {"cell": name, "chunk": chunk,
                    "pages_per_step": pages_per_step,
                    "device": {"platform": dev.platform,
                               "kind": dev.device_kind}}
            for case, (rows, pages) in (
                    ("least", (0, 0)), ("mean", mean),
                    ("all", (c["rows"], c["rows"] * c["width"]))):
                pos, folded = positions(c, rows, pages, chunk)
                tab = jnp.where(jnp.asarray(pos > 0)[:, None], table, 0)
                pos = jnp.asarray(pos)
                got = paged_attention(q, kp, vp, tab, pos, scale)
                want = oracle(q, kp, vp, tab, pos, scale)
                ms = timed(q, kp, vp, tab, pos, scale)
                line[case] = {
                    "pages": folded, "ms": round(ms, 5),
                    "GB_per_s": round(folded * page_bytes / ms / 1e6, 1),
                    "bytes_ms": round(
                        folded * page_bytes / HBM_BYTES_PER_S * 1e3, 5),
                    "max_err": float(jnp.max(jnp.abs(
                        got.astype(jnp.float32) - want)))}
            lo, hi = line["least"], line["all"]
            per_page = (hi["ms"] - lo["ms"]) / (hi["pages"] - lo["pages"])
            line["us_a_page"] = round(1e3 * per_page, 4)
            line["fixed_ms"] = round(lo["ms"] - lo["pages"] * per_page, 5)
            print(json.dumps(line), flush=True)
            lines.append(line)
    with open(os.path.join(OUT, "probe.json"), "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
