#!/usr/bin/env python3
"""One selected read alone at cell 12's shapes (PR 58): the three
formulations `MLAttention.selected_plan` chooses between, a layer.

Run by hand on the chip; no switch in the program reads anything here.

    chiprun --chips 1 -- python3 scripts/selected_read_probe.py \
        [--forms walk,fetch,fold,mask,read,view,gather] [--chunks 16,1] \
        [--pages 16,32,64] [--rows-per-fold 128,256,512]

`glm52-ep16-serve.long-context`'s geometry: 32 rows, 64 heads, a pool of
25,601 pages `[16, 640]` bf16, a table of 800 pages (12,800 positions),
2,048 picks a query, latent rank 512.  Every row's picks are a random
set of `min(t + 1, 2,048)` of its causal keys, as random index weights
pick them.  Cases, by the rows' incoming lengths: `least` (every row
parked on scratch at length 0), `2048`, `4200` (the window's mean),
`all` (the table's width) and `mix`, the lengths of the iteration in
the middle of the cell's window (`scripts/serve_window_replay.py
window_rows`: what ISSUE 51, 55 and 57's probes at ONE length missed).

Forms: `walk` is the kernel's launch (`ops/pallas/selected_attention.py`:
the mask's re-layout, the head-major queries, the kernel) once a listed
`--pages` a tile and `--rows-per-fold`; `fetch` / `fold` are two
throw-away forms of it, made HERE by patching the module for one
compile: every copy issued and waited for and nothing folded / the fold
over whatever the buffers hold and no copy issued; `mask` the product
of two one-hots that turns picks into the mask (`_picks_mask`); `read`,
`view` and `gather` the op's three whole reads from a step's queries to
the heads' contexts (`_attend_walk`, `_attend_masked_view`,
`_attend_selected`: the latent queries, the mask, the read, the value
up-projection: what the by-scope table counts under `selected_read`).
Prints ms a launch and, for `read`, the largest difference of its
contexts from the gather's.

Timing: `--iters` launches chained in one jitted `lax.fori_loop` with
real dataflow (scripts/paged_read_probe.py's discipline), the table,
the lengths and the picks hanging on the carry as the queries do (an
XLA gather whose indices do not is hoisted out of the loop: the first
table of PR 58 read the decode step's gather at 0.32 ms a layer, its
program 1.4).  Writes the
lines under chiprun_out/selected_read_probe/.
"""
import argparse
import functools
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.join(_HERE, "..")
OUT = os.path.join(_ROOT, "chiprun_out", "selected_read_probe")
sys.path.insert(0, _HERE)

CELL = dict(rows=32, heads=64, page=16, width=800, pool_width=640,
            rank=512, blocks=25601, topk=2048, chunk=16,
            traffic="long-context", pass_ms=114.6)


class _NoCopy:
    """`make_async_copy`'s stand-in for `--forms fold`."""

    def start(self):
        pass

    wait = start


def variant(kernel, name):
    """Patch `kernel` (the module) into a throw-away form of the walk;
    returns what undoes it (`scripts/paged_read_probe.py variant`)."""
    if name == "fetch":
        was = kernel._fold_selected
        kernel._fold_selected = lambda *a, **k: None
        return lambda: setattr(kernel, "_fold_selected", was)
    if name == "fold":
        was = kernel.pltpu

        class Patched:
            make_async_copy = staticmethod(lambda *a, **k: _NoCopy())

            def __getattr__(self, attr):
                return getattr(was, attr)

        kernel.pltpu = Patched()
        return lambda: setattr(kernel, "pltpu", was)
    return lambda: None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--forms",
                    default="walk,fetch,fold,mask,read,view,gather")
    ap.add_argument("--chunks", default="16,1")
    ap.add_argument("--cases", default="least,2048,4200,all,mix")
    ap.add_argument("--pages", default="",
                    help="comma list: pages a tile of the walk holds, in "
                    "place of the kernel's own choice")
    ap.add_argument("--rows-per-fold", default="",
                    help="comma list: query rows a fold scores at once")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tag", default="")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the control flow at a toy size under the "
                    "interpreter; its numbers mean nothing")
    args = ap.parse_args()
    sys.path.insert(0, _ROOT)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from serve_window_replay import window_rows

    from flexflow_tpu.ops import mla

    dev = jax.devices()[0]
    c = CELL
    if args.rehearse_cpu:
        c = dict(c, rows=3, heads=4, width=40, blocks=121, topk=24)
        args.iters = 2
    elif dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    n = c["width"] * c["page"]
    params = mla.MLAParams(
        embed_dim=6144, num_heads=c["heads"], q_lora_rank=2048,
        kv_lora_rank=c["rank"], qk_nope_head_dim=192, qk_rope_head_dim=64,
        v_head_dim=256, index_topk=c["topk"], index_n_heads=32,
        index_head_dim=128, indexer="shared")

    class Reader(mla.MLAttention):
        """The op's reads without a graph round it."""

        def __init__(self):
            self.params, self._kv_page_size = params, c["page"]

    op = Reader()
    r = np.random.default_rng(args.seed)
    pool = jnp.asarray(r.normal(size=(c["blocks"], c["page"],
                                      c["pool_width"])), jnp.bfloat16)
    pool = pool.at[..., c["rank"] + 64:].set(0)
    table = jnp.asarray(1 + r.permutation(c["rows"] * c["width"]).reshape(
        c["rows"], c["width"]) % (c["blocks"] - 1), jnp.int32)
    scale = mla.softmax_scale(params)

    def lengths(case, chunk):
        if case == "mix":
            pos, _ = window_rows(c["traffic"], CELL["rows"], c["chunk"],
                                 c["pass_ms"])
            return np.minimum(np.asarray(pos[-c["rows"]:], np.int32),
                              n - chunk)
        live = {"least": 0, "all": n - chunk}.get(case)
        return np.full(c["rows"], min(int(case), n - chunk)
                       if live is None else live, np.int32)

    def picks_for(pos, chunk):
        out = np.full((c["rows"], chunk, c["topk"]), -1, np.int32)
        for i, at in enumerate(pos):
            for j in range(chunk):
                m = min(at + j + 1, c["topk"])
                out[i, j, :m] = r.permutation(at + j + 1)[:m]
        return jnp.asarray(out)

    def timed(read, q, *rest):
        def run(q, *rest):
            def body(_, carry):
                q, acc = carry
                # every index (table, lengths, picks) hangs on the carry
                # too: a gather that does not is hoisted out of the loop
                zero = (0.0 * acc).astype(jnp.int32)
                t = jnp.sum(read(q, *(
                    x + zero if x.dtype == jnp.int32 else x
                    for x in rest)).astype(jnp.float32))
                return q + (0.0 * t).astype(q.dtype), acc + t
            return jax.lax.fori_loop(0, args.iters, body,
                                     (q, jnp.float32(0)))
        f = jax.jit(run)
        jax.block_until_ready(f(q, *rest))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(f(q, *rest))
            best = min(best, time.perf_counter() - t0)
        return best / args.iters * 1e3

    os.makedirs(OUT, exist_ok=True)
    lines = []
    pages = [int(p) for p in args.pages.split(",") if p] or [None]
    folds = [int(p) for p in args.rows_per_fold.split(",") if p] or [None]
    for chunk in (int(x) for x in args.chunks.split(",")):
        q_nope, q_rope = (jnp.asarray(r.normal(size=(
            c["rows"], chunk, c["heads"], d)), jnp.bfloat16)
            for d in (params.qk_nope_head_dim, params.qk_rope_head_dim))
        wkv_b = jnp.asarray(0.05 * r.normal(size=(
            c["rank"], c["heads"],
            params.qk_nope_head_dim + params.v_head_dim)), jnp.bfloat16)
        q_lat = op._latent_queries(q_nope, q_rope, wkv_b, c["pool_width"])
        made = {}
        for case in args.cases.split(","):
            pos = lengths(case, chunk)
            tab = jnp.where(jnp.asarray(pos > 0)[:, None], table, 0)
            picks = picks_for(pos, chunk)
            made[case] = (jnp.asarray(pos), tab, picks,
                          jax.jit(lambda p: op._picks_mask(
                              p, n, jnp.bfloat16))(picks), int(pos.sum()))
        for form in args.forms.split(","):
            from flexflow_tpu.ops.pallas import selected_attention as kernel

            walks = form in ("walk", "fetch", "fold", "read")
            for tile, fold in ((p, f) for p in (pages if walks else [None])
                               for f in (folds if walks else [None])):
                undo = variant(kernel, form)
                jax.clear_caches()  # (`_walk_launch` is jitted)
                own = kernel.pages_per_tile, kernel.ROWS_PER_FOLD
                if form == "read":  # (the op's call names neither)
                    if tile:
                        kernel.pages_per_tile = lambda page, tile=tile: tile
                    kernel.ROWS_PER_FOLD = fold or own[1]
                line = {"form": form, "chunk": chunk, "pages": tile,
                        "rows_per_fold": fold,
                        "device": {"platform": dev.platform,
                                   "kind": dev.device_kind}}
                for case, (pos, tab, picks, keep, keys) in made.items():
                    q, rest = q_nope, (q_rope, wkv_b, pool, tab, picks)
                    if form == "read":
                        def read(q_nope, q_rope, wkv_b, pool, tab, pos,  # noqa: E306
                                 picks):
                            return op._attend_walk(q_nope, q_rope, wkv_b,
                                                   pool, tab, pos, picks)
                        rest = (q_rope, wkv_b, pool, tab, pos, picks)
                    elif walks:
                        read = functools.partial(
                            kernel.selected_latent_attention, scale=scale,
                            rank=c["rank"], pages_per_step=tile,
                            rows_per_fold=fold)
                        q, rest = q_lat, (pool, tab, pos, keep)
                    elif form == "mask":
                        def read(q, picks):  # noqa: E306
                            return op._picks_mask(picks, n, q.dtype)
                        rest = (picks,)
                    else:
                        read = (op._attend_masked_view if form == "view"
                                else op._attend_selected)
                    try:
                        ms = timed(read, q, *rest)
                    except Exception as e:  # a form that does not fit
                        line[case] = {"failed": f"{type(e).__name__}: "
                                                f"{str(e)[:200]}"}
                        continue
                    line[case] = {"live_keys": keys + c["rows"] * chunk,
                                  "ms": round(ms, 4)}
                    if form == "read":
                        got = read(q, *rest).astype(jnp.float32)
                        want = op._attend_selected(
                            q_nope, q_rope, wkv_b, pool, tab,
                            picks).astype(jnp.float32)
                        line[case]["max_err"] = float(
                            jnp.max(jnp.abs(got - want)))
                        line[case]["max_abs"] = float(jnp.max(jnp.abs(want)))
                undo()
                kernel.pages_per_tile, kernel.ROWS_PER_FOLD = own
                print(json.dumps(line), flush=True)
                lines.append(line)
    with open(os.path.join(OUT, f"probe{args.tag}.json"), "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
