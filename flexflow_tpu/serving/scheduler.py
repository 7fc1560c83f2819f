"""Continuous (iteration-level) batching: the Orca OSDI'22 scheduling
discipline on top of the paged KV-cache pool (serving/kv_pool.py).

`GenerationBatcher` (the static path) coalesces requests into ONE scan
program: every row rides to the batch's max length, a 5-token reply
pays for a 200-token neighbor, and a request arriving one step after
dispatch waits out the whole scan.  The continuous scheduler instead
keeps a persistent decode loop stepping every in-flight sequence by
one token per iteration; at EVERY step boundary it retires finished
sequences (eos / max_new_tokens) and admits queued prompts into the
freed slots — prefill is interleaved with decode (an admitted prompt
feeds one token per step at its own position), so the device never
waits for stragglers and short replies exit the moment they finish.

Allocation rides the paged pool: sequences reserve worst-case blocks
at admission (a full pool QUEUES the request — never a crash), extend
block-by-block as they grow, and free on retirement, so resident KV
HBM is sum-of-live-lengths instead of slots x max_seq.

The pool is also a PREFIX CACHE (kv_pool.py): admissions map the
longest indexed block-aligned token prefix of their prompt straight
onto shared physical blocks (skipping prefill for those tokens, with
copy-on-write isolating a full-prompt hit's tail block), and a second
compiled [slots, C] program chunk-prefills the uncached remainder C
tokens per dispatch — docs/SERVING.md "Prefix cache & chunked
prefill".  Greedy output is token-identical with sharing and chunking
on or off: shared bytes were written by the same programs at the same
positions, and the chunk program scans the seq-1 graph so every op
keeps the decode step's shapes (a family whose recipe carries
`prefill_pass` runs the chunk as one forward instead, equal by
tolerance: PagedKVDecodeModel).

The step plan, an iteration (`plan_chunk_rows`, `_iteration`): while
any live row is still feeding its prompt, the scan's family (GPT) pays
TWO dispatches, the chunk over the feeding rows (the others ride it on
scratch) and then the decode step of every row; a family on the
one-pass program pays ONE, because that pass returns each row's logits
at its last real token: every live row is a row of it, a row past its
prompt with its pending token and one token fed, and is sampled from
it.  When no row is feeding, the plain decode step runs.  Nothing
switches this: the recipe's `prefill_pass` and whether a row feeds.
Such a family's programs also keep each row's greedy id on the device,
so the loop leaves a dispatch in flight and enqueues the next one
before it waits for it: the host's turn (sampling, retiring, admitting,
preparing) runs beside a pass, not between two.  A dispatch's
bookkeeping is cut at what it needs to know: `_advance_rows` from the
plan alone, `_settle_rows` once the logits are on the host; what keeps
an iteration synchronous is `_synchronous`'s to say.

Shape discipline (the TPU-native part): one compiled [slots, 1] step
program (and one [slots, C] chunk program) serves the engine's whole
lifetime — admissions, retirements and per-row positions are DATA
(block tables + seq_lens), never shapes, so steady state has zero
recompiles.  Sampling is host-side
per row (a greedy row's id is also chosen on the device, where the
family's programs keep it: the same id), which also lifts the static
batcher's same-temperature coalescing restriction: a continuous batch
freely mixes temperatures.

SLO telemetry (obs.metrics): TTFT and per-token latency histograms,
queue depth, KV-pool occupancy/fragmentation — drained to
run_telemetry.jsonl and surfaced in /v2/stats (docs/SERVING.md).
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import operator
import queue
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..logger import serving_logger
from ..obs.trace import span
from ..ops.op import DispatchGroup
from ..ops.routed_experts import MOE_STATS, MOE_ZERO_STATS
from .kv_pool import KVPool

#: how the scheduler's running totals take a dispatch's routed-expert
#: count that is no sum (every other one is added)
_MOE_FOLD = {"real_min": min, "real_max": max}


def pick_paged_read(asked: str = "auto", *, backend: str,
                    have_kernel: bool, carries, family: str) -> str:
    """THE choice of paged-attention READ formulation (docs/SERVING.md
    "Fused paged attention"), made once, at engine build: "gather"
    (the dense [slots, decode_max_seq] view: the CPU path and the
    tests' oracle) or "pallas" (each row's live blocks read in place,
    ops/pallas/paged_attention.py).

    "auto" follows what decides which of the two is the fast one: on a
    TPU, for a family whose attention op has the kernel (`pallas_read`
    in its recipe's `carries`), Mosaic compiles the in-place read;
    anywhere else the kernel exists only under the Pallas interpreter,
    so the gather runs.  A formulation asked for by name is honoured
    or refused, never replaced: ConfigError for an unknown name, for
    "pallas" on a jax without Pallas and for "pallas" on a family that
    does not carry it, here and not from inside a trace.  Logs which
    formulation the engine runs."""
    from ..config import ConfigError

    if asked not in ("auto", "gather", "pallas"):
        raise ConfigError(
            "paged_kernel must be one of ('auto', 'gather', 'pallas'), "
            f"got {asked!r}")
    if asked == "pallas" and not have_kernel:
        raise ConfigError(
            "paged_kernel='pallas' needs jax.experimental.pallas, which "
            "this jax build does not provide: use 'gather' (the "
            "reference formulation) or a jax with Pallas support")
    if asked == "pallas" and "pallas_read" not in carries:
        raise ConfigError(
            f"{family} does not carry pallas_read (paged_kernel="
            f"'pallas'); it carries {sorted(carries)}")
    kernel = asked
    if asked == "auto":
        kernel = ("pallas" if backend == "tpu" and have_kernel
                  and "pallas_read" in carries else "gather")
    serving_logger.info(
        "paged attention formulation: %s%s (%s)", kernel,
        "" if kernel == asked else f" (from {asked!r})",
        "fused Pallas kernel, block reads in place"
        if kernel == "pallas"
        else "dense block-gather, the bit-identity oracle")
    return kernel


class _Launched:
    """What an enqueued sampling program left on the device, until
    `PagedKVDecodeModel.land` fetches it: the logits, the rows' exit
    pdf (`()` without an exit gate) and the decode step's routed-layer
    count buffers (None without any)."""

    __slots__ = ("program", "logits", "exit_pdf", "counts")

    def __init__(self, program: str, logits, exit_pdf, counts):
        self.program, self.logits = program, logits
        self.exit_pdf, self.counts = exit_pdf, counts


class PagedKVDecodeModel:
    """Device half of the continuous engine: the paged decode twin of
    a trained GPT plus its compiled step programs.

    step(tokens[b], seq_lens[b], block_tables[b, max_blocks]) runs one
    decode step for every slot at its OWN position and returns host
    logits [b, vocab].  The block tables and seq_lens are host-owned
    scheduler data written into the op-state pytree each step.

    prefill_chunk = C > 1 additionally compiles the [b, C]
    chunked-prefill program: one dispatch fills C prompt tokens per
    row at its own positions, so a P-token prompt costs ~P/C steps.
    Which program is ONE choice at build, read from the family's
    recipe (`prefill_passes` says which: weight passes a dispatch):
      * the scan (decoding.build_paged_prefill_step; GPT): C passes of
        the SAME seq-1 graph, so the K/V bytes it writes are
        bit-identical to one-token prefill — chunked greedy output
        stays token-identical to the unchunked oracle, which
        `speculative`, `handoff` and `disaggregated` are built on;
      * the pass (decoding.build_paged_prefill_pass; a recipe that
        carries `prefill_pass`: kimi_k2's latent cache): one forward
        over [b, C], the weights streamed and each layer's view built
        once a dispatch; equal to seq-1 stepping by tolerance, not by
        bytes, for a family that carries none of those three.  It
        returns host logits [b, vocab] at each row's last real token
        (`row_tokens`), so the scheduler samples from it and a row
        past its prompt rides it with its one pending token.

    copy_block(src, dst) is the prefix cache's copy-on-write primitive
    (one physical block cloned across every layer's pool, compiled
    once); prefix_cache=False lets the scheduler skip sharing without
    rebuilding the twin.

    paged_kernel is what the caller asks of the attention READ
    formulation: "auto" (the default: by backend and family), or
    "gather" / "pallas" by name — how the tests run the kernel under
    the interpreter against the gather.  Decided by pick_paged_read;
    `self.paged_kernel` is what it decided."""

    def __init__(self, ff_train, batch_slots: int = 8,
                 page_size: int = 16, num_blocks: Optional[int] = None,
                 devices=None, prefill_chunk: int = 0,
                 prefix_cache: bool = True,
                 paged_kernel: str = "auto", tp: int = 1,
                 spec_decode: str = "off", spec_k: int = 4,
                 draft_model=None):
        import jax

        from ..config import (ConfigError, resolve_serving_tp,
                              resolve_spec_decode)
        from ..decoding import (_gpt_dims, build_paged_copy_block,
                                build_paged_decode_step,
                                build_paged_prefill_pass,
                                build_paged_prefill_step,
                                build_paged_verify_step,
                                build_slot_state_reset, cache_entries,
                                cache_planes, decoder_recipe, make_decoder,
                                require_carried, slot_state_entries,
                                split_pass_counts)
        from ..ops.pallas.paged_attention import have_paged_kernel

        recipe = decoder_recipe(ff_train)
        self.paged_kernel = pick_paged_read(
            paged_kernel, backend=jax.default_backend(),
            have_kernel=have_paged_kernel(), carries=recipe.carries,
            family=recipe.family)
        self.spec_decode = resolve_spec_decode(spec_decode, spec_k)
        self.spec_k = int(spec_k)
        dims = _gpt_dims(ff_train)
        if self.spec_decode != "off":
            require_carried(ff_train, "speculative",
                            f"--spec-decode {self.spec_decode}")
        if prefix_cache:
            # FFConfig.prefix_cache defaults to True: a family whose
            # per-sequence state is more than pages (a page hit without
            # the recurrent state at that position is wrong) has to be
            # served with it off, said by name
            require_carried(ff_train, "prefix_cache",
                            "prefix_cache=True; pass prefix_cache=False "
                            "/ --no-prefix-cache")
        # tensor-parallel replica degree (docs/SERVING.md
        # "Tensor-parallel replicas"): the decode twin compiles over a
        # tp-chip {"data": 1, "model": tp} mesh, heads + KV pools
        # sharded — validated against head count / visible devices
        # HERE so a bad degree is a ConfigError at build, never a
        # mid-compile shape error
        self.tp = resolve_serving_tp(
            tp, num_heads=dims["num_heads"],
            visible_devices=(len(devices) if devices is not None
                             else None))
        max_seq = dims["max_seq"]
        if page_size < 1 or max_seq % page_size:
            raise ValueError(
                f"page_size {page_size} must divide the model's "
                f"max positions {max_seq}")
        max_blocks = max_seq // page_size
        if num_blocks is None:
            # default: half of the dense footprint (+ scratch) — the
            # HBM the pool actually saves; callers needing guaranteed
            # all-slots-at-max-length admission pass the full
            # 1 + batch_slots * max_blocks
            num_blocks = 1 + max(max_blocks,
                                 (batch_slots * max_blocks + 1) // 2)
        self.ffd = make_decoder(
            ff_train, batch_size=batch_slots, devices=devices,
            kv_page_size=page_size, kv_num_blocks=num_blocks,
            kv_kernel=self.paged_kernel, tp=self.tp,
        )
        if not cache_entries(self.ffd):
            # a twin without a paged pool (evabyte: all of a sequence's
            # state is its slot's own arrays, allocated with the twin):
            # the block table addresses nothing, so a sequence is
            # admitted by what it will really hold, a slot, and the
            # table is made wide enough never to refuse one
            num_blocks = max(num_blocks, 1 + batch_slots * max_blocks)
        self.batch_slots = batch_slots
        self.page_size = page_size
        self.num_blocks = num_blocks
        self.max_blocks_per_seq = max_blocks
        self.max_seq = max_seq
        self.vocab = dims["vocab_size"]
        self.prefill_chunk = max(0, int(prefill_chunk))
        if self.prefill_chunk == 1:
            self.prefill_chunk = 0  # a 1-token chunk IS the decode step
        self.prefix_cache = bool(prefix_cache)
        self._step_fn = build_paged_decode_step(self.ffd)
        # the chunked-prefill program: one pass over [slots, C] where
        # the family's recipe says its graph allows it, else the scan of
        # the seq-1 step; `prefill_passes` = weight passes a dispatch
        one_pass = "prefill_pass" in recipe.carries
        self.prefill_passes = 1 if one_pass else self.prefill_chunk
        self._prefill_fn = (
            (build_paged_prefill_pass if one_pass
             else build_paged_prefill_step)(self.ffd, self.prefill_chunk)
            if self.prefill_chunk else None)
        self._copy_fn = build_paged_copy_block(self.ffd)
        # a family on the one-pass program keeps each row's greedy id
        # on the device (GPT's scan and step are held to byte equality
        # with their oracle and stay the programs they were: one rule,
        # the recipe's carry, chooses both): its sampling programs take
        # the last sampling dispatch's `ids` and `take_prev` (which rows
        # are fed from them, not from the host's tokens) and return
        # their own, so a dispatch can be enqueued before the one
        # before it is fetched (`launch_step` / `launch_prefill` /
        # `land`).  `step` and `prefill_step` pass `take_prev` all zero:
        # one program a family either way
        self.keeps_ids = one_pass
        self._ids = None  # (made at the first sampling dispatch)
        # {program: the static args of its `model.enqueue` span}, made
        # at the program's first call (`_enqueue`); {program: bytes its
        # `model.fetch` brings back}
        self._moved: Dict[str, Dict] = {}
        self._fetched: Dict[str, int] = {}
        # speculative verify twin (docs/SERVING.md "Speculative
        # decoding"): ONE [slots, spec_k+1] program scores a pending
        # token plus up to spec_k drafts per row — per-position logits
        # bit-identical to seq-1 stepping, so greedy acceptance keeps
        # output token-identical to the plain engine.  counts is data:
        # adaptive-k rounds reuse the same compiled program.
        self.verify_chunk = self.spec_k + 1 if self.spec_decode != "off" \
            else 0
        self._verify_fn = (
            build_paged_verify_step(self.ffd, self.verify_chunk)
            if self.spec_decode != "off" else None)
        self.draft_model = draft_model
        if self.spec_decode == "draft":
            dm = draft_model
            if dm is None:
                raise ConfigError(
                    "--spec-decode draft needs a draft model — pass "
                    "draft_model= (or from_trained(..., draft_ff=)) "
                    "or use --spec-decode ngram")
            if int(getattr(dm, "vocab", -1)) != self.vocab:
                raise ConfigError(
                    f"draft model vocab {getattr(dm, 'vocab', None)} "
                    f"!= target vocab {self.vocab} — draft token ids "
                    f"are proposed verbatim, so the vocabularies must "
                    f"match")
            if int(getattr(dm, "max_seq", 0)) < max_seq:
                raise ConfigError(
                    f"draft model position table "
                    f"({getattr(dm, 'max_seq', 0)}) is shorter than "
                    f"the target's ({max_seq}) — the drafter must be "
                    f"able to reach every target position")
            if int(getattr(dm, "batch_slots", 0)) < batch_slots:
                raise ConfigError(
                    f"draft model has {getattr(dm, 'batch_slots', 0)} "
                    f"slots < the target's {batch_slots} — draft rows "
                    f"mirror engine slots 1:1")
        # the step fns DONATE their state argument.  The twin's own
        # pytree is taken over and threaded, and the twin keeps its
        # shapes, dtypes and shardings (all that reset() reads after a
        # failed step): the pools exist ONCE on the device, where a
        # private copy beside a pristine one held them twice (8 GB
        # twice does not fit a chip)
        import jax

        self._state = self.ffd._state
        self.ffd._state = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            self._state)
        # bytes of ONE physical block summed across every layer's k/v
        # pool — the unit of the kernel-read telemetry (blocks read *
        # this = per-step KV bytes the fused kernel streams; the
        # dense-gather equivalent is table_width blocks per slot).
        # Shapes here are GLOBAL (GSPMD arrays report the logical
        # shape); each of a tp replica's chips holds 1/tp of the head
        # axis, so per-chip bytes are the global count / tp.
        # An op a repeated region runs holds a plane a pass in each
        # pool (`cache_planes`): a block of the sequence's one table is
        # that many pages there, and is counted, copied, exported and
        # imported as such.
        self._pools = cache_entries(self.ffd)
        self._planes = cache_planes(self.ffd)
        self.kv_block_bytes = sum(
            int(np.prod(self._state[op][k].shape[1:]))
            * self._state[op][k].dtype.itemsize * self._planes[op]
            for op, names in self._pools.items() for k in names)
        self.kv_block_bytes_per_chip = self.kv_block_bytes // self.tp
        # routed-expert layers count their routing in a state entry the
        # step program returns anyway (ops/routed_experts.py MOE_STATS);
        # `step` fetches it with the logits and keeps the last
        # dispatch's counts, summed over layers, here
        # a layer with identity experts counts their picks in a second
        # entry (MOE_ZERO_STATS), fetched and kept the same way: the
        # picks summed, the least and the most real picks a row over
        # the layers.  The one-pass prefill program counts its real
        # tokens alone and returns the layers' counts IN the logits'
        # buffer, as rows behind them: no further buffer to fetch
        # (decoding.build_paged_prefill_pass, `split_pass_counts`)
        self._moe_ops = [op for op, entries in self._state.items()
                         if "moe_stats" in entries]
        self._moe_zero_ops = [op for op in self._moe_ops
                              if "moe_zero" in self._state[op]]
        self._split_pass_counts = split_pass_counts
        self.moe_last: Optional[Dict[str, int]] = None
        # repeated regions of the twin's graph (`loop_regions`,
        # `loop_steps`, `loop_ops`; {} without one): a step program
        # reads the regions' weights `loop_steps` times a pass.  A
        # family with an exit gate also gets each row's exit pdf back
        # from the decode step, fetched with the logits: `exit_last`
        # [slots, passes] of the last dispatch
        self.loop = dict(self.ffd.executor.loop_counts)
        self.loop_steps = int(self.loop.get("loop_steps", 0))
        self.exit_last: Optional[np.ndarray] = None
        # per-slot recurrent state (`slot_state_entries`): [slots, ...]
        # arrays beside the pools, of fixed size; a twin that has any
        # takes `row_tokens` in its step programs, and the scheduler
        # zeroes a slot's rows when it admits a request into it
        self._slot_state = slot_state_entries(self.ffd)
        # what a dispatch costs the mixers that say so, and what they
        # keep (`Op.dispatch_group`; docs/SERVING.md "What a mixer with
        # serving state declares"): the ops of a name answer together,
        # once, here (`Op.dispatch_group_of`, which also refuses a
        # `prefill_chunk` they cannot take), and the engine knows the
        # names it is handed, no op's.  `groups` {name: what they said,
        # its geometry with `layers` and `state_bytes` added}
        named: Dict[str, list] = {}
        for op in self.ffd.operators.topo_order():
            if (name := op.dispatch_group()):
                named.setdefault(name, []).append(op)
        bytes_of = lambda ops: sum(  # noqa: E731
            int(self._state[op.name][k].nbytes)
            for op in ops for k in op.slot_state_entries())
        self.groups: Dict[str, DispatchGroup] = {}
        for name, ops in named.items():
            held = bytes_of(ops)
            told = type(ops[0]).dispatch_group_of(
                ops, family=recipe.family, batch_slots=batch_slots,
                page_size=page_size, max_seq=max_seq,
                prefill_chunk=self.prefill_chunk, state_bytes=held)
            self.groups[name] = dataclasses.replace(
                told, geometry=dict(told.geometry, layers=len(ops),
                                    state_bytes=held))
        # the state that the reset at admission zeroes (a state masked
        # by the sequence's own positions has nothing to zero)
        self.rstate_bytes = bytes_of(
            op for op in self.ffd.operators.topo_order()
            if op.slot_state_resets)
        self._reset_slot_fn = (build_slot_state_reset(self.ffd)
                               if self._slot_state else None)
        self.mesh_shape = {
            str(k): int(s)
            for k, s in zip(self.ffd.mesh.axis_names,
                            self.ffd.mesh.devices.shape)
        } if getattr(self.ffd, "mesh", None) is not None else {}

    def reset(self):
        """Fresh zero decode state (fault recovery: a step that died
        mid-execution may have invalidated the donated buffers).  The
        scheduler invalidates the pool's prefix index right after —
        cached blocks' bytes are zeroed with everything else.  Zeros
        are placed onto each leaf's compiled NamedSharding — on a tp
        replica mesh a bare jnp.zeros would land single-device and the
        donated step program would reject (or silently reshard) the
        mismatched state on the next dispatch."""
        import jax
        import jax.numpy as jnp

        self._state = None  # (the old pools go before the new ones come)
        self._ids = None
        self._state = jax.tree.map(
            lambda x: jax.device_put(
                jnp.zeros(x.shape, x.dtype), x.sharding),
            self.ffd._state)

    @property
    def has_slot_state(self) -> bool:
        return bool(self._slot_state)

    def dispatch_counts(self, positions, counts,
                        chunk: int = 1) -> Dict[str, Dict[str, int]]:
        """{group: the args of the span of a dispatch that advances row
        i over `positions[i] .. + counts[i] - 1` in a program of `chunk`
        tokens a row}, as each group's ops count it
        (`DispatchGroup.counts`): host arithmetic on host-owned
        lengths, no fetch.  {} for a twin without a group."""
        return {name: told.counts(positions, counts, chunk)
                for name, told in self.groups.items()}

    def _row_tokens(self, row_tokens, one_pass: bool = False,
                    take_prev=None) -> tuple:
        """The step programs' trailing arguments: `row_tokens` for a
        twin with per-slot state and for the one-pass prefill program
        (required there), nothing otherwise; then, for a family that
        keeps its ids on the device, the last sampling dispatch's `ids`
        and `take_prev` (None: no row takes its token from them)."""
        rows: tuple = ()
        if self._slot_state or one_pass:
            if row_tokens is None:
                raise ValueError(
                    "this twin carries per-slot recurrent state, or this "
                    "is its one-pass prefill program: it needs row_tokens "
                    "(how far each row advances)")
            rows = (np.asarray(row_tokens, np.int32),)
        if not self.keeps_ids:
            return rows
        if self._ids is None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec

            # (placed as the programs return theirs, replicated over
            # the twin's mesh: the first call's program is then every
            # later call's)
            self._ids = jax.device_put(
                np.zeros(self.batch_slots, np.int32),
                NamedSharding(self.ffd.mesh, PartitionSpec()))
        if take_prev is None:
            take_prev = np.zeros(self.batch_slots, np.int32)
        return (*(rows or (None,)), self._ids,
                np.asarray(take_prev, np.int32))

    def reset_slot_state(self, slot: int) -> None:
        """Zero ONE slot's recurrent state (admission): ordered with
        the step stream by jax's state dependency, like copy_block."""
        import jax.numpy as jnp

        if self._reset_slot_fn is None:  # state masked by position only
            return
        self._state = self._enqueue("reset_slot_state", self._reset_slot_fn,
                                    self._state, jnp.int32(slot))

    def _enqueue(self, program: str, fn, *args):
        """`fn(*args)`, a jitted program's call, under `model.enqueue`.
        The span says what the call moved, counted once a program at its
        first call (the shapes never change): `program`, `arg_leaves`
        (array leaves of everything passed: weights, state, host arrays)
        and `host_bytes` (bytes of the host arrays, which the call
        copies in); `first` is 1 on that call (its lazy compile)."""
        moved = self._moved.get(program)
        first = moved is None
        if first:
            import jax

            leaves = jax.tree.leaves(args)
            moved = self._moved[program] = {
                "program": program, "arg_leaves": len(leaves),
                "host_bytes": sum(int(x.nbytes) for x in leaves
                                  if isinstance(x, np.ndarray))}
        with span("model.enqueue", first=int(first), **moved):
            return fn(*args)

    def step(self, tokens: np.ndarray, seq_lens: np.ndarray,
             block_tables: np.ndarray, row_tokens=None) -> np.ndarray:
        return self.land(self.launch_step(tokens, seq_lens, block_tables,
                                          row_tokens))

    def launch_step(self, tokens: np.ndarray, seq_lens: np.ndarray,
                    block_tables: np.ndarray, row_tokens=None,
                    take_prev=None) -> "_Launched":
        """Enqueue the decode step and return what it left on the
        device (`land` fetches it).  `take_prev[i]` set (a family that
        `keeps_ids` only): row i is fed the id the last sampling
        dispatch chose for it, which the host has not seen yet."""
        # per-token hot path: the block table / seq_lens override
        # happens INSIDE the jitted step and the state pytree is
        # donated — no host-side dict rebuild, no per-layer pool copy
        out = self._enqueue(
            "step", self._step_fn, self.ffd._weights, self._state, tokens,
            seq_lens, block_tables,
            *self._row_tokens(row_tokens, take_prev=take_prev))
        logits, exit_pdf = self._took(out)
        counts = None
        if self._moe_ops and not exit_pdf and not self.keeps_ids:
            # (the step leaves its counts in the state, two buffers a
            # layer; a program that keeps its ids returns them behind
            # the logits' rows, as the one-pass prefill does: the state
            # is the next dispatch's before a flight is fetched)
            counts = {"moe_stats": [self._state[op]["moe_stats"]
                                    for op in self._moe_ops],
                      "moe_zero": [self._state[op]["moe_zero"]
                                   for op in self._moe_zero_ops]}
        return _Launched("step", logits, exit_pdf, counts)

    def _took(self, out) -> tuple:
        """(logits, exit_pdf) of a sampling program's outputs, the
        state and the ids kept here."""
        if self.keeps_ids:
            *out, self._ids = out
        logits, self._state, *exit_pdf = out
        return logits, exit_pdf

    def land(self, launched: "_Launched", behind: bool = False) -> np.ndarray:
        """The wait for the device, then the logits' copy to the host
        and with it, in the one `device_get`, what the program returned
        beside them: the rows' exit pdf (`exit_last`) and, from the
        decode step, the routed layers' counts (`moe_last`; a buffer
        a layer and entry, as the step leaves them in its state; the
        one-pass program's come as rows behind the logits).  The span
        carries `program` and the `bytes` that came back; it is
        `model.fetch`, or `model.fetch_behind` when another sampling
        dispatch was enqueued since this one (`behind`): the wait is
        then beside that dispatch's launch, not between two."""
        import jax

        program, logits = launched.program, launched.logits
        exit_pdf, counts = launched.exit_pdf, launched.counts
        with span("model.fetch_behind" if behind else "model.fetch",
                  program=program) as fetch:
            beside = {**({"exit": exit_pdf[0]} if exit_pdf else {}),
                      **(counts or {})}
            if beside:
                logits, beside = jax.device_get((logits, beside))
            else:
                logits = np.asarray(logits)
            if program not in self._fetched:
                self._fetched[program] = sum(
                    int(x.nbytes) for x in jax.tree.leaves((logits, beside)))
            fetch.set(bytes=self._fetched[program])
            if exit_pdf:
                self.exit_last = beside["exit"]
            if counts:
                self.moe_last = self._summed(beside["moe_stats"],
                                             beside["moe_zero"])
            logits = np.asarray(logits, np.float32)
        if self.keeps_ids and self._moe_ops:
            # (such a family's sampling programs, pass and step alike,
            # return the routed layers' counts behind the logits' rows)
            logits, counts = self._split_pass_counts(
                logits, self.batch_slots, len(self._moe_ops),
                len(self._moe_zero_ops))
            self.moe_last = self._summed(**counts)
        return logits

    @staticmethod
    def _summed(moe_stats, moe_zero) -> Dict[str, int]:
        """The routed layers' counts ([layers, 4], [layers with identity
        experts, 3]) over the layers: sums, but the least and the most
        real picks a row."""
        out = dict(zip(MOE_STATS,
                       (int(v) for v in np.sum(moe_stats, axis=0))))
        if len(moe_zero):
            picks, least, most = np.asarray(moe_zero).T
            out.update(zip(MOE_ZERO_STATS, (
                int(picks.sum()), int(least.min()), int(most.max()))))
        return out

    def prefill_step(self, tokens: np.ndarray, positions: np.ndarray,
                     block_tables: np.ndarray, row_tokens=None,
                     meanwhile=None) -> Optional[np.ndarray]:
        """Chunked prefill: scatter tokens[b, C] at positions[b]..+C-1
        into the pool.  The scan returns nothing (the last prompt token
        runs through the decode program).  The one-pass program
        (`prefill_passes` 1) takes `row_tokens` whatever the family and
        returns host logits [b, vocab] at each row's last real token,
        fetched as `step` fetches its own; the routed layers' counts
        over the pass's real tokens come in the same buffer, behind
        the logits' rows (`moe_last`).
        `meanwhile()` is called once the program is enqueued, before
        the wait for it: host work that needs no result of the
        dispatch runs beside the device, not after it."""
        launched = self.launch_prefill(tokens, positions, block_tables,
                                       row_tokens)
        if meanwhile is not None:
            meanwhile()
        return None if launched is None else self.land(launched)

    def launch_prefill(self, tokens: np.ndarray, positions: np.ndarray,
                       block_tables: np.ndarray, row_tokens=None,
                       take_prev=None) -> Optional["_Launched"]:
        """Enqueue the chunked-prefill program; what the one-pass
        program left on the device for `land`, None from the scan
        (nothing to fetch).  `take_prev` as `launch_step` takes it, for
        the rows' column 0."""
        one_pass = self.prefill_passes == 1
        out = self._enqueue(
            "prefill", self._prefill_fn, self.ffd._weights, self._state,
            tokens, positions, block_tables,
            *self._row_tokens(row_tokens, one_pass, take_prev))
        if not one_pass:
            self._state = out
            return None
        return _Launched("prefill", *self._took(out), None)

    def verify_step(self, tokens: np.ndarray, seq_lens: np.ndarray,
                    counts: np.ndarray,
                    block_tables: np.ndarray) -> np.ndarray:
        """Speculative verify: feed tokens[b, :counts[b]] at
        seq_lens[b].. and return per-position logits
        [b, verify_chunk, vocab] — row i's logits[j] are bit-identical
        to what the decode step would have produced feeding
        tokens[i, j] at seq_lens[i]+j (docs/SERVING.md "Speculative
        decoding").  Built only when spec_decode != "off"."""
        logits, self._state = self._enqueue(
            "verify", self._verify_fn, self.ffd._weights, self._state,
            tokens, seq_lens, counts, block_tables)
        return self.land(_Launched("verify", logits, (), None))

    def copy_block(self, src: int, dst: int) -> None:
        """Copy-on-write: clone physical block src -> dst in every
        layer's k/v pool (ordered with the step stream by jax's state
        dependency, so a following step reads the copied bytes)."""
        import jax.numpy as jnp

        self._state = self._copy_fn(
            self._state, jnp.int32(src), jnp.int32(dst))

    def export_block(self, block: int) -> Dict[str, np.ndarray]:
        """Device->host read of ONE physical block across every layer's
        k/v pool — the migration export path (serving/kv_transfer.py).
        Keyed "<op>/<pool entry>" so import lands each page back in the
        matching layer.  Worker-thread only: the state pytree is
        donated to the step programs, so reads must sit between steps."""
        return {f"{name}/{k}": np.asarray(
                    self._state[name][k][self._block_rows(name, k, block)])
                for name, names in self._pools.items() for k in names}

    def _block_rows(self, name: str, entry: str, block: int):
        """Where one block of the table lives in a pool: its row, or,
        in a pool of several planes, its row in each (an index array:
        the exported page is then `[planes, page, ...]`)."""
        planes = self._planes[name]
        if planes == 1:
            return block
        return block + np.arange(planes) * (
            self._state[name][entry].shape[0] // planes)

    def import_block(self, block: int,
                     arrays: Dict[str, np.ndarray]) -> None:
        """Host->device write of one migrated block into every layer's
        pool, sharding-preserving (a tp replica's head-sharded pools
        keep their NamedSharding — a bare at[].set result could land
        single-device).  Worker-thread only, like export_block."""
        import jax
        import jax.numpy as jnp

        state = {}
        for name, entries in self._state.items():
            e = dict(entries)
            for k in self._pools.get(name, ()):
                v = e[k]
                page = jnp.asarray(arrays[f"{name}/{k}"], v.dtype)
                e[k] = jax.device_put(
                    v.at[self._block_rows(name, k, block)].set(page),
                    v.sharding)
            state[name] = e
        self._state = state


class _PendingSeq:
    """Future-style handle for one continuous-mode request.  Besides
    the final token list it records the SLO timestamps load generators
    and telemetry consume: submit, first generated token (TTFT), done.

    `on_done` (set at submission, never after) fires exactly once when
    the request settles — success, fault, or drain — on whichever
    thread settled it.  The replicated front (serving/front.py) rides
    it to route completions/requeues without polling handles."""

    __slots__ = ("prompt", "max_new_tokens", "temperature", "seed",
                 "event", "result", "error", "t_submit", "t_first_token",
                 "t_done", "n_generated", "prefix_hit_tokens",
                 "spec_proposed", "spec_accepted", "on_done", "trace",
                 "resume", "resume_out", "_settle_lock", "_settled")

    def __init__(self, prompt, max_new_tokens, temperature, seed,
                 on_done=None, trace=None, resume=None):
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.seed = int(seed)
        # resume: a handoff.ResumeRecord continuing a mid-decode
        # generation (the re-fed tokens replay as prompt, the sampling
        # RNG restores mid-stream).  resume_out: stamped by the
        # scheduler when it settles this handle un-finished with
        # recoverable state (death, drain) so the front's requeue
        # resumes instead of regenerating from scratch.
        self.resume = resume
        self.resume_out = None
        self.event = threading.Event()
        self.result: Optional[List[int]] = None
        self.error: Optional[Exception] = None
        self.t_submit = time.monotonic()
        self.t_first_token: Optional[float] = None
        self.t_done: Optional[float] = None
        self.n_generated = 0
        self.prefix_hit_tokens = 0  # prompt tokens served from cache
        self.spec_proposed = 0   # draft tokens verified for this request
        self.spec_accepted = 0   # ... of which the target agreed with
        self.on_done = on_done
        self.trace = trace  # TraceContext (obs/reqtrace.py) or None
        self._settle_lock = threading.Lock()
        self._settled = False

    def _settle(self) -> None:
        """Wake the waiter and fire the completion hook — exactly once,
        even when a drain races the submit path's late-enqueue check
        (both may settle the same request; the second is a no-op)."""
        with self._settle_lock:
            if self._settled:
                return
            self._settled = True
        self.event.set()
        if self.on_done is not None:
            try:
                self.on_done(self)
            except Exception:  # noqa: BLE001 — a hook must never kill
                pass           # the decode loop or a drain

    def wait(self, timeout: Optional[float] = None) -> List[int]:
        if not self.event.wait(timeout):
            raise TimeoutError("generation request timed out")
        if self.error is not None:
            raise self.error
        return self.result


class _Live:
    """Slot-resident decoding state for one admitted sequence.
    `start` > 0 means a prefix-cache hit: positions [0, start) are
    already in shared KV blocks and never prefill.

    `feed` is the token stream positions consume before sampling
    begins: the prompt, or — for a resumed mid-decode handoff — the
    prompt plus every previously generated token (replayed as prompt,
    so the KV state rebuilds bit-identically).  `generated` is then
    pre-seeded with those tokens: the completion and the generation
    budget count them exactly as the uninterrupted run would."""

    __slots__ = ("req", "seq_id", "pos", "next_token", "generated",
                 "max_new", "rng", "feed", "tspan", "unsettled")

    def __init__(self, req: _PendingSeq, seq_id: int, max_new: int,
                 start: int = 0, feed=None, generated=None,
                 rng_state=None):
        self.req = req
        self.seq_id = seq_id
        self.feed = req.prompt if feed is None else list(feed)
        # tokens already in the cache, those that a dispatch still in
        # flight writes included (advanced once it is enqueued)
        self.pos = start
        self.next_token = self.feed[start]  # token fed at position pos
        # sampled tokens that dispatches in flight owe this row: the
        # host has not seen them (`next_token` is then stale: the next
        # dispatch takes the row's token from the device's ids)
        self.unsettled = 0
        self.generated: List[int] = list(generated or [])
        self.max_new = max_new            # clamped to the position table
        self.rng = (np.random.RandomState(req.seed)
                    if req.temperature > 0.0 else None)
        if self.rng is not None and rng_state is not None:
            # mid-stream resume: continue the sampled sequence exactly
            # where the pause captured it — the replayed tokens make
            # no draws, so the state is already post-draw-correct
            self.rng.set_state(rng_state)
        self.tspan: Optional["_LiveTrace"] = None  # request-trace state


class _LiveTrace:
    """Per-slot request-trace bookkeeping (obs/reqtrace.py) for ONE
    traced live row.  The row owns exactly one open PHASE span at a
    time ("prefill" until its first generated token, then "decode");
    batched dispatches (prefill chunks, decode steps, verify rounds)
    each have ONE host span (`sched.*.dispatch`, obs/trace.py), and the
    per-request phase span REFERENCES those by span id instead of
    duplicating them — N rows riding one dispatch never write N copies
    of it."""

    __slots__ = ("ctx", "pid", "span", "chunks", "chunk_refs",
                 "steps", "spec_rounds", "batch_refs")

    MAX_REFS = 64  # cap the per-request batch-span reference list

    def __init__(self, ctx, pid: int, hit_tokens: int, plen: int):
        self.ctx = ctx
        self.pid = pid
        self.span = ctx.begin("prefill", pid=pid,
                              prefix_hit_tokens=hit_tokens,
                              prompt_len=plen)
        self.chunks = 0        # chunked-prefill dispatches ridden
        self.chunk_refs: List[int] = []  # their dispatch span ids
        self.steps = 0         # decode/verify dispatches ridden
        self.spec_rounds = 0   # of which were speculative verifies
        self.batch_refs: List[int] = []  # decode-phase dispatch span ids

    def ref_chunk(self, dispatch) -> None:
        self.chunks += 1
        if len(self.chunk_refs) < self.MAX_REFS:
            self.chunk_refs.append(dispatch.span_id)

    def ref_step(self, dispatch, spec: bool = False) -> None:
        self.steps += 1
        if spec:
            self.spec_rounds += 1
        if len(self.batch_refs) < self.MAX_REFS:
            self.batch_refs.append(dispatch.span_id)

    def to_decode(self) -> None:
        """First generated token: close the prefill phase, open decode."""
        self.span.end(chunks=self.chunks, batch_spans=self.chunk_refs)
        self.span = self.ctx.begin("decode", pid=self.pid)

    def finish(self, req: _PendingSeq) -> None:
        self.span.end(steps=self.steps, n_generated=req.n_generated,
                      spec_rounds=self.spec_rounds,
                      spec_proposed=req.spec_proposed,
                      spec_accepted=req.spec_accepted,
                      batch_spans=self.batch_refs)


class _Flight:
    """One sampling dispatch from its enqueue to the settling of its
    rows: its span, what it left on the device (`_Launched`; None once
    fetched), `rows` [(slot, live, position before it, tokens fed)] and
    the `_kv_reads` of it."""

    __slots__ = ("program", "dispatch", "launched", "rows", "reads")

    def __init__(self, program: str, dispatch, launched, rows, reads):
        self.program, self.dispatch, self.launched = (
            program, dispatch, launched)
        self.rows, self.reads = rows, reads


def advanced_slots(slots) -> list:
    """The slots as the NEXT dispatch sees them while one is in flight:
    a row that the dispatches in flight give its last token (by length:
    `max_new` is known a dispatch ahead, an EOS is not) is no row of
    it.  Pure, like the plan it feeds: once the flights are settled it
    is `slots` without the rows they finished."""
    return [None if live is None
            or len(live.generated) + live.unsettled >= live.max_new
            else live for live in slots]


def plan_chunk_rows(slots, chunk: int, sampled: bool) -> List[tuple]:
    """THE step plan of a chunked-prefill dispatch, a pure function of
    the slots: `[(slot, live, fed)]`, the rows of the [slots, C]
    program that really advance and by how many tokens each; `[]` when
    no row is still feeding its prompt (no chunk dispatch: the plain
    decode step runs).

    `sampled` False (the scan returns no logits): the feeding rows
    alone, each up to its feed's last token but one — that one runs
    through the decode program, whose logits seed sampling — and the
    decode dispatch follows.  `sampled` True (the one-pass program
    returns each row's logits at its last real token): EVERY live row.
    A feeding row takes up to C tokens, its feed's last included; a row
    past its prompt (or with only that last token left) takes its one
    pending token.  Every row whose feed ends inside the dispatch is
    sampled from it, and no decode dispatch follows."""
    left = [(i, live, len(live.feed) - live.pos)
            for i, live in enumerate(slots) if live is not None]
    if not any(n > 1 for _, _, n in left):
        return []
    if sampled:
        return [(i, live, max(1, min(chunk, n))) for i, live, n in left]
    return [(i, live, min(chunk, n - 1)) for i, live, n in left if n > 1]


class ContinuousScheduler:
    """Persistent decode loop with iteration-level admission/retirement.

    API-compatible with GenerationBatcher (generate / generate_async /
    latency_stats / close / batches_run / requests_done), so serve_http
    and a load generator drive either engine unchanged.  `batches_run`
    counts decode steps here — the unit of batching is the step."""

    def __init__(self, model, pool: Optional[KVPool] = None,
                 eos_id: int = -1, registry=None, seed: int = 0,
                 latency_window: int = 1024,
                 close_timeout_s: float = 60.0,
                 on_death=None, check_invariants: bool = False,
                 reqtrace=None, trace_pid: int = 0):
        self.model = model
        # per-request distributed tracing (obs/reqtrace.py): requests
        # arrive carrying a TraceContext minted at the front; this
        # engine contributes phase + batch spans on its own Perfetto
        # track (`trace_pid` = replica id).  None keeps every hot-path
        # check a single `is not None` that allocates nothing.
        self._reqtrace = (reqtrace if reqtrace is not None
                          and getattr(reqtrace, "enabled", True)
                          else None)
        self._trace_pid = int(trace_pid)
        self.pool = pool or KVPool(
            model.num_blocks, model.page_size, model.max_blocks_per_seq,
            prefix_cache=bool(getattr(model, "prefix_cache", True)))
        # chunked prefill: C prompt tokens per dispatch through the
        # model's second compiled program (0 = one-token prefill, the
        # PR 6 path); COW needs the model's device block copy
        self._chunk = int(getattr(model, "prefill_chunk", 0) or 0)
        # weight passes of one prefill dispatch: C for the scanned
        # seq-1 step, 1 for a family's one-pass program
        self._passes = int(getattr(model, "prefill_passes", self._chunk))
        if self._chunk and getattr(model, "prefill_step", None) is None:
            self._chunk = 0
        # the one-pass program returns each row's logits at its last
        # real token: while a row is feeding, an iteration is that ONE
        # dispatch with every live row in it (`plan_chunk_rows`)
        self._pass_samples = bool(self._chunk) and self._passes == 1
        self.pass_decode_tokens = 0  # tokens sampled from such passes
        # one dispatch of lookahead (docs/SERVING.md "The step plan"):
        # a model whose sampling programs keep the greedy id on the
        # device lets a dispatch be enqueued before the one before it
        # is fetched.  `_flights`: the dispatches enqueued and not yet
        # settled, oldest first (one between iterations, two while the
        # next is being enqueued)
        self._keeps_ids = bool(getattr(model, "keeps_ids", False))
        self._flights: deque = deque()
        self._landed_at = 0.0  # when the last flight's logits arrived
        self.dispatches_ahead = 0  # enqueued behind an unfetched one
        # why a sampling dispatch was fetched at once or a flight ended
        # out of turn (`_synchronous`; `fault`: flights dropped)
        self.lookahead_drains = dict.fromkeys(
            ("temperature", "spec", "service", "fault", "family"), 0)
        self.overrun_tokens = 0  # sampled for a row an EOS had ended
        self._can_cow = getattr(model, "copy_block", None) is not None
        # fused-kernel read telemetry (docs/SERVING.md "Fused paged
        # attention"): under paged_kernel="pallas" every dispatch
        # streams only each live row's own blocks, so we track the
        # physical blocks actually read vs what the dense gather
        # formulation would have materialized for the same dispatches
        # (scratch-block fetches excluded — they are one elided page).
        self._paged_kernel = str(getattr(model, "paged_kernel",
                                         "gather"))
        self._kv_block_bytes = int(getattr(model, "kv_block_bytes", 0))
        self.kernel_blocks_read = 0   # physical blocks streamed
        self.kernel_dense_blocks = 0  # gather-equivalent block reads
        # routed-expert counters summed over the dispatches whose
        # logits are sampled, by program (a model without such layers
        # leaves them None): stats()["moe"], the decode step's under the
        # bare names and `dispatches` (every slot's row), the sampling
        # passes' under `prefill_<name>` and `prefill_dispatches` (their
        # real tokens alone)
        self.moe_totals: Optional[Dict[str, int]] = None
        # a model whose graph repeats a region: weight passes of its
        # dispatches and, with an exit gate, the exit pdf of the live
        # rows summed over decode dispatches: stats()["loop"] (a model
        # without a region leaves these off every span and out of
        # stats())
        self._loop_steps = int(getattr(model, "loop_steps", 0) or 0)
        self.loop_totals: Optional[Dict] = (
            {"decode_dispatches": 0, "decode_weight_passes": 0,
             "prefill_dispatches": 0, "prefill_weight_passes": 0,
             "exit_rows": 0, "exit_mass": []}
            if self._loop_steps else None)
        # a model with per-slot state takes `row_tokens` in its step
        # programs; where some of that state is zeroed at admission
        # (`rstate_bytes`), the scheduler asks for it
        self._rstate = bool(getattr(model, "has_slot_state", False))
        self._resets = bool(getattr(model, "rstate_bytes", 0))
        # what the model's mixers count of a dispatch (`model.groups`,
        # `model.dispatch_counts`: `Op.dispatch_group`; a model without
        # a group leaves these off every span and out of stats()), and
        # {group: the counts summed by program}
        self._groups = dict(getattr(model, "groups", None) or {})
        self.group_totals: Dict[str, Dict[str, int]] = {
            name: {"decode_dispatches": 0, "prefill_dispatches": 0}
            for name in self._groups}
        # bench/debug: run the pool's full invariant sweep after every
        # scheduler step (the serving_prefix leg's acceptance bar)
        self._check_invariants = bool(check_invariants)
        self._evictions_seen = 0  # delta base for the obs counter
        self.prefill_steps = 0    # chunked-prefill dispatches
        # speculative decoding (serving/speculative.py,
        # docs/SERVING.md "Speculative decoding"): the model carries
        # the mode, the verify program and (for "draft") the draft
        # twin; the scheduler owns the proposer, the adaptive-k
        # controller and the accept/rollback loop.  A model without
        # the verify surface (test fakes) simply runs with spec off.
        spec = str(getattr(model, "spec_decode", "off") or "off")
        self._spec_k = int(getattr(model, "spec_k", 0) or 0)
        self._proposer = None
        if (spec != "off" and self._spec_k >= 1
                and getattr(model, "verify_step", None) is not None):
            from .speculative import AdaptiveK, build_proposer

            self._proposer = build_proposer(
                spec, getattr(model, "draft_model", None))
            self._adaptive = AdaptiveK(self._spec_k)
        self._spec = spec if self._proposer is not None else "off"
        self._spec_broken = False  # verify/proposer fault: plain decode
        self._spec_t0: Optional[float] = None
        self.spec_rounds = 0        # verify dispatches run
        self.spec_fallback_rounds = 0  # spec on, but a round had no
        self.spec_proposed = 0         # proposals -> plain decode step
        self.spec_accepted = 0
        self.spec_verify_faults = 0
        self.eos_id = int(eos_id)
        self.registry = registry
        # tensor-parallel geometry gauges (serving/tp_* group,
        # docs/OBSERVABILITY.md): static per-engine facts, set once
        if registry is not None:
            tp = int(getattr(model, "tp", 1))
            registry.gauge("serving/tp_degree").set(tp)
            registry.gauge("serving/tp_chips").set(
                max(1, int(np.prod(list(
                    (getattr(model, "mesh_shape", None) or {"": tp})
                    .values())))))
            registry.gauge("serving/tp_kv_block_bytes_per_chip").set(
                int(getattr(model, "kv_block_bytes_per_chip",
                            getattr(model, "kv_block_bytes", 0))))
            registry.gauge("serving/tp_kv_pool_bytes_per_chip").set(
                int(getattr(model, "kv_block_bytes_per_chip",
                            getattr(model, "kv_block_bytes", 0)))
                * int(getattr(model, "num_blocks", 0)))
            for told in self._groups.values():
                for name, value in told.gauges.items():
                    registry.gauge(f"serving/{name}").set(int(value))
        self._queue: "queue.Queue[_PendingSeq]" = queue.Queue()
        self._waiting: deque = deque()  # worker-local FIFO admit order
        # worker-marshalled service calls (KV block import, export):
        # the state pytree is donated to the step programs, so ONLY the
        # worker may touch it — run_on_worker() queues a callable the
        # loop executes between steps
        self._service: "queue.Queue" = queue.Queue()
        # measured per-dispatch wall time (EWMA over decode + prefill
        # dispatches): the disagg dispatcher's re-prefill cost unit
        self.step_ms_ewma = 0.0
        self._stop = threading.Event()
        self._latencies = deque(maxlen=latency_window)
        self._ttfts = deque(maxlen=latency_window)
        self._lat_lock = threading.Lock()
        self._slots: List[Optional[_Live]] = [None] * model.batch_slots
        # persistent step buffers, updated INCREMENTALLY: block-table
        # rows change only on admit/retire and when a row crosses a
        # page boundary (every page-th token), not per step — the
        # decode loop's python cost stays O(live rows), not
        # O(rows x table width)
        self._tokens = np.zeros(model.batch_slots, np.int32)
        self._slens = np.zeros(model.batch_slots, np.int32)
        self._btab = np.zeros(
            (model.batch_slots, self.pool.max_blocks_per_seq), np.int32)
        self._next_seq_id = 0
        self._seed = itertools.count(int(seed) + 1)
        self._close_timeout_s = float(close_timeout_s)
        # fired (with the exception) when the worker dies on a fault —
        # NOT on a clean close.  The replica supervisor's death signal.
        self._on_death = on_death
        # graceful drain (autoscaler scale-down / SIGTERM grace): set by
        # drain(); new submissions are refused, everything already
        # accepted runs to completion, then the worker exits cleanly
        # and fires _on_drained exactly once
        self._draining = False
        self._on_drained = None
        self.batches_run = 0       # decode steps executed
        self.admitted = 0          # requests given a slot
        self.queue_wait_s_sum = 0.0  # their submit-to-admit waits, summed
        self.requests_done = 0
        self.tokens_generated = 0
        self.step_failures = 0
        self._worker = threading.Thread(target=self._loop, daemon=True)
        self._worker.start()

    @classmethod
    def from_trained(cls, ff_train, batch_slots: int = 8,
                     page_size: int = 16,
                     num_blocks: Optional[int] = None, devices=None,
                     eos_id: int = -1, registry=None,
                     seed: int = 0, prefill_chunk: int = 0,
                     prefix_cache: bool = True,
                     paged_kernel: str = "auto",
                     check_invariants: bool = False,
                     tp: int = 1, spec_decode: str = "off",
                     spec_k: int = 4, draft_ff=None,
                     draft_num_blocks: Optional[int] = None,
                     ) -> "ContinuousScheduler":
        # the draft twin (--spec-decode draft) is its own single-chip
        # paged engine over the smaller trained GPT: same slot count
        # and page size as the target (draft rows mirror engine slots
        # 1:1), no prefix cache or chunking of its own — catch-up IS
        # its prefill
        draft_model = None
        if spec_decode == "draft" and draft_ff is not None:
            draft_model = PagedKVDecodeModel(
                draft_ff, batch_slots=batch_slots, page_size=page_size,
                num_blocks=draft_num_blocks, devices=devices,
                paged_kernel=paged_kernel)
        model = PagedKVDecodeModel(ff_train, batch_slots=batch_slots,
                                   page_size=page_size,
                                   num_blocks=num_blocks,
                                   devices=devices,
                                   prefill_chunk=prefill_chunk,
                                   prefix_cache=prefix_cache,
                                   paged_kernel=paged_kernel, tp=tp,
                                   spec_decode=spec_decode,
                                   spec_k=spec_k,
                                   draft_model=draft_model)
        return cls(model, eos_id=eos_id, registry=registry, seed=seed,
                   check_invariants=check_invariants)

    # -- client API -----------------------------------------------------
    def generate(self, prompt: Sequence[int], max_new_tokens: int = 16,
                 temperature: float = 0.0,
                 timeout: Optional[float] = 60.0) -> List[int]:
        return self.generate_async(
            prompt, max_new_tokens, temperature).wait(timeout)

    def generate_async(self, prompt, max_new_tokens: int = 16,
                       temperature: float = 0.0,
                       on_done=None, trace=None, seed=None,
                       resume=None) -> _PendingSeq:
        if self._stop.is_set():
            raise RuntimeError("ContinuousScheduler is closed")
        if self._draining:
            # the drain cutoff: everything accepted BEFORE drain() runs
            # to completion; nothing new boards a leaving engine
            raise RuntimeError("ContinuousScheduler is draining")
        # validate HERE so a bad request fails alone (the batcher
        # convention); continuous mode has no same-temperature
        # restriction — sampling is host-side per row.  on_done rides
        # the handle from birth, so a completion can never race the
        # caller attaching it.  `seed` pins the sampling RNG (the
        # front mints one per request so a resubmission on ANY replica
        # samples identically); None keeps the per-engine counter.
        # `resume` (handoff.ResumeRecord) continues a paused/recovered
        # mid-decode generation: its generated tokens replay as prompt.
        p = _PendingSeq(prompt, max_new_tokens, temperature,
                        next(self._seed) if seed is None else int(seed),
                        on_done=on_done, trace=trace, resume=resume)
        if not 1 <= len(p.prompt) < self.model.max_seq:
            raise ValueError(
                f"prompt length {len(p.prompt)} outside [1, "
                f"{self.model.max_seq})")
        if p.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if resume is not None and not 1 <= len(
                resume.replay_tokens()) < self.model.max_seq:
            raise ValueError(
                f"resume replay length {len(resume.replay_tokens())} "
                f"outside [1, {self.model.max_seq})")
        self._queue.put(p)
        if self._stop.is_set():  # close() raced the put
            p.error = RuntimeError("ContinuousScheduler is closed")
            p._settle()
        return p

    def run_on_worker(self, fn, on_dropped=None) -> None:
        """Queue `fn` for the decode worker to run between steps — the
        only thread allowed to touch the model's donated state (KV
        block import lands here).  `fn` owns its own error handling;
        an exception it lets escape is treated like a step fault
        (fatal_to_engine propagates, anything else fails in-flight).
        If the engine closes/drains/dies before `fn` runs, `on_dropped`
        fires with the terminal error instead — a caller is never left
        waiting on a callable that will not run."""
        if self._stop.is_set():
            raise RuntimeError("ContinuousScheduler is closed")
        self._service.put((fn, on_dropped))
        if self._stop.is_set():  # close() raced the put
            self._drop_services(RuntimeError(
                "ContinuousScheduler is closed"))

    def _drop_services(self, err: Exception) -> None:
        while True:
            try:
                fn, on_dropped = self._service.get_nowait()
            except queue.Empty:
                return
            if on_dropped is not None:
                try:
                    on_dropped(err)
                except Exception:  # noqa: BLE001 — drains never mask
                    pass

    def _run_services(self) -> None:
        while True:
            try:
                fn, on_dropped = self._service.get_nowait()
            except queue.Empty:
                return
            if self._flights:
                # a service (pause, handoff, a KV import) sees every
                # row where the device left it: nothing in flight
                self.lookahead_drains["service"] += 1
                self._settle_flights()
            try:
                fn()
            except Exception as e:
                if getattr(e, "fatal_to_engine", False):
                    raise
                if on_dropped is not None:
                    try:
                        on_dropped(e)
                    except Exception:  # noqa: BLE001
                        pass

    @property
    def worker_alive(self) -> bool:
        return self._worker.is_alive()

    @property
    def draining(self) -> bool:
        return self._draining

    def drain(self, on_drained=None) -> None:
        """Stop ACCEPTING and run everything already accepted to
        completion (decode proceeds undisturbed — completions are
        token-identical to an engine that was never drained).  When the
        last live sequence retires and the arrival queue is empty, the
        worker exits cleanly and fires `on_drained` exactly once; the
        engine then refuses submissions like a closed one.

        Unlike close(), drain() never fails an in-flight request.  A
        wedged drain is still bounded by close(timeout_s=), which
        overrides it."""
        if self._stop.is_set() or self._draining:
            return
        self._on_drained = on_drained
        self._draining = True

    def request_handoff(self, *, remaining_over: int = 0,
                        max_sequences: int = 0,
                        export_kv: bool = True,
                        on_paused=None) -> None:
        """Pause live generations at the next step boundary and settle
        their handles with handoff.HandoffPaused — the resumable-
        migration entry point (docs/SERVING.md "Mid-decode handoff").
        Eligible rows have MORE than `remaining_over` tokens still to
        generate (a draining replica passes 0 to shed everything; a
        terminating front passes the count that still fits its
        deadline); `max_sequences` > 0 caps how many pause, largest
        remaining budget first (the rebalance trigger moves one whale
        at a time).  With `export_kv`, each paused row's written KV
        blocks — partial tail included — ride the settle so the front
        can stream them to a destination replica; the host resume
        record rides regardless, so every downstream fault still
        degrades to replay.  `on_paused(count)` fires on the worker
        after the sweep (0 if the engine died first).  Safe to call on
        a DRAINING engine: services still run between its final steps.
        """
        def service():
            rows = [(live.max_new - len(live.generated), i, live)
                    for i, live in enumerate(self._slots)
                    if live is not None]
            rows = [r for r in rows if r[0] > int(remaining_over)]
            rows.sort(key=lambda r: (-r[0], r[1]))
            if max_sequences and int(max_sequences) > 0:
                rows = rows[:int(max_sequences)]
            for _, i, live in rows:
                self._pause_slot(i, live, export_kv)
            if on_paused is not None:
                on_paused(len(rows))

        self.run_on_worker(
            service,
            on_dropped=((lambda e: on_paused(0))
                        if on_paused is not None else None))

    def latency_stats(self) -> Dict[str, float]:
        from .batcher import latency_percentiles

        return latency_percentiles(self._latencies, self._lat_lock)

    def ttft_stats(self) -> Dict[str, float]:
        from .batcher import latency_percentiles

        return latency_percentiles(self._ttfts, self._lat_lock)

    def cached_prefix_tokens(self, prompt) -> int:
        """Read-only probe: prompt tokens the prefix cache would serve
        right now.  Admission control discounts them — cached tokens
        cost zero prefill steps (serving/front.py)."""
        return self.pool.cached_prefix_tokens(
            [int(t) for t in prompt])

    def stats(self) -> Dict:
        live = [s for s in self._slots if s is not None]
        return {
            "mode": "continuous",
            "draining": self._draining,
            "steps": self.batches_run,
            "prefill_steps": self.prefill_steps,
            "prefill_chunk": self._chunk,
            "prefill_passes": self._passes,
            "pass_decode_tokens": self.pass_decode_tokens,
            "dispatches_ahead": self.dispatches_ahead,
            "lookahead_drains": dict(self.lookahead_drains),
            "overrun_tokens": self.overrun_tokens,
            "requests_done": self.requests_done,
            "tokens_generated": self.tokens_generated,
            "step_failures": self.step_failures,
            "step_ms_ewma": round(self.step_ms_ewma, 4),
            "queue_depth": self._queue.qsize() + len(self._waiting),
            "admitted": self.admitted,
            "queue_wait_s_sum": round(self.queue_wait_s_sum, 6),
            "live_sequences": len(live),
            "kv_pool": {
                "page_size": self.pool.page_size,
                "usable_blocks": self.pool.usable_blocks,
                "used_blocks": self.pool.used_blocks,
                "reserved_blocks": self.pool.reserved_blocks,
                "peak_used_blocks": self.pool.peak_used,
                "occupancy": round(self.pool.occupancy(), 4),
                "fragmentation": round(self.pool.fragmentation(), 4),
                # all layers' pools: 576 values a layer for a latent
                # cache, 2 x heads x head_dim for keys and values
                "bytes_per_token":
                    self._kv_block_bytes // self.pool.page_size,
            },
            "prefix_cache": self.pool.prefix_stats(),
            "speculative": {
                "mode": self._spec,
                "k_max": self._spec_k if self._spec != "off" else 0,
                "k_current": (self._adaptive.k
                              if self._proposer is not None else 0),
                "rounds": self.spec_rounds,
                "fallback_rounds": self.spec_fallback_rounds,
                "proposed": self.spec_proposed,
                "accepted": self.spec_accepted,
                "accept_rate": round(
                    self.spec_accepted / self.spec_proposed, 4)
                if self.spec_proposed else 0.0,
                "accepted_per_round": round(
                    self.spec_accepted / self.spec_rounds, 4)
                if self.spec_rounds else 0.0,
                "verify_faults": self.spec_verify_faults,
                "degraded": self._spec_broken,
                "proposer": (self._proposer.stats()
                             if self._proposer is not None else {}),
            },
            "tp": {
                "degree": int(getattr(self.model, "tp", 1)),
                "mesh_shape": dict(getattr(self.model, "mesh_shape",
                                           {}) or {}),
                "kv_block_bytes": self._kv_block_bytes,
                "kv_block_bytes_per_chip": int(getattr(
                    self.model, "kv_block_bytes_per_chip",
                    self._kv_block_bytes)),
                "kv_pool_bytes_per_chip": int(getattr(
                    self.model, "kv_block_bytes_per_chip",
                    self._kv_block_bytes))
                * int(getattr(self.model, "num_blocks", 0)),
            },
            "paged_kernel": {
                "formulation": self._paged_kernel,
                "blocks_read": self.kernel_blocks_read,
                "dense_blocks_equiv": self.kernel_dense_blocks,
                "bytes_read":
                    self.kernel_blocks_read * self._kv_block_bytes,
                "dense_bytes_avoided":
                    max(0, self.kernel_dense_blocks
                        - self.kernel_blocks_read)
                    * self._kv_block_bytes,
            },
            "ttft": self.ttft_stats(),
            "latency": self.latency_stats(),
            **({"moe": dict(self.moe_totals)}
               if self.moe_totals is not None else {}),
            **({"loop": dict(self.loop_totals,
                             exit_mass=list(self.loop_totals["exit_mass"]),
                             **getattr(self.model, "loop", {}))}
               if self.loop_totals is not None else {}),
            **{name: dict(totals, **self._groups[name].geometry)
               for name, totals in self.group_totals.items()},
        }

    def close(self, timeout_s: Optional[float] = None):
        """Stop the loop and drain: in-flight sequences fail with a
        closed error (their blocks are freed), queued requests fail
        without hanging out their timeout.  The worker owns _slots and
        _waiting, so the full drain runs EITHER on the worker's way out
        of _loop OR here once the worker is confirmed dead — never
        concurrently; the thread-safe arrival queue is always drained.

        The wait for the worker is BOUNDED (`timeout_s`, defaulting to
        the constructor's close_timeout_s): a worker wedged inside a
        hung device dispatch cannot hold shutdown hostage — the drain
        proceeds without it."""
        self._stop.set()
        if timeout_s is None:
            timeout_s = self._close_timeout_s
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline and self._worker.is_alive():
            self._worker.join(timeout=min(0.2, max(0.0, timeout_s)))
        err = RuntimeError("ContinuousScheduler closed")
        # Drain even if the worker outlived the deadline (a device step
        # wedged mid-dispatch): waiters must not sit out their full
        # wait() timeouts against a hung engine.  _drain is defensive
        # about double-retires, and a worker that later un-wedges finds
        # _stop set, treats its emptied slots as idle, and exits
        # through its own (now no-op) drain.
        self._drain(err)
        while True:  # late enqueues that raced the stop flag
            try:
                p = self._queue.get_nowait()
            except queue.Empty:
                break
            p.error = err
            p._settle()

    # -- worker ---------------------------------------------------------
    def _free_slot_buffers(self, slot: int):
        """Point a vacated slot's step buffers back at scratch."""
        self._btab[slot] = 0
        self._tokens[slot] = 0
        self._slens[slot] = 0

    def _resume_record_of(self, live: _Live):
        """Host-side resume record for a live row — built on the
        failure paths too: the tokens live on the host, so a dead
        device cannot tear them, and the front's requeue replays
        prompt+generated instead of regenerating from scratch."""
        from .handoff import ResumeRecord

        try:
            return ResumeRecord(
                live.req.prompt, live.generated, live.pos,
                live.req.seed, live.req.temperature,
                rng_state=(live.rng.get_state()
                           if live.rng is not None else None),
                page_size=self.pool.page_size)
        except Exception:  # noqa: BLE001 — recovery metadata must
            return None    # never mask the original failure

    def _pause_slot(self, slot: int, live: _Live,
                    export_kv: bool = True) -> None:
        """Worker-side pause: snapshot the row (and optionally its
        written KV blocks, partial tail included), retire it, and
        settle the handle with HandoffPaused.  Runs only between
        steps, so the exported bytes are a consistent prefix of the
        generation."""
        from .handoff import HandoffPaused

        req = live.req
        written = (req.prompt + live.generated)[:live.pos]
        rec = self._resume_record_of(live)
        pages = arrays = None
        exporter = getattr(self.model, "export_block", None)
        if export_kv and exporter is not None:
            try:
                blocks, pages = self.pool.export_live(
                    live.seq_id, written)
                arrays = [exporter(b) for b in blocks]
            except Exception as e:
                if getattr(e, "fatal_to_engine", False):
                    raise
                pages = arrays = None  # replay-only resume
        if self._proposer is not None:
            self._proposer.release(slot)
        # the written prefix keys the retired blocks into the prefix
        # cache: a re-admit on THIS replica is a hit too
        self.pool.retire(live.seq_id, tokens=written)
        self._slots[slot] = None
        self._free_slot_buffers(slot)
        if live.tspan is not None:
            live.tspan.span.end(paused=True)
            live.tspan = None
        if self.registry is not None:
            self.registry.counter("serving/handoff_paused").inc()
        req.error = HandoffPaused(rec, pages=pages, arrays=arrays,
                                  page_size=self.pool.page_size)
        req._settle()

    def _drain(self, err: Exception):
        """Fail every queued/waiting/live request (close or fault).
        Runs on the worker's way out of _loop AND from close() — which
        overlap only when close() gave up on a wedged worker, so
        retires tolerate the other drain having won the race."""
        self._drop_flights()
        for i, s in enumerate(self._slots):
            if s is not None:
                try:
                    self.pool.retire(s.seq_id)
                except KeyError:
                    pass  # the racing drain already freed it
                if s.generated:
                    # death recovery: the front's requeue resumes from
                    # this instead of regenerating from scratch
                    s.req.resume_out = self._resume_record_of(s)
                s.req.error = err
                s.req._settle()
                self._free_slot_buffers(i)
        self._slots = [None] * self.model.batch_slots
        while self._waiting:
            p = self._waiting.popleft()
            p.error = err
            p._settle()
        while True:
            try:
                p = self._queue.get_nowait()
            except queue.Empty:
                break
            p.error = err
            p._settle()
        self._drop_services(err)

    def _admit(self):
        """Pull arrivals, then admit FIFO into free slots while the
        pool can GUARANTEE completion.  Strict FIFO: a head-of-line
        request that doesn't fit blocks later (smaller) ones — no
        starvation, predictable SLO.

        Admission consults the prefix cache: the longest indexed
        block-aligned prefix of the prompt is mapped straight onto the
        shared physical blocks (those tokens never prefill).  A
        FULL-prompt hit still re-runs the last prompt token for its
        logits — its write position lands in the shared tail block, so
        the pool copy-on-writes it here, BEFORE any step runs."""
        while True:
            try:
                self._waiting.append(self._queue.get_nowait())
            except queue.Empty:
                break
        free = [i for i, s in enumerate(self._slots) if s is None]
        while free and self._waiting:
            req = self._waiting[0]
            plen = len(req.prompt)
            rs = req.resume
            # resume admission: the previously generated tokens replay
            # as prompt (`feed`), so the whole machinery below — cache
            # hit, chunked prefill, budget clamp — continues the
            # original generation token-identically
            feed = req.prompt if rs is None else rs.replay_tokens()
            flen = len(feed)
            max_new = min(req.max_new_tokens, self.model.max_seq - plen)
            if rs is not None and len(rs.generated) >= max_new:
                # the pause raced the budget edge: nothing left to
                # decode — settle the finished completion directly
                self._waiting.popleft()
                req.result = req.prompt + list(rs.generated)
                req.n_generated = len(rs.generated)
                req.t_done = time.monotonic()
                req._settle()
                continue
            sid = self._next_seq_id
            try:
                admitted = self.pool.try_admit(
                    sid, plen + max_new, prompt=feed,
                    cow_ok=self._can_cow)
            except ValueError as e:
                # can never fit any pool state (table width): fail it
                # alone instead of wedging the FIFO head forever
                self._waiting.popleft()
                req.error = e
                req._settle()
                continue
            if not admitted:
                if self.pool.reserved_blocks == 0:
                    # empty pool and still no room: this pool can never
                    # serve the request — fail instead of starving
                    self._waiting.popleft()
                    req.error = ValueError(
                        f"request needs {self.pool.blocks_for(plen + max_new)} "
                        f"KV blocks but the pool only has "
                        f"{self.pool.usable_blocks}")
                    req._settle()
                    continue
                if self.registry is not None:
                    self.registry.counter(
                        "serving/admissions_deferred").inc()
                break
            self._waiting.popleft()
            self._next_seq_id += 1
            self.admitted += 1
            self.queue_wait_s_sum += time.monotonic() - req.t_submit
            hit = self.pool.admit_hit_tokens(sid)
            if rs is not None:
                # a live handoff may have shipped the partial tail
                # block's bytes: land them when the cache hit covers
                # every full page, so the tail never replays either
                hit = self._import_resume_tail(sid, rs, hit)
            # a full-prompt hit still feeds the LAST prompt token (its
            # logits seed sampling); everything before `start` is
            # served from shared blocks
            start = min(hit, flen - 1)
            req.prefix_hit_tokens = hit
            if hit and self.registry is not None:
                self.registry.counter("serving/prefix_hits").inc()
                self.registry.counter(
                    "serving/prefix_hit_tokens").inc(hit)
            cow = self.pool.ensure_writable(sid, start)
            if cow is not None:
                try:
                    self.model.copy_block(*cow)
                except Exception as e:
                    # the COW device copy is a dispatch like any step:
                    # fail the admitting request alone on a transient
                    # fault; a fatal (hung copy, device loss) drains
                    # the engine through the normal death path
                    self.pool.retire(sid)
                    req.error = e
                    req._settle()
                    if getattr(e, "fatal_to_engine", False):
                        raise
                    continue
                if self.registry is not None:
                    self.registry.counter("serving/kv_cow_copies").inc()
            live = _Live(
                req, sid, max_new, start=start,
                feed=feed if rs is not None else None,
                generated=rs.generated if rs is not None else None,
                rng_state=rs.rng_state if rs is not None else None)
            if rs is not None and self.registry is not None:
                self.registry.counter("serving/handoff_resumed").inc()
            if self._reqtrace is not None and req.trace is not None:
                live.tspan = _LiveTrace(req.trace, self._trace_pid,
                                        hit, plen)
            slot = free.pop(0)
            self._slots[slot] = live
            if self._resets:
                # the slot's last tenant left its state behind
                self.model.reset_slot_state(slot)
            # first private block (or a no-op after a full hit):
            # allocate-on-admit
            self.pool.extend(sid, start + 1, written=start)
            self._btab[slot] = self.pool.table_row(sid)
            self._tokens[slot] = live.next_token
            self._slens[slot] = start

    def _import_resume_tail(self, sid: int, rs, hit: int) -> int:
        """Land a resumed sequence's migrated partial-tail KV block.
        Only when the adopted full pages already cover the hit (the
        tail chains through them — importing it over a shorter hit
        would leave a hole no replay fills) and the written watermark
        actually ends sub-page.  Returns the new effective hit; any
        failure rolls the table back to the block-aligned hit and the
        tail replays through chunked prefill instead."""
        page = self.pool.page_size
        tail_len = rs.written % page
        if (rs.kv_tail is None or not tail_len
                or rs.page_size != page
                or hit != (rs.written // page) * page
                or getattr(self.model, "import_block", None) is None):
            return hit
        try:
            self.pool.extend(sid, rs.written, written=rs.written)
            blk = self.pool.table_of(sid)[-1]
            self.model.import_block(blk, rs.kv_tail)
            if self.registry is not None:
                self.registry.counter(
                    "serving/handoff_tail_imports").inc()
            return rs.written
        except Exception as e:
            if getattr(e, "fatal_to_engine", False):
                raise
            try:
                self.pool.rollback(sid, (rs.written // page) * page)
            except Exception:  # noqa: BLE001 — fall back to replay
                pass
            return hit

    def _loop(self):
        """Thread body: run the decode loop, then drain no matter how
        it exited — a crash fails pending requests immediately instead
        of parking them for their full wait timeout (and leaves
        worker_alive False for the /v2/health degraded check).  A
        fatal exit additionally fires on_death so a supervisor
        (serving/replica.py) learns of the death without polling."""
        err: Exception = RuntimeError("ContinuousScheduler closed")
        fatal: Optional[Exception] = None
        try:
            self._decode_loop()
        except Exception as e:  # scheduler bug / pool invariant breach
            err = fatal = e
        drained = (fatal is None and self._draining
                   and not self._stop.is_set())
        if drained:
            # clean drain completion: flip the closed flag so late
            # submissions refuse, then notify AFTER the residual drain
            # below settles any racer that slipped into the queue
            self._stop.set()
        if fatal is not None:
            # the engine is dead for NEW submissions too: flip the
            # closed flag and notify the supervisor BEFORE failing the
            # pending requests, so a front's requeue callbacks already
            # see this replica as down and route elsewhere (otherwise
            # a requeue can race back onto this dead engine and park
            # until its client timeout)
            self._stop.set()
            if self._on_death is not None:
                try:
                    self._on_death(fatal)
                except Exception:  # noqa: BLE001 — the worker is
                    pass           # exiting; never mask the drain
        self._drain(err)
        if drained and self._on_drained is not None:
            try:
                self._on_drained()
            except Exception:  # noqa: BLE001 — the worker is exiting;
                pass           # a retire hook must never mask that

    def _fail_inflight(self, e: Exception):
        """Transient step fault: fail in-flight only; queued requests
        survive on the same engine."""
        self.step_failures += 1
        if self.registry is not None:
            self.registry.counter("serving/step_failures").inc()
        if self._flights:
            self.lookahead_drains["fault"] += 1
            self._drop_flights()
        for i, live in enumerate(self._slots):
            if live is None:
                continue
            self.pool.retire(live.seq_id)
            if live.generated:
                # the tokens survive on the host: a front retry
                # replays them instead of regenerating from scratch
                live.req.resume_out = self._resume_record_of(live)
            live.req.error = e
            live.req._settle()
            self._slots[i] = None
            self._free_slot_buffers(i)
        # a step that died mid-execution may have consumed the
        # donated state buffers — rebuild before the next admit
        reset = getattr(self.model, "reset", None)
        if reset is not None:
            reset()
            # the rebuild ZEROED the device pools: every cached
            # prefix block's bytes are garbage now — drop the index
            # so no future admission maps onto them
            self.pool.invalidate_prefix_cache()
        # drafter state describes sequences that no longer exist (and
        # a draft twin's pools may be mid-sequence): clear it so
        # speculation resumes from scratch with the fresh engine
        if self._proposer is not None:
            self._proposer.reset()

    def _share_dispatch(self, dispatch, lives) -> None:
        """Request tracing: a finished dispatch span that a sampled
        request rode is recorded once with the request tracer, as the
        shared span those requests' phase spans reference by id."""
        if self._reqtrace is not None and any(
                live is not None and live.tspan is not None
                for live in lives):
            self._reqtrace.shared_span(dispatch, self._trace_pid)

    @contextlib.contextmanager
    def _sample_span(self):
        """`sched.sample` around the per-row work after a dispatch,
        carrying what it emitted: tokens and finished requests."""
        with span("sched.sample") as sp:
            t0, d0 = self.tokens_generated, self.requests_done
            yield
            sp.set(tokens=self.tokens_generated - t0,
                   finished=self.requests_done - d0)

    def _note_step_time(self, dt_s: float) -> None:
        """EWMA of per-dispatch wall time (decode + chunked-prefill).
        The disagg dispatcher prices a re-prefill as chunked steps x
        this measurement (serving/disagg.py)."""
        ms = dt_s * 1e3
        self.step_ms_ewma = (ms if self.step_ms_ewma == 0.0
                             else 0.9 * self.step_ms_ewma + 0.1 * ms)

    def _kv_reads(self, seq_lens, counts, steps: int = 1) -> Dict:
        """The `kv_blocks_read` / `kv_blocks_dense` args of a dispatch
        span: physical KV blocks the dispatch's attention reads
        against what the dense [slots, decode_max_seq] view holds, for
        a program that reads row i's prefix `counts[i]` times, at
        positions `seq_lens[i]` on, in `steps` passes that each build
        the view.  The gather formulation reads the whole view
        whatever is live;
        `kv_blocks_live` is what an in-place read would touch, under
        either formulation."""
        from ..ops.pallas.paged_attention import scan_blocks_read

        tw = self.pool.max_blocks_per_seq
        dense = self.model.batch_slots * tw * steps
        live = scan_blocks_read(seq_lens, counts, self.pool.page_size, tw)
        return {"kv_blocks_read": (live if self._paged_kernel == "pallas"
                                   else dense),
                "kv_blocks_dense": dense, "kv_blocks_live": live}

    def _note_counts(self, dispatch, program: str, positions, counts,
                     chunk: int) -> None:
        """What the model's mixers count of a dispatch that advances
        row i over `positions[i] .. + counts[i] - 1` in a program of
        `chunk` tokens a row (`model.dispatch_counts`, {group: args}):
        onto its span, and summed by program into `group_totals`."""
        if not self.group_totals:
            return
        rows = self.model.dispatch_counts(positions, counts, chunk)
        for group, args in rows.items():
            dispatch.set(**args)
            t = self.group_totals[group]
            t[f"{program}_dispatches"] += 1
            for k, v in args.items():
                t[f"{program}_{k}"] = t.get(f"{program}_{k}", 0) + v

    def _note_moe(self, dispatch, program: str) -> None:
        """The `moe_*` args of a dispatch whose logits were fetched
        (`model.moe_last`: the routed layers' counts of that dispatch,
        over every slot's row from the decode step, over the real tokens
        alone from the one-pass prefill) and their sums by program,
        `real_min` / `real_max` folded by `_MOE_FOLD`."""
        moe = getattr(self.model, "moe_last", None)
        if moe is None:
            return
        dispatch.set(**{f"moe_{k}": v for k, v in moe.items()})
        if self.moe_totals is None:
            self.moe_totals = {"dispatches": 0, "prefill_dispatches": 0}
        totals, prefix = self.moe_totals, "" if program == "decode" \
            else "prefill_"
        totals[f"{prefix}dispatches"] += 1
        for k, v in moe.items():
            name = prefix + k
            totals[name] = (_MOE_FOLD.get(k, operator.add)(totals[name], v)
                            if name in totals else v)

    def _note_loop(self, dispatch, program: str, passes: int) -> None:
        """The `loop_steps` arg of a dispatch span (weight passes: the
        program's own passes times the region's), summed into
        `loop_totals`."""
        t = self.loop_totals
        steps = passes * self._loop_steps
        dispatch.set(loop_steps=steps)
        t[f"{program}_dispatches"] += 1
        t[f"{program}_weight_passes"] += steps

    def _note_fetched(self, flight: _Flight) -> None:
        """What only the fetch of a sampling dispatch brings, onto the
        record of THAT dispatch (its span may have ended a dispatch
        ago: `span.set` then reaches the ring's record alone): the
        routed layers' `moe_*` counts, and of a model with an exit gate
        `exit_mass_<t>`, the exit pdf after pass t, mean over the
        dispatch's rows (`model.exit_last`), summed into
        `loop_totals`."""
        dispatch = flight.dispatch
        self._note_moe(dispatch, flight.program)
        pdf = getattr(self.model, "exit_last", None)
        if pdf is None or not self._loop_steps or not flight.rows:
            return
        live = [i for i, *_ in flight.rows]
        mass = np.asarray(pdf, np.float64)[live].sum(axis=0)
        dispatch.set(**{f"exit_mass_{k}": float(v) / len(live)
                        for k, v in enumerate(mass)})
        t = self.loop_totals
        if not t["exit_mass"]:
            t["exit_mass"] = [0.0] * len(mass)
        t["exit_mass"] = [a + float(b) for a, b in zip(t["exit_mass"], mass)]
        t["exit_rows"] += len(live)

    def _note_kernel_reads(self, reads: Dict) -> None:
        """Sum one completed dispatch's `_kv_reads` into the fused
        kernel's counters (stats()["paged_kernel"], obs:
        serving/paged_kernel_*); they stay zero under the gather."""
        if self._paged_kernel != "pallas":
            return
        blocks = reads["kv_blocks_read"]
        dense_blocks = reads["kv_blocks_dense"]
        self.kernel_blocks_read += blocks
        self.kernel_dense_blocks += dense_blocks
        if self.registry is None:
            return
        reg = self.registry
        reg.counter("serving/paged_kernel_blocks_read").inc(blocks)
        if self._kv_block_bytes:
            reg.counter("serving/paged_kernel_bytes_read").inc(
                blocks * self._kv_block_bytes)
            reg.counter("serving/paged_dense_bytes_avoided").inc(
                max(0, dense_blocks - blocks) * self._kv_block_bytes)

    def _prefill_chunk_step(self, plan, why: Optional[str]) -> bool:
        """One [slots, C] chunked-prefill dispatch advancing the rows of
        `plan` (`plan_chunk_rows`) by their tokens.

        The scan (GPT) returns no logits: its rows are the feeding rows,
        never past plen-1 (the last prompt token runs through the
        decode program, whose logits seed sampling), and decode-phase
        rows ride along pointed at scratch (all-zero table row,
        position 0).  The one-pass program returns every row's logits
        at its last real token, so every live row is a row of it at its
        own position and table: a feeding row with up to C tokens of its
        feed, the last included, a row past its prompt with its pending
        token at column 0 and one token fed (the device's own id where
        the host has not seen it yet: `take_prev`); the rows whose feed
        is exhausted are then sampled from the pass and the iteration
        ends there.  `why` None leaves that pass in flight (`_fly`);
        else it is fetched here, for that reason (`_synchronous`).

        Either way a row's trailing pad columns write garbage only at
        positions PAST its own frontier — overwritten by its later real
        writes before any query can attend them, or absorbed by scratch
        via the table padding — the same argument that makes idle-slot
        writes safe; per-slot state advances by the row's real tokens
        alone (`row_tokens`).  Returns False after a transient fault
        (already handled); fatal faults propagate."""
        C, passes, sampled = self._chunk, self._passes, self._pass_samples
        with span("sched.prefill.prepare"):
            tok = np.zeros((self.model.batch_slots, C), np.int32)
            slen = np.zeros(self.model.batch_slots, np.int32)
            fed = np.zeros(self.model.batch_slots, np.int32)
            take = np.zeros(self.model.batch_slots, np.int32)
            btab = np.zeros_like(self._btab)
            real = ends = 0  # tokens really advanced; rows to sample
            for i, live, n in plan:
                if self.pool.extend(live.seq_id, live.pos + n,
                                    written=live.pos):
                    self._btab[i] = self.pool.table_row(live.seq_id)
                tok[i, :n] = (live.feed[live.pos:live.pos + n]
                              if live.pos < len(live.feed)
                              else live.next_token)
                take[i] = live.unsettled > 0
                slen[i] = live.pos
                fed[i] = n
                btab[i] = self._btab[i]
                real += n
                ends += live.pos + n >= len(live.feed)
        rows = [(i, live, live.pos, n) for i, live, n in plan]
        logits = landed = None
        try:
            with span("sched.prefill.dispatch", rows=len(plan),
                      tokens=real, passes=passes,
                      capacity=self.model.batch_slots * C,
                      ahead=int(bool(self._flights)),
                      **({"decode_rows": ends,
                          "slots": self.model.batch_slots}
                         if sampled else {}),
                      ) as dispatch:
                reads = {}
                flight = _Flight("prefill", dispatch, None, rows, reads)

                def count():
                    # the dispatch's counters, taken while the program
                    # runs: after its enqueue, before any wait for it
                    self._note_counts(dispatch, "prefill", slen, fed, C)
                    # a plan row's prefix is read once a pass: by the
                    # scan at each of its C positions, by the one-pass
                    # program once, up to the chunk's last; the scan's
                    # riders sit on scratch and read nothing
                    counts = np.zeros_like(slen)
                    counts[[i for i, _, _ in plan]] = passes
                    first_read = slen + (C - passes)
                    reads.update(self._kv_reads(first_read, counts,
                                                steps=passes))
                    dispatch.set(**reads)
                    if self._loop_steps:
                        self._note_loop(dispatch, "prefill", passes)

                # (`row_tokens`: the scan's riders advance by 0 tokens,
                # the pass's rows by their real tokens)
                if not sampled:
                    self.model.prefill_step(
                        tok, slen, btab, *((fed,) if self._rstate else ()))
                    count()
                elif why is None:
                    flight.launched = self.model.launch_prefill(
                        tok, slen, btab, fed, take_prev=take)
                    count()
                    landed = self._fly(flight)
                else:
                    logits = self.model.prefill_step(
                        tok, slen, btab, fed, meanwhile=count)
                    # (the exit pdf and the routed layers' counts came
                    # with the logits)
                    self._note_fetched(flight)
        except Exception as e:
            if getattr(e, "fatal_to_engine", False):
                raise
            self._fail_inflight(e)
            return False
        if sampled:
            self._sampled(flight, why, logits, landed)
            return True
        self._advance_rows(rows)
        self._share_dispatch(dispatch, [live for _, live, _ in plan])
        self._note_step_time(dispatch.t_end - dispatch.t_start)
        self.prefill_steps += 1
        self._note_kernel_reads(reads)
        for _, live, _ in plan:
            if live.tspan is not None:
                live.tspan.ref_chunk(dispatch)
        if self._check_invariants:
            self.pool.check_invariants()
        return True

    def _spec_proposals(self):
        """Ask the proposer for this round's drafts.  Eligible rows are
        GREEDY decode-phase slots with >= 2 tokens of budget left (a
        draft only helps if at least one extra token may be emitted);
        mid-prefill and sampled rows ride the verify round with
        count 1.  Per-row draft length is capped by the adaptive-k
        controller and the row's remaining budget, so fed positions
        never pass prompt+max_new (<= max_seq by admission)."""
        k = min(self._adaptive.k, self._spec_k)
        contexts: Dict[int, List[int]] = {}
        limits: Dict[int, int] = {}
        caps: Dict[int, int] = {}
        for i, live in enumerate(self._slots):
            if live is None or live.req.temperature > 0.0:
                continue
            plen = len(live.req.prompt)
            if live.pos < len(live.feed) - 1:
                continue  # still prefilling (or replaying a resume)
            rem = live.max_new - len(live.generated)
            if rem < 2:
                continue
            contexts[i] = live.req.prompt + live.generated
            limits[i] = min(plen + live.max_new + self._spec_k,
                            self.model.max_seq)
            caps[i] = min(k, rem - 1)
        if not contexts:
            return None
        try:
            props = self._proposer.propose(contexts, k, limits)
        except Exception:  # noqa: BLE001 — a proposer bug degrades to
            self._spec_broken = True   # plain decode, never kills the
            return None                # engine
        out = {}
        for i, d in (props or {}).items():
            if i in caps and d:
                d = [int(t) for t in d[:caps[i]]]
                if d:
                    out[i] = d
        return out or None

    def _spec_round(self, props) -> bool:
        """ONE speculative verify dispatch advancing EVERY live row:
        row i feeds its pending next_token followed by its draft
        tokens (counts[i] total; 1 for rows without proposals) and
        gets per-position logits back.  Greedy rows accept the longest
        prefix of drafts matching the model's own argmax chain plus
        the first corrected token; the KV pool rolls back past the
        accept point (un-registering prefix-index entries over
        rejected positions and COWing a kept shared tail).  Per-step
        logits are bit-identical to seq-1 stepping, so acceptance is
        token-identical to plain decode BY CONSTRUCTION.

        Returns True when the round ran; False after a verify fault —
        speculation is disabled (sticky for this engine instance) and
        in-flight slots continue on the plain decode path, where a
        consumed state surfaces as an ordinary step fault."""
        C = self.model.verify_chunk
        bs = self.model.batch_slots
        tok = np.zeros((bs, C), np.int32)
        counts = np.zeros(bs, np.int32)
        for i, live in enumerate(self._slots):
            if live is None:
                continue
            tok[i, 0] = live.next_token
            counts[i] = 1
            d = props.get(i)
            if d:
                m = 1 + len(d)
                tok[i, 1:m] = d
                counts[i] = m
                # the drafts' blocks must exist BEFORE dispatch; the
                # admission reservation covers them (fed positions
                # stay under prompt+max_new)
                self.pool.extend(live.seq_id, live.pos + m,
                                 written=live.pos)
                self._btab[i] = self.pool.table_row(live.seq_id)
        # the verify program scans the seq-1 read over each row's fed
        # positions
        reads = self._kv_reads(self._slens, counts, steps=C)
        try:
            with span("sched.spec.verify.dispatch",
                      rows=int((counts > 0).sum()),
                      drafted=len(props), fed=int(counts.sum()),
                      **reads,
                      **self._proposer.trace_attrs()) as dispatch:
                logits = self.model.verify_step(
                    tok, self._slens, counts, self._btab)
        except Exception as e:
            if getattr(e, "fatal_to_engine", False):
                raise  # hung verify / device loss: drain-and-die
            # transient verify fault: DEGRADE, don't fail in-flight —
            # a pre-dispatch injection left the state intact and the
            # plain decode path resumes token-identically; a true
            # mid-dispatch death surfaces on the next plain step and
            # takes the normal _fail_inflight recovery
            self.spec_verify_faults += 1
            self._spec_broken = True
            if self._proposer is not None:
                self._proposer.reset()
            if self.registry is not None:
                self.registry.counter(
                    "serving/spec_verify_faults").inc()
            return False
        self._share_dispatch(dispatch, self._slots)
        self._note_step_time(dispatch.t_end - dispatch.t_start)
        self.batches_run += 1
        self.spec_rounds += 1
        if self._spec_t0 is None:
            self._spec_t0 = time.monotonic()
        self._note_kernel_reads(reads)
        with self._sample_span():
            self._accept_rows(logits, tok, counts, dispatch)
        if self.registry is not None:
            self.registry.counter("serving/spec_rounds").inc()
        return True

    def _accept_rows(self, logits, tok, counts, dispatch) -> None:
        """After a verify dispatch: per row, accept the longest prefix
        of its drafts that matches the model's own chain, roll the
        pool back past it, retire the finished."""
        now = time.monotonic()
        for i, live in enumerate(self._slots):
            if live is None:
                continue
            m = int(counts[i])
            if live.pos < len(live.feed) - 1:
                # mid-prefill row rode with its prompt token (m == 1):
                # identical to the plain decode path's prefill branch
                live.pos += 1
                self.pool.note_written(live.seq_id, live.pos)
                live.next_token = live.feed[live.pos]
                self._tokens[i] = live.next_token
                self._slens[i] = live.pos
                if live.tspan is not None:
                    live.tspan.ref_step(dispatch, spec=True)
                continue
            # decode-phase: walk the model's own token chain across
            # the fed positions — position j's output is valid iff
            # every fed token before it matched the chain
            out: List[int] = []
            for j in range(m):
                t = int(self._sample(logits[i, j], live))
                out.append(t)
                if self.eos_id >= 0 and t == self.eos_id:
                    break
                if j + 1 >= m or t != int(tok[i, j + 1]):
                    break
            emitted = len(out)
            proposed, accepted = m - 1, emitted - 1
            # watermark first (the dispatch really wrote all m
            # positions), then roll rejected positions back out —
            # freeing their blocks, un-registering their prefix-index
            # entries, and COWing a kept shared tail
            self.pool.note_written(live.seq_id, live.pos + m)
            new_pos = live.pos + emitted
            if m > emitted:
                cow = self.pool.rollback(live.seq_id, new_pos)
                # the table shrank (and its kept tail block may have
                # been COW-swapped): refresh the row BEFORE the next
                # dispatch can write through a stale block id
                self._btab[i] = self.pool.table_row(live.seq_id)
                if cow is not None:
                    try:
                        self.model.copy_block(*cow)
                    except Exception as e:
                        if getattr(e, "fatal_to_engine", False):
                            raise
                        # rollback's device COW failed: this row's KV
                        # is unsynced — fail the one request, like the
                        # admission COW path
                        self.pool.retire(live.seq_id)
                        if self._proposer is not None:
                            self._proposer.release(i)
                        live.req.error = e
                        live.req._settle()
                        self._slots[i] = None
                        self._free_slot_buffers(i)
                        continue
            live.pos = new_pos
            if proposed:
                self.spec_proposed += proposed
                self.spec_accepted += accepted
                live.req.spec_proposed += proposed
                live.req.spec_accepted += accepted
                self._adaptive.update(proposed, accepted)
                if self.registry is not None:
                    reg = self.registry
                    reg.counter("serving/spec_proposed").inc(proposed)
                    reg.counter("serving/spec_accepted").inc(accepted)
                    reg.histogram(
                        "serving/spec_accepted_per_round").observe(
                        accepted)
            if live.tspan is not None:
                live.tspan.ref_step(dispatch, spec=True)
            if not live.generated:
                live.req.t_first_token = now
                with self._lat_lock:
                    self._ttfts.append(now - live.req.t_submit)
                if self.registry is not None:
                    self.registry.histogram("serving/ttft_ms").observe(
                        (now - live.req.t_submit) * 1e3,
                        exemplar=(live.req.trace.trace_id
                                  if live.req.trace is not None
                                  else None))
                if live.tspan is not None:
                    live.tspan.to_decode()
            live.generated.extend(out)
            self.tokens_generated += emitted
            done = (len(live.generated) >= live.max_new
                    or (self.eos_id >= 0 and out[-1] == self.eos_id))
            if done:
                self._finish(i, live)
            else:
                live.next_token = out[-1]
                self._tokens[i] = out[-1]
                self._slens[i] = live.pos

    def _decode_loop(self):
        while not self._stop.is_set():
            with span("sched.iteration"):
                if not self._iteration():
                    return

    def _synchronous(self) -> Optional[str]:
        """Why this iteration's sampling dispatch has to be fetched
        before anything else is enqueued, or None: it may stay in
        flight while the next one is planned and enqueued behind it.
        Decided from what the loop can see, nothing switches it: a
        `family` whose programs leave no ids on the device (GPT's scan
        and step, a test's fake), a `spec` round to come (its proposer
        reads the tokens), a `service` waiting for the worker or a
        drain going on (they see every row where the device left it),
        a live row sampled at a `temperature` (its RNG stream draws on
        the host, from the logits)."""
        if not self._keeps_ids:
            return "family"
        if self._spec != "off" and not self._spec_broken:
            return "spec"
        if self._draining or not self._service.empty():
            return "service"
        if any(live is not None and live.req.temperature > 0.0
               for live in self._slots):
            return "temperature"
        return None

    def _iteration(self) -> bool:
        """One turn of the decode loop; False once a drain is complete.
        Every stretch of it runs under a named host span (children of
        `sched.iteration`; docs/OBSERVABILITY.md lists them), so device
        idle time can be laid at what the host was doing.

        With a dispatch in flight (`_flights`) the turn is: services,
        admission, the plan over the ADVANCED slots, this turn's
        dispatch enqueued behind the one in flight, and only then the
        wait for that one and the settling of its rows: the host's turn
        runs beside a pass, not between two."""
        page = self.pool.page_size
        with span("sched.services"):
            self._run_services()
        with span("sched.admit") as sp:
            n0, w0 = self.admitted, self.queue_wait_s_sum
            self._admit()
            sp.set(admitted=self.admitted - n0,
                   queue_depth=len(self._waiting),
                   wait_ms=round(1e3 * (self.queue_wait_s_sum - w0), 3))
        why = self._synchronous()
        slots = self._slots
        if self._flights:
            if why is None:
                slots = advanced_slots(slots)
            if why is not None or all(s is None for s in slots):
                # nothing may be enqueued behind the flight, or nothing
                # is left to enqueue: every live row takes its last
                # token from it (or an EOS ended it a dispatch ago)
                if self._settle_flights():
                    with span("sched.observe"):
                        self._observe_step()
                return True
        if all(s is None for s in slots):
            if (self._draining and not self._waiting
                    and self._queue.empty()):
                # drain complete: nothing live, nothing queued —
                # exit cleanly (a submit that raced past the
                # drain() cutoff sits in _queue and was admitted
                # above, so it is NOT abandoned here)
                return False
            # idle: park on the arrival queue instead of spinning
            with span("sched.idle_wait"):
                try:
                    self._waiting.append(self._queue.get(timeout=0.05))
                except queue.Empty:
                    pass
            return True
        if self._chunk:
            # while a row is feeding its prompt, chunked prefill first.
            # The scan: mid-prefill rows jump up to C positions, then
            # everyone (them included) takes the normal one-token
            # decode step below.  The one-pass program: every live row
            # is a row of that dispatch, the rows past their prompt
            # take their token from its logits, and the iteration is
            # that ONE dispatch
            plan = plan_chunk_rows(slots, self._chunk, self._pass_samples)
            if plan:
                ran = self._prefill_chunk_step(plan, why)
                if ran and self._pass_samples:
                    with span("sched.observe"):
                        self._observe_step()
                if not ran or self._pass_samples:
                    return True
        props = None
        with span("sched.decode.prepare"):
            decoding = feeding = 0
            for i, live in enumerate(slots):
                if live is None:
                    continue
                if live.pos + 1 < len(live.feed):
                    feeding += 1  # its logits will be ignored
                else:
                    decoding += 1
                # crossing a page boundary: allocate the next block
                # (admission reserved it, so this cannot fail)
                if live.pos and live.pos % page == 0:
                    self.pool.extend(live.seq_id, live.pos + 1)
                    self._btab[i] = self.pool.table_row(live.seq_id)
            if self._spec != "off" and not self._spec_broken:
                with span("sched.spec.propose"):
                    props = self._spec_proposals()
                if not props:
                    # no proposals anywhere: the plain [slots, 1]
                    # decode step below is the required empty-round
                    # fallback (and the whole path when spec is off)
                    self.spec_fallback_rounds += 1
        if props:
            # speculative round: every live row rides ONE verify
            # dispatch (drafted rows multi-token, everyone else
            # count-1)
            self.lookahead_drains[why] += 1
            if self._spec_round(props):
                with span("sched.observe"):
                    self._observe_step()
            return True
        rows = [(i, live, live.pos, 1) for i, live in enumerate(slots)
                if live is not None]
        logits = landed = None
        try:
            with span("sched.decode.dispatch", rows=decoding,
                      feeding=feeding, slots=self.model.batch_slots,
                      ahead=int(bool(self._flights))) as dispatch:
                reads = self._kv_reads(
                    self._slens, [live is not None for live in slots])
                flight = _Flight("decode", dispatch, None, rows, reads)
                dispatch.set(**reads)
                alive = ()  # per-slot state: which rows advance
                if self._rstate or self.group_totals:
                    advance = np.array([live is not None for live in slots],
                                       np.int32)
                    alive = (advance,) if self._rstate else ()
                    self._note_counts(dispatch, "decode", self._slens,
                                      advance, 1)
                if self._loop_steps:
                    self._note_loop(dispatch, "decode", 1)
                if why is None:
                    # (copies: the step buffers move on, at `_advance_rows`
                    # below, while the program may still read these)
                    flight.launched = self.model.launch_step(
                        self._tokens.copy(), self._slens.copy(),
                        self._btab.copy(), *alive,
                        take_prev=np.array(
                            [live is not None and live.unsettled > 0
                             for live in slots], np.int32))
                    landed = self._fly(flight)
                else:
                    logits = self.model.step(
                        self._tokens, self._slens, self._btab, *alive)
                    self._note_fetched(flight)
        except Exception as e:
            if getattr(e, "fatal_to_engine", False):
                # device-loss-style fault (hung dispatch, lost
                # device — serving/replica.py marks them): the
                # ENGINE is gone, not just this batch.  Propagate
                # so _loop drains everything and fires on_death —
                # the supervisor restarts the replica.
                raise
            self._fail_inflight(e)
            return True
        self._sampled(flight, why, logits, landed)
        with span("sched.observe"):
            self._observe_step()
        return True

    # -- a dispatch's bookkeeping, cut in two at what it needs to know ----
    def _advance_rows(self, rows) -> None:
        """ADVANCE: what a dispatch does to its `rows` [(slot, live,
        position before, tokens fed)] that its plan alone tells, done
        once it is enqueued: positions, the pool's written watermark
        (freshly written prompt blocks join the prefix index NOW, so a
        same-prefix arrival in the next admit already shares them: the
        program that reads them queues behind the one that writes
        them), a feeding row's next token, and for a row whose feed
        ends inside it the token it is owed (`unsettled`); one that
        reaches `max_new` with it is no row of the next dispatch, so
        its step buffers go back to scratch."""
        for i, live, start, n in rows:
            live.pos = start + n
            # keep the pool's written-token watermark current so
            # fragmentation never over-reports a mid-page tail
            self.pool.note_written(live.seq_id, live.pos)
            self._slens[i] = live.pos
            if live.pos < len(live.feed):
                # prefill: the next token is given, logits ignored
                live.next_token = live.feed[live.pos]
                self._tokens[i] = live.next_token
                continue
            live.unsettled += 1
            if len(live.generated) + live.unsettled >= live.max_new:
                self._free_slot_buffers(i)

    def _sampled(self, flight: _Flight, why: Optional[str], logits,
                 landed: Optional[tuple]) -> None:
        """Behind a sampling dispatch's span.  Left in flight (`why`
        None): the flight before it, which `_fly` waited for inside
        that span (`landed`), is settled.  Fetched at once, for the
        reason `why`: its own rows are advanced and settled, back to
        back."""
        dispatch = flight.dispatch
        if why is None:
            self.dispatches_ahead += dispatch.args["ahead"]
            if landed is not None:
                self._settle(*landed)
            return
        self.lookahead_drains[why] += 1
        self._advance_rows(flight.rows)
        self._settle(flight, logits, dispatch.t_end - dispatch.t_start)

    def _fly(self, flight: _Flight) -> Optional[tuple]:
        """Leave an enqueued dispatch in flight: advance its rows, and
        now that it is queued behind the one before it, wait for THAT
        one (`_land_first`; None where nothing was in flight).  Called
        inside the new dispatch's span, which so covers a pass's time
        on the device as a synchronous dispatch's does: what the loop
        spends outside its dispatch spans stays its own host work."""
        self._flights.append(flight)
        self._advance_rows(flight.rows)
        return self._land_first() if len(self._flights) > 1 else None

    def _land_first(self) -> tuple:
        """The wait for the oldest flight and its counts onto its
        record: (flight, logits, seconds it took), what `_settle`
        takes.  A fault leaves the flights as they are for
        `_fail_inflight` to drop."""
        flight = self._flights[0]
        logits = self.model.land(flight.launched,
                                 behind=len(self._flights) > 1)
        self._flights.popleft()
        flight.launched = None
        # the dispatch's own time, for the step-time EWMA: from its
        # enqueue, or the arrival of the flight before it if that came
        # later (it then queued behind it), to its logits' arrival
        now = time.monotonic()
        took = now - max(flight.dispatch.t_start, self._landed_at)
        self._landed_at = now
        self._note_fetched(flight)
        return flight, logits, took

    def _settle_flights(self) -> bool:
        """Settle everything in flight, oldest first (before a
        synchronous iteration, a service, the end of a stretch).  False
        after a transient fault (handled: the flights dropped, the
        in-flight requests failed); fatal faults propagate."""
        while self._flights:
            try:
                landed = self._land_first()
            except Exception as e:
                if getattr(e, "fatal_to_engine", False):
                    raise
                self._fail_inflight(e)
                return False
            self._settle(*landed)
        return True

    def _drop_flights(self) -> None:
        """Forget what is in flight (a fault, a close): its rows go
        back to where the host last saw them, newest flight first, so
        the resume records made from them are true."""
        while self._flights:
            for _, live, start, _ in self._flights.pop().rows:
                live.pos, live.unsettled = start, 0

    def _settle(self, flight: _Flight, logits, took_s: float) -> None:
        """A fetched sampling dispatch's counters, then SETTLE its
        rows."""
        self._share_dispatch(flight.dispatch,
                             [live for _, live, _, _ in flight.rows])
        self._note_step_time(took_s)
        self.batches_run += 1  # (a dispatch whose logits are sampled)
        self._note_kernel_reads(flight.reads)
        t0 = self.tokens_generated
        with self._sample_span():
            self._settle_rows(flight, logits)
        if flight.program == "prefill":
            self.prefill_steps += 1
            self.pass_decode_tokens += self.tokens_generated - t0

    def _settle_rows(self, flight: _Flight, logits) -> None:
        """SETTLE: what needs the fetched logits of a dispatch whose
        rows were advanced: the sampled token's value into `generated`,
        the first token's time, EOS, `_finish` (the pool's retire keys
        the blocks by the tokens), the request's trace spans.  A row
        that is no longer its slot's (an EOS ended it at the dispatch
        before, found out after this one was enqueued) drops its
        token: `overrun_tokens`; what that dispatch wrote for it lay
        inside the reservation it was admitted with."""
        now = time.monotonic()
        dispatch = flight.dispatch
        for i, live, start, n in flight.rows:
            sampled = start + n >= len(live.feed)
            if self._slots[i] is not live:
                self.overrun_tokens += sampled
                continue
            if live.tspan is not None:
                # a chunk of its prompt, or a step past it
                if flight.program == "prefill" and start + 1 < len(live.feed):
                    live.tspan.ref_chunk(dispatch)
                else:
                    live.tspan.ref_step(dispatch)
            if not sampled:
                continue
            tok = int(self._sample(logits[i], live))
            live.unsettled -= 1
            if not live.generated:
                live.req.t_first_token = now
                with self._lat_lock:
                    self._ttfts.append(now - live.req.t_submit)
                if self.registry is not None:
                    self.registry.histogram(
                        "serving/ttft_ms").observe(
                        (now - live.req.t_submit) * 1e3,
                        exemplar=(live.req.trace.trace_id
                                  if live.req.trace is not None
                                  else None))
                if live.tspan is not None:
                    live.tspan.to_decode()
            live.generated.append(tok)
            self.tokens_generated += 1
            done = (len(live.generated) >= live.max_new
                    or (self.eos_id >= 0 and tok == self.eos_id))
            if done:
                self._finish(i, live)
            else:
                live.next_token = tok
                self._tokens[i] = tok

    def _sample(self, row_logits: np.ndarray, live: _Live) -> int:
        if live.req.temperature <= 0.0:  # greedy hot path: one argmax
            return int(row_logits.argmax())
        from ..models.transformer import sample_next

        return sample_next(row_logits[None], live.req.temperature,
                           live.rng)[0]

    def _finish(self, slot: int, live: _Live):
        if self._proposer is not None:
            self._proposer.release(slot)
        # the written token prefix (everything fed; excludes the final
        # sampled token, whose k/v never landed) keys the retired
        # blocks into the prefix cache — a future prompt extending
        # this completion hits them
        self.pool.retire(
            live.seq_id,
            tokens=(live.req.prompt + live.generated)[:live.pos])
        self._slots[slot] = None
        self._free_slot_buffers(slot)
        req = live.req
        req.n_generated = len(live.generated)
        req.result = req.prompt + live.generated
        req.t_done = time.monotonic()
        if live.tspan is not None:
            live.tspan.finish(req)
            live.tspan = None
        with self._lat_lock:
            self._latencies.append(req.t_done - req.t_submit)
        self.requests_done += 1
        if self.registry is not None:
            reg = self.registry
            ex = req.trace.trace_id if req.trace is not None else None
            reg.counter("serving/requests_done").inc()
            reg.histogram("serving/request_latency_ms").observe(
                (req.t_done - req.t_submit) * 1e3, exemplar=ex)
            if req.n_generated > 1 and req.t_first_token is not None:
                reg.histogram("serving/per_token_ms").observe(
                    (req.t_done - req.t_first_token) * 1e3
                    / (req.n_generated - 1), exemplar=ex)
        req._settle()

    def _observe_step(self):
        if self._check_invariants:
            self.pool.check_invariants()
        if self.registry is None:
            return
        reg = self.registry
        live = [s for s in self._slots if s is not None]
        reg.counter("serving/steps").inc()
        reg.gauge("serving/queue_depth").set(
            self._queue.qsize() + len(self._waiting))
        reg.gauge("serving/live_sequences").set(len(live))
        reg.gauge("serving/kv_used_blocks").set(self.pool.used_blocks)
        reg.gauge("serving/kv_shared_blocks").set(
            self.pool.shared_blocks)
        reg.gauge("serving/kv_cached_blocks").set(
            self.pool.cached_blocks)
        ev = self.pool.prefix_evictions
        if ev > self._evictions_seen:
            reg.counter("serving/prefix_evictions").inc(
                ev - self._evictions_seen)
            self._evictions_seen = ev
        reg.histogram("serving/kv_occupancy").observe(
            self.pool.occupancy())
        reg.histogram("serving/kv_fragmentation").observe(
            self.pool.fragmentation())
        if self.spec_rounds and self._spec_t0 is not None:
            dt = time.monotonic() - self._spec_t0
            if dt > 0:
                reg.gauge("serving/spec_rounds_per_s").set(
                    round(self.spec_rounds / dt, 4))
