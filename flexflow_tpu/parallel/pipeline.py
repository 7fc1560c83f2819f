"""Pipeline parallelism (first-class, TPU-native).

The reference has only vestigial pipeline hooks — PIPELINE_INIT/FWD/BWD
task IDs exist (include/flexflow/model.h:190-192) but no pipeline op is
implemented; SURVEY §2.3 directs this build to treat PP as a
build-fresh strategy.  TPU-native design (the scaling-book recipe):

* mesh axis ``pp`` holds the stages; each device owns a contiguous
  chunk of identical blocks, stacked on a leading dim and sharded over
  ``pp`` (homogeneous-stage pipelining — the transformer case);
* the GPipe schedule is a ``lax.scan`` over ``M + S - 1`` ticks inside
  ``shard_map``: every tick each stage runs its block chunk, then
  ``lax.ppermute`` shifts activations one stage forward over ICI;
* the *backward* pipeline is not hand-written: ``jax.grad`` through the
  scan + ppermute emits the reverse schedule (ppermute transposes to
  the opposite shift) automatically — the functional-autodiff win over
  the reference's task-based design.

All-stages-equal SPMD means invalid ticks (pipeline fill/drain) compute
garbage that is masked, costing the standard bubble fraction
(S-1)/(M+S-1).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def gpipe(
    stage_fn: Callable,
    stage_params,
    x_mb: jax.Array,
    *,
    axis_name: str = "pp",
    num_stages: int,
    num_microbatches: int,
):
    """GPipe forward over one pipeline group.  Call INSIDE shard_map.

    stage_fn(stage_params, act) -> act: this device's stage (shape
    preserved — homogeneous stages).
    stage_params: the local stage's parameters (already pp-sharded).
    x_mb: [M, mb, ...] microbatched input (read on stage 0; other
    stages may hold anything of the same shape).
    Returns [M, mb, ...] outputs, broadcast to every stage of the group.
    """
    S, M = num_stages, num_microbatches
    stage = lax.axis_index(axis_name)
    zero = jnp.zeros_like(x_mb[0])

    def tick(buf, t):
        # stage 0 consumes microbatch t (clipped; masked when t >= M)
        x_t = jnp.take(x_mb, jnp.minimum(t, M - 1), axis=0)
        x_t = jnp.where(t < M, x_t, zero)
        inp = jnp.where(stage == 0, x_t, buf)
        y = stage_fn(stage_params, inp)
        nxt = lax.ppermute(y, axis_name, [(i, (i + 1) % S) for i in range(S)])
        return nxt, y

    _, ys = lax.scan(tick, zero, jnp.arange(M + S - 1))
    outs = ys[S - 1:]  # [M, mb, ...]; real values live on the last stage
    # where-mask (not multiply) so NaN/inf from fill/drain garbage ticks
    # on earlier stages cannot leak through the psum broadcast
    outs = jnp.where(stage == S - 1, outs, jnp.zeros_like(outs))
    return lax.psum(outs, axis_name)  # broadcast to the group


def one_f_one_b(
    stage_fn: Callable,
    stage_params,
    x_mb: jax.Array,
    last_fn: Callable,
    last_params,
    targets_mb: jax.Array,
    *,
    axis_name: str = "pp",
    num_stages: int,
    num_microbatches: int,
):
    """1F1B schedule over one pipeline group: forward and backward
    interleave, capping in-flight saved activations at O(S) per stage
    instead of GPipe's O(M).  Call INSIDE shard_map.

    Unlike `gpipe` (plain forward; jax.grad derives the reverse
    schedule), 1F1B cannot be expressed through outer autodiff — the
    whole point is running microbatch j's backward before microbatch
    j+k's forward — so this function computes the gradients ITSELF with
    per-tick jax.vjp and returns them.  The loss head must live on the
    last stage (that is what lets cotangents exist mid-schedule):

      stage_fn(stage_params, act) -> act        homogeneous block chunk
      last_fn(last_params, act, target) -> loss  one microbatch's head+loss

    Timing (lockstep SPMD, everything masked): stage s runs microbatch
    f's forward at tick s+f and microbatch j's backward at tick
    2(S-1)-s+j; the last stage's backward of mb j lands the same tick
    as its forward, the classic 1F1B cadence.  Saved boundary
    activations live in a [2S-1]-slot ring (residency 2(S-1-s) ticks).
    Total ticks M+2S-2 vs GPipe's 2(M+S-1) fwd+bwd — same steady-state
    compute (each tick does one fwd + one vjp), 2(S-1) extra warmup/
    drain tick-halves, O(S/M) of the schedule.

    Returns (mean loss, stage_params grads, last_params grads) — loss
    and last-grads are psum-broadcast to the group; stage grads are the
    LOCAL stage's (pp-sharded like stage_params).
    """
    S, M = num_stages, num_microbatches
    R = 2 * S - 1  # ring slots: max residency + 1
    stage = lax.axis_index(axis_name)
    zero_act = jnp.zeros_like(x_mb[0])
    zero_tgt = jnp.zeros_like(targets_mb[0])

    def masked_add(acc, upd, valid):
        return jax.tree.map(
            lambda a, u: a + jnp.where(valid, u, jnp.zeros_like(u)), acc, upd
        )

    def tick(carry, t):
        fwd_buf, bwd_buf, ring, g_stage, g_last, loss_acc = carry
        # ---- forward half: stage s runs microbatch f = t - s --------
        f = t - stage
        valid_f = (f >= 0) & (f < M)
        x_t = jnp.take(x_mb, jnp.clip(f, 0, M - 1), axis=0)
        a_in = jnp.where(stage == 0, x_t, fwd_buf)
        a_in = jnp.where(valid_f, a_in, zero_act)
        y = stage_fn(stage_params, a_in)
        ring = ring.at[t % R].set(jnp.where(valid_f, a_in, ring[t % R]))
        # last stage: this microbatch's head + loss, cotangent NOW
        tgt = jnp.take(targets_mb, jnp.clip(f, 0, M - 1), axis=0)
        tgt = jnp.where(valid_f, tgt, zero_tgt)
        loss_f, head_vjp = jax.vjp(
            lambda lp, a: last_fn(lp, a, tgt), last_params, y
        )
        d_last, dy_here = head_vjp(jnp.ones_like(loss_f) / M)
        is_last = stage == S - 1
        loss_acc = loss_acc + jnp.where(is_last & valid_f, loss_f / M, 0.0)
        g_last = masked_add(g_last, d_last, is_last & valid_f)
        # ---- backward half: stage s runs microbatch j ---------------
        j = t - (2 * (S - 1) - stage)
        valid_b = (j >= 0) & (j < M)
        a_saved = ring[(stage + j) % R]
        dy = jnp.where(is_last, dy_here, bwd_buf)
        dy = jnp.where(valid_b, dy, zero_act)
        _, stage_vjp = jax.vjp(stage_fn, stage_params, a_saved)
        d_stage, dx = stage_vjp(dy)
        g_stage = masked_add(g_stage, d_stage, valid_b)
        # ---- shift: activations right, cotangents left --------------
        fwd_buf = lax.ppermute(
            y, axis_name, [(i, (i + 1) % S) for i in range(S)]
        )
        bwd_buf = lax.ppermute(
            dx, axis_name, [(i, (i - 1) % S) for i in range(S)]
        )
        return (fwd_buf, bwd_buf, ring, g_stage, g_last, loss_acc), None

    ring0 = jnp.zeros((R,) + x_mb.shape[1:], x_mb.dtype)
    g_stage0 = jax.tree.map(jnp.zeros_like, stage_params)
    g_last0 = jax.tree.map(jnp.zeros_like, last_params)
    carry = (zero_act, zero_act, ring0, g_stage0, g_last0, jnp.zeros(()))
    carry, _ = lax.scan(tick, carry, jnp.arange(M + 2 * S - 2))
    _, _, _, g_stage, g_last, loss = carry
    # loss/head grads were accumulated on the last stage only
    return (
        lax.psum(loss, axis_name),
        g_stage,
        jax.tree.map(lambda g: lax.psum(g, axis_name), g_last),
    )


def _split_microbatches(x: jax.Array, num_microbatches: int) -> jax.Array:
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(
            f"batch {b} not divisible by num_microbatches {num_microbatches}"
        )
    return x.reshape((num_microbatches, b // num_microbatches) + x.shape[1:])


def pipelined_apply(
    block_fn: Callable,
    stacked_params,
    x: jax.Array,
    *,
    mesh: Mesh,
    num_microbatches: int,
    pp_axis: str = "pp",
    dp_axis: str = "data",
    remat: bool = False,
):
    """Apply a stack of identical blocks as a dp x pp pipelined SPMD
    computation.

    block_fn(params_i, act) -> act: ONE block (e.g. a transformer
    layer).  stacked_params: pytree with leading dim L = num blocks,
    sharded over ``pp`` (L % pp == 0).  x: [batch, ...] sharded over
    ``data``.  Differentiable end to end.

    remat=True checkpoints each block: autodiff through the schedule
    then stores only per-(tick, block) boundary activations instead of
    every block's internals (attention scores, ffn hiddens) for every
    in-flight microbatch — the activation-memory lever that lets deep
    pipelines raise num_microbatches (smaller bubble) without raising
    peak HBM.  Same schedule, same collectives; backward recomputes
    block internals.  Boundary storage still grows O(M); when that is
    the binding constraint, `one_f_one_b` caps residency at O(S)
    (measured: temp bytes flat in M vs linear here — docs/PERF.md).
    """
    pp = mesh.shape[pp_axis]
    layers = jax.tree.leaves(stacked_params)[0].shape[0]
    if layers % pp:
        raise ValueError(f"{layers} blocks not divisible by pp={pp}")
    stage_fn = _make_stage_fn(block_fn, remat)

    def spmd(params, xb):
        x_mb = _split_microbatches(xb, num_microbatches)
        y_mb = gpipe(stage_fn, params, x_mb, axis_name=pp_axis,
                     num_stages=pp, num_microbatches=num_microbatches)
        return y_mb.reshape((-1,) + y_mb.shape[2:])

    param_specs = jax.tree.map(
        lambda a: P(pp_axis, *([None] * (a.ndim - 1))), stacked_params
    )
    in_x = P(dp_axis, *([None] * (x.ndim - 1)))
    return jax.shard_map(
        spmd,
        mesh=mesh,
        in_specs=(param_specs, in_x),
        out_specs=in_x,
        check_vma=False,
    )(stacked_params, x)


def _make_stage_fn(block_fn: Callable, remat: bool) -> Callable:
    """One stage = scan over this device's local block chunk (shared by
    the GPipe and 1F1B schedules so their numerics cannot diverge)."""
    body_block = jax.checkpoint(block_fn) if remat else block_fn

    def stage_fn(local_params, act):
        def body(a, p):
            return body_block(p, a), None

        out, _ = lax.scan(body, act, local_params)
        return out

    return stage_fn


def stacked_param_sharding(mesh: Mesh, a, pp_axis: str = "pp"):
    """NamedSharding for a [L, ...] stacked block-parameter array."""
    return NamedSharding(mesh, P(pp_axis, *([None] * (a.ndim - 1))))


# ----------------------------------------------------------------------
# Reference-parity demo model: a pipelined transformer-encoder train
# step used by tests and the driver's multichip dryrun.
# ----------------------------------------------------------------------

def _init_block_params(key, layers, hidden, ffn, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    scale = 1.0 / jnp.sqrt(hidden)
    return {
        "w_qkv": jax.random.normal(ks[0], (layers, hidden, 3 * hidden), dtype) * scale,
        "w_o": jax.random.normal(ks[1], (layers, hidden, hidden), dtype) * scale,
        "w_in": jax.random.normal(ks[2], (layers, hidden, ffn), dtype) * scale,
        "w_out": jax.random.normal(ks[3], (layers, ffn, hidden), dtype) * scale,
    }


def _encoder_block(p, x, *, num_heads: int):
    b, s, h = x.shape
    hd = h // num_heads
    qkv = x @ p["w_qkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(b, s, num_heads, hd).transpose(0, 2, 1, 3)

    q, k, v = heads(q), heads(k), heads(v)
    a = jax.nn.softmax(q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(hd), axis=-1)
    o = (a @ v).transpose(0, 2, 1, 3).reshape(b, s, h)
    x = _ln(x + o @ p["w_o"])
    y = jax.nn.relu(x @ p["w_in"]) @ p["w_out"]
    return _ln(x + y)


def _ln(x, eps=1e-5):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps)


def make_pipelined_transformer_step(
    mesh: Mesh,
    *,
    layers: int,
    hidden: int,
    ffn: int,
    num_heads: int,
    num_classes: int,
    num_microbatches: int,
    lr: float = 0.01,
    pp_axis: str = "pp",
    dp_axis: str = "data",
    schedule: str = "gpipe",
    remat: bool = False,
):
    """(init_fn, step_fn): a full SGD train step (fwd+loss+bwd+update)
    for a block-stacked encoder pipelined over `pp` and batch-sharded
    over `data`.

    schedule: "gpipe" (forward scan, jax.grad derives the reverse
    schedule; O(M) saved boundaries, remat=True shrinks each to the
    block boundary) or "1f1b" (interleaved fwd/bwd via `one_f_one_b`;
    O(S) in-flight activations — the deep-pipeline memory lever).
    Both compute identical gradients (test_pipeline.py asserts it)."""
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    pp = mesh.shape[pp_axis]
    if layers % pp:
        raise ValueError(f"{layers} blocks not divisible by pp={pp}")

    def init_fn(seed: int):
        key = jax.random.key(seed)
        kb, kh = jax.random.split(key)
        params = {
            "blocks": _init_block_params(kb, layers, hidden, ffn),
            "head": jax.random.normal(kh, (hidden, num_classes)) / jnp.sqrt(hidden),
        }
        shardings = {
            "blocks": jax.tree.map(
                lambda a: stacked_param_sharding(mesh, a, pp_axis),
                params["blocks"],
            ),
            "head": NamedSharding(mesh, P(None, None)),
        }
        return jax.tree.map(jax.device_put, params, shardings)

    block = functools.partial(_encoder_block, num_heads=num_heads)

    def loss_fn(params, x, y):
        h = pipelined_apply(block, params["blocks"], x, mesh=mesh,
                            num_microbatches=num_microbatches,
                            pp_axis=pp_axis, dp_axis=dp_axis, remat=remat)
        logits = h.mean(axis=1) @ params["head"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    @jax.jit
    def gpipe_step(params, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return params, loss

    # ---- 1f1b: grads computed inside the schedule ---------------------
    stage_fn = _make_stage_fn(block, remat)

    def last_fn(head, act, tgt):
        logits = act.mean(axis=1) @ head
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, tgt[:, None], axis=-1).mean()

    def spmd_1f1b(params, x, y):
        x_mb = _split_microbatches(x, num_microbatches)
        y_mb = _split_microbatches(y, num_microbatches)
        loss, g_blocks, g_head = one_f_one_b(
            stage_fn, params["blocks"], x_mb, last_fn, params["head"],
            y_mb, axis_name=pp_axis, num_stages=pp,
            num_microbatches=num_microbatches,
        )
        # dp: average grads (and loss) over the data axis
        dp = mesh.shape.get(dp_axis, 1)
        if dp > 1:
            g_blocks = jax.tree.map(
                lambda g: lax.pmean(g, dp_axis), g_blocks)
            g_head = jax.tree.map(lambda g: lax.pmean(g, dp_axis), g_head)
            loss = lax.pmean(loss, dp_axis)
        return loss, {"blocks": g_blocks, "head": g_head}

    block_shapes = jax.eval_shape(
        lambda: _init_block_params(jax.random.key(0), layers, hidden, ffn)
    )
    block_specs = jax.tree.map(lambda _: P(pp_axis, None, None),
                               block_shapes)
    param_specs = {"blocks": block_specs, "head": P(None, None)}
    in_x, in_y = P(dp_axis, None, None), P(dp_axis)

    @jax.jit
    def ofob_step(params, x, y):
        loss, grads = jax.shard_map(
            spmd_1f1b, mesh=mesh,
            in_specs=(param_specs, in_x, in_y),
            out_specs=(P(), param_specs),
            check_vma=False,
        )(params, x, y)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return params, loss

    return init_fn, (gpipe_step if schedule == "gpipe" else ofob_step)
