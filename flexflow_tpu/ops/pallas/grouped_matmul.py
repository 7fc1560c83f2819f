"""Grouped matrix products for the routed experts, as Pallas kernels.

lhs [m, k] holds runs of rows, one run a group (an expert), `sizes[g]`
rows for group g, the runs back to back from row 0; their sum may stay
below m.  Three products (what `ops/routed_experts.py`'s
`grouped_matmul`, `grouped_matmul_into_lhs` and `grouped_matmul_into_rhs`
take on a TPU):

* `product(lhs, rhs [g, k, n])           -> [m, n]`: each run times its
  group's matrix;
* `product(ct, rhs [g, n, k], transpose_rhs=True) -> [m, n]`: the same
  with the matrix read as it is stored and contracted over its LAST
  axis (the gradient into lhs: no transposed copy of a weight);
* `product_into_groups(lhs [m, k], ct [m, n]) -> [g, k, n]`: each run's
  rows contracted into its group's slice (the gradient into rhs); an
  empty group's slice is zero.

The walk is megablox's (`jax.experimental.pallas.ops.tpu.megablox`):
row tiles of `tm` rows lie on the buffer's own grid, a grid step is one
(row tile, group) visit, a tile that two runs share is visited once for
each and the rows of the other run are masked.  What differs from
calling megablox's `gmm` / `tgmm` a product:

* the visits are computed ONCE for the products that share them
  (`visits`, a jitted function of its own: the six products of a routed
  layer's forward and backward share two lists) in a dozen
  operations, where each `gmm` call traces its metadata's ~40 jax.numpy
  operations again: 16 tracings of 85 ms in a step with routed layers,
  which `setup_s` paid (PERF.md, PR 50);
* widths are whole tiles (tk divides k, tn divides n: the picker's
  tilings), so no remainder is masked; with tk = k the product is
  stored as it leaves the matrix unit, without an accumulator's round
  trip;
* the weight-gradient product masks its operands only on a visit whose
  tile does not lie wholly inside its run.

Rows of lhs past the last run are not read into any result that is
defined; rows of the first two products' RESULT past the last run are
not written (whatever the buffer held, NaN included: the interpreter
fills them with NaN).  bf16 or float32 operands, float32 accumulation,
results in the operands' dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ARBITRARY = ("parallel", "arbitrary", "arbitrary")


@functools.partial(jax.jit, static_argnames=("m", "tm", "empty_groups"))
def visits(sizes, *, m: int, tm: int, empty_groups: bool):
    """The grid steps of a grouped product over m rows in row tiles of
    tm -> (group_offsets [g + 1], group_ids, m_tile_ids [m / tm + g - 1],
    the number of steps): group after group, each group the row tiles
    its run touches in turn.  Steps past the count are never run.
    `empty_groups`: an empty group is visited once all the same
    (`product_into_groups`, which has to zero its slice)."""
    groups, tiles_m = sizes.shape[0], m // tm
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first_tile = starts // tm
    tiles = jnp.where(sizes > 0, -(-ends // tm) - first_tile,
                      int(empty_groups))
    step_ends = jnp.cumsum(tiles)
    step = jnp.arange(tiles_m + groups - 1, dtype=jnp.int32)
    group_ids = jnp.minimum(
        jnp.sum(step[:, None] >= step_ends[None, :], axis=1,
                dtype=jnp.int32), groups - 1)
    m_tile_ids = jnp.clip(
        first_tile[group_ids] + step - (step_ends - tiles)[group_ids],
        0, tiles_m - 1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return offsets, group_ids, m_tile_ids.astype(jnp.int32), step_ends[-1]


def _rows_of_the_run(offsets, group_ids, m_tile_ids, step, tm):
    """(whether the step's row tile lies wholly inside its group's run,
    a function of a width -> the [tm, width] mask of the run's rows)."""
    group = group_ids[step]
    start, end = offsets[group], offsets[group + 1]
    row0 = m_tile_ids[step] * tm

    def mask(width):
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (tm, width), 0)
        return (rows >= start) & (rows < end)

    return (start <= row0) & (row0 + tm <= end), mask


def _product_kernel(offsets, group_ids, m_tile_ids, lhs, rhs, out, *acc,
                    tm, tn, tiles_k, transpose_rhs):
    step, k_i = pl.program_id(1), pl.program_id(2)
    contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    def product():
        return jax.lax.dot_general(lhs[...], rhs[...], contract,
                                   preferred_element_type=jnp.float32)

    def store(total):
        # the tile's other rows are another run's, or nobody's.  (One
        # masked store for every visit: a second, unmasked form for the
        # tiles that lie wholly inside their run read 1.7 % SLOWER on
        # the v5e, 0.569 against 0.559 ms for cell 6's product.)
        _, mask = _rows_of_the_run(offsets, group_ids, m_tile_ids, step, tm)
        out[...] = jnp.where(mask(tn), total, out[...].astype(jnp.float32)
                             ).astype(out.dtype)

    if tiles_k == 1:
        store(product())
        return
    (acc,) = acc

    @pl.when(k_i == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += product()  # the matrix unit adds into the scratch

    @pl.when(k_i == tiles_k - 1)
    def _():
        store(acc[...])


@functools.partial(jax.jit, static_argnames=("tiling", "transpose_rhs",
                                             "interpret"))
def product(lhs, rhs, steps, *, tiling, transpose_rhs: bool = False,
            interpret: bool = False):
    """lhs [m, k] x rhs [g, k, n] (`transpose_rhs`: [g, n, k]) -> [m, n]
    over `steps` = `visits(sizes, m=m, tm=tiling[0], empty_groups=False)`.
    Jitted by itself: the layers of a model call it with the same
    shapes, so a kernel is traced and lowered once a step program."""
    offsets, group_ids, m_tile_ids, count = steps
    tm, tk, tn = tiling
    (m, k), n = lhs.shape, rhs.shape[1 if transpose_rhs else 2]
    if m % tm or k % tk or n % tn:
        raise ValueError(f"tiling {tiling} does not divide ({m}, {k}, {n})")
    tiles_n, tiles_k = n // tn, k // tk

    def lhs_map(n_i, step, k_i, offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], k_i

    def rhs_map(n_i, step, k_i, offsets, group_ids, m_tile_ids):
        return ((group_ids[step], n_i, k_i) if transpose_rhs
                else (group_ids[step], k_i, n_i))

    def out_map(n_i, step, k_i, offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], n_i

    bytes_ = lhs.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_product_kernel, tm=tm, tn=tn, tiles_k=tiles_k,
                          transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles_n, count, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lhs_map),
                pl.BlockSpec((None, tn, tk) if transpose_rhs
                             else (None, tk, tn), rhs_map),
            ],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=([] if tiles_k == 1
                            else [pltpu.VMEM((tm, tn), jnp.float32)]),
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=_ARBITRARY),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=bytes_ * (m * k * tiles_n + rhs.size + m * n)),
        interpret=interpret,
        name="grouped_matmul_into_lhs" if transpose_rhs else "grouped_matmul",
    )(offsets, group_ids, m_tile_ids, lhs, rhs)


def _into_groups_kernel(offsets, group_ids, m_tile_ids, lhs, ct, out, acc,
                        *, tm, tk, tn):
    step, last = pl.program_id(2), pl.num_programs(2) - 1
    group = group_ids[step]

    @pl.when((step == 0) | (group_ids[jnp.maximum(step - 1, 0)] != group))
    def _():
        acc[...] = jnp.zeros_like(acc)

    def add(x, c):  # [tm, tk], [tm, tn] -> [tk, tn] over the rows
        acc[...] += jax.lax.dot_general(
            x, c, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    whole, mask = _rows_of_the_run(offsets, group_ids, m_tile_ids, step, tm)

    @pl.when(whole)
    def _():
        add(lhs[...], ct[...])

    # a shared tile, or the run's last: the other rows may hold anything
    # (NaN past the last run), so both operands drop them; an empty
    # group's one visit adds nothing
    @pl.when(jnp.logical_not(whole)
             & (offsets[group + 1] > offsets[group]))
    def _():
        add(jnp.where(mask(tk), lhs[...], 0), jnp.where(mask(tn), ct[...], 0))

    @pl.when((step == last) | (group_ids[jnp.minimum(step + 1, last)]
                               != group))
    def _():
        out[...] = acc[...].astype(out.dtype)


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def product_into_groups(lhs, ct, steps, *, tiling, interpret: bool = False):
    """lhs [m, k], ct [m, n] -> [g, k, n] over `steps` =
    `visits(sizes, m=m, tm=tiling[0], empty_groups=True)`."""
    offsets, group_ids, m_tile_ids, count = steps
    tm, tk, tn = tiling
    (m, k), n = lhs.shape, ct.shape[1]
    groups = offsets.shape[0] - 1
    if m % tm or k % tk or n % tn:
        raise ValueError(f"tiling {tiling} does not divide ({m}, {k}, {n})")
    tiles_n, tiles_k = n // tn, k // tk

    def lhs_map(n_i, k_i, step, offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], k_i

    def ct_map(n_i, k_i, step, offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], n_i

    def out_map(n_i, k_i, step, offsets, group_ids, m_tile_ids):
        return group_ids[step], k_i, n_i

    bytes_ = lhs.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_into_groups_kernel, tm=tm, tk=tk, tn=tn),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles_n, tiles_k, count),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((tm, tn), ct_map)],
            out_specs=pl.BlockSpec((None, tk, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=_ARBITRARY),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=bytes_ * (m * k * tiles_n + m * n * tiles_k
                                     + groups * k * n)),
        interpret=interpret,
        name="grouped_matmul_into_rhs",
    )(offsets, group_ids, m_tile_ids, lhs, ct)
