"""One chip's share of a routed-expert layer, with its shared expert.

The layer is TOLD WHICH EXPERTS IT HOLDS: `experts_total` is the
router's published width, `experts_held` of them live here, starting
at `first_held`.  It routes every token over all `experts_total` in
float32 (scores by the params' `scoring` rule: `sigmoid` of each logit,
or `softmax` over all the logits; the top `top_k` of score + bias, the
chosen scores normalised over ALL of the chosen and scaled), and adds

    sum over experts chosen AND held here of w_e E_e(h)  +  g E_shared(h)

with `g = 1`, or `sigmoid(w_sg . h)` where `shared_expert_gate` is set.

Experts that live on other chips add nothing: on one chip the layer
runs without its exchange, and nothing stands in for the absent chips.
`experts_held == experts_total` is the whole layer.

No token is dropped at any load, by either of the two products, of
which `pick_expert_product` chooses one from the step's shapes:

* "dense": every held expert is applied to every row of the step and
  the result combined with the routing weights (zero where an expert
  was not chosen): a capacity of the step's rows, which at a decode
  step's few rows costs what any static-shape dispatch costs, the read
  of the held experts' weights.  What the serving steps take.
* "grouped": the step's (token, expert) pairs are sorted by expert,
  those on held experts first; each held expert multiplies its own run
  of rows (`grouped_matmul`: one ragged product a weight, whose groups
  are the runs) and the results go back to their tokens weighted by the
  routing.  The buffers are static (`GROUPED_SLACK` x the pairs an even
  router sends here; every pair of the step when there are more), the
  product visits the held runs only.  What a training step's thousands
  of rows take: dense would multiply `experts_total / top_k` times what
  the routing asked for.

`moe_stats` counts what a dispatch with a smaller capacity would have
to get right: routed pairs that landed on held experts, pairs the
combine left out (0), the rows of the fullest held expert, and the held
experts that received a row.  The grouped product also counts the rows
it multiplied (`moe_rows_computed`, padding of the product's row tiles
included); the dense product's is the static `rows x experts_held`.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ..fftype import DataType, OperatorType
from ..initializer import DEFAULT_WEIGHT_INIT, ZeroInitializer
from ..obs.scopes import scope
from ..tensor import ParallelDim, ParallelTensorShape
from .dense import gated_mlp
from .op import Op, ShapeError, WeightSpec, remat_keep

#: order of the counters in the `moe_stats` state entry
MOE_STATS = ("pairs", "dropped", "max_rows", "hit")


@dataclasses.dataclass(frozen=True)
class RoutedExpertsParams:
    experts_total: int
    experts_held: int
    first_held: int
    top_k: int
    expert_hidden: int
    shared_hidden: int = 0  # 0: no shared expert
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    dtype: DataType = DataType.FLOAT
    scoring: str = "sigmoid"  # or "softmax", over all experts_total
    shared_expert_gate: bool = False  # shared expert times sigmoid(w . h)
    norm_eps: float = 1e-20  # added to the chosen scores' sum


def route(h, router, bias, p: RoutedExpertsParams):
    """h [t, e] -> (chosen expert ids [t, k], their weights [t, k]),
    all in float32 at full matmul precision: scores by `p.scoring`, the
    bias only chooses, the normaliser runs over all k chosen."""
    logits = jnp.matmul(
        h.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.softmax(logits, axis=-1) if p.scoring == "softmax"
              else jax.nn.sigmoid(logits))
    _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), p.top_k)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if p.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + p.norm_eps)
    return chosen, w * p.routed_scaling_factor


#: routed rows a held expert has to expect before the grouped product
#: pays for its sort and its gathers: the rows of one pass of the
#: matrix unit
GROUPED_MIN_ROWS_PER_EXPERT = 128
#: rows the ragged product is counted to multiply at a time (a sublane
#: tile): a held expert's run of n rows counts `ceil(n / 8)` tiles
GROUPED_ROW_TILE = 8
#: the grouped product's usual buffers hold this many times the pairs an
#: even router sends to the held experts; a step with more takes the
#: buffers that hold every pair
GROUPED_SLACK = 1.5


def pick_expert_product(rows: int, experts_held: int, experts_total: int,
                        top_k: int, backend: str = "") -> str:
    """"dense" or "grouped" for a step of `rows` rows.  A pure function
    of its arguments; the backend does not enter (both products are
    plain XLA programs), it is taken so that a caller never has to know
    that.  Dense multiplies `rows x experts_held` rows; grouped about
    `rows x top_k x experts_held / experts_total` and pays a sort and
    four gathers of every pair: worth it once a held expert expects
    `GROUPED_MIN_ROWS_PER_EXPERT` rows, which no serving step's few
    hundred rows give it."""
    del backend
    if experts_held < 2:
        return "dense"
    expected = rows * top_k / experts_total  # routed rows an expert
    return ("grouped" if expected >= GROUPED_MIN_ROWS_PER_EXPERT
            else "dense")


def dense_experts(h, combine, w_gate, w_up, w_down):
    """The dense product: every held expert (axis x) over every row of
    h [t, e], combined with `combine` [t, held], the routing weights
    (zero where an expert was not chosen) -> [t, e]."""
    with scope("products"):
        gate = jnp.einsum("te,xef->xtf", h, w_gate)
        up = jnp.einsum("te,xef->xtf", h, w_up)
        y = jnp.einsum("xtf,xfe->xte", jax.nn.silu(gate) * up, w_down)
    with scope("combine"):
        return jnp.einsum("xte,tx->te", y, combine.astype(y.dtype))


def grouped_matmul(lhs, rhs, sizes):
    """lhs [m, k] whose rows run expert after expert, `sizes[g]` rows
    for expert g (their sum may stay below m); rhs [g, k, n] -> [m, n].
    Rows past the last group come out as zeros."""
    return jax.lax.ragged_dot(lhs, rhs, sizes)


@jax.custom_vjp
def _rows_to_slots(h, order, slot_of):
    """h [t, e] -> [m, e]: slot s < m holds the row of the token of pair
    `order[s]` (`order` [m]: the first m slots' pairs).  Backward is a
    gather too (`slot_of` [t, k] is the inverse permutation; a pair
    whose slot is m or later was not kept), not a scatter-add."""
    return h[order // slot_of.shape[1]]


def _rows_to_slots_fwd(h, order, slot_of):
    return _rows_to_slots(h, order, slot_of), slot_of


def _rows_to_slots_bwd(slot_of, d_slots):
    return jnp.sum(_kept_rows(d_slots, slot_of), axis=1), None, None


_rows_to_slots.defvjp(_rows_to_slots_fwd, _rows_to_slots_bwd)


def _kept_rows(ys, slot_of):
    """ys [m, e] -> [t, k, e]: every pair's row; zeros for a pair whose
    slot was not kept."""
    m = ys.shape[0]
    rows = ys[jnp.minimum(slot_of, m - 1)]
    return jnp.where((slot_of < m)[..., None], rows, 0)


@jax.custom_vjp
def _slots_to_pairs(ys, order, slot_of):
    """ys [m, e] -> [t, k, e], back in token order (`_kept_rows`);
    backward the inverse gather."""
    return _kept_rows(ys, slot_of)


def _slots_to_pairs_fwd(ys, order, slot_of):
    return _slots_to_pairs(ys, order, slot_of), order


def _slots_to_pairs_bwd(order, d_pairs):
    return d_pairs.reshape(-1, d_pairs.shape[-1])[order], None, None


_slots_to_pairs.defvjp(_slots_to_pairs_fwd, _slots_to_pairs_bwd)


def grouped_experts(h, landed_on, w, w_gate, w_up, w_down,
                    expected_pairs: float):
    """The grouped product: h [t, e]; landed_on [t, k] the held expert
    (0 .. held - 1) each chosen pair landed on, or `held` for an expert
    that lives elsewhere; w [t, k] the routing weights; `expected_pairs`
    the pairs an even router sends to the held experts (t * k * held /
    total) -> (out [t, e], rows multiplied, tile padding included).

    The step's t * k pairs are sorted by expert, the held experts' runs
    first.  Static shapes and no drop at any load: the buffers keep the
    first m slots, where m is `GROUPED_SLACK` times the pairs the held
    experts expect when they hold that many, and EVERY pair when they
    hold more (one `lax.cond` on the count: both sizes are compiled,
    one runs).  The products visit the held runs and nothing after."""
    t, k = landed_on.shape
    held = w_gate.shape[0]
    with scope("dispatch"):
        order = jnp.argsort(landed_on.reshape(-1), stable=True)  # slot -> pair
        slot_of = jnp.argsort(order).reshape(t, k).astype(jnp.int32)
        order = order.astype(jnp.int32)
        sizes = jnp.sum(jax.nn.one_hot(landed_on.reshape(-1), held,
                                       dtype=jnp.int32), axis=0)
        count = jnp.sum(sizes)
        weights = jnp.where(landed_on < held, w, 0)

    m_all = t * k
    m_usual = min(m_all, -(-int(GROUPED_SLACK * expected_pairs)
                           // GROUPED_ROW_TILE) * GROUPED_ROW_TILE)

    def kept(m, h, weights, w_gate, w_up, w_down):
        with scope("dispatch"):
            live = (jnp.arange(m, dtype=jnp.int32) < count)[:, None]

        def product(x, weight):
            with scope("dispatch"):
                x = jnp.where(live, x, 0)
            with scope("products"):
                y = grouped_matmul(x, weight, sizes)
                if m == m_usual:
                    # a checkpointed segment may hold the usual buffers'
                    # products; the overflow's (a `cond` keeps BOTH
                    # branches' residuals alive) are computed again
                    y = remat_keep(y)
            # zeros past the held runs on both sides, so that neither a
            # value nor a gradient of a row nobody multiplied goes on
            with scope("dispatch"):
                return jnp.where(live, y, 0).astype(h.dtype)

        with scope("dispatch"):
            xs = _rows_to_slots(h, order[:m], slot_of)
        gate = product(xs, w_gate)
        with scope("products"):
            gate = jax.nn.silu(gate)
        up = product(xs, w_up)
        with scope("products"):
            act = gate * up
        ys = product(act, w_down)
        with scope("dispatch"):
            pairs = _slots_to_pairs(ys, order[:m], slot_of)
        with scope("combine"):
            return jnp.einsum("tke,tk->te", pairs,
                              weights.astype(pairs.dtype))

    args = (h, weights, w_gate, w_up, w_down)
    if m_usual == m_all:
        out = kept(m_all, *args)
    else:
        with scope("dispatch"):
            fits = count <= m_usual
        out = jax.lax.cond(fits, functools.partial(kept, m_usual),
                           functools.partial(kept, m_all), *args)
    with scope("dispatch"):
        tiles = -(-sizes // GROUPED_ROW_TILE)
        return out, jnp.sum(tiles) * GROUPED_ROW_TILE


class RoutedExperts(Op):
    op_type = OperatorType.ROUTED_EXPERTS
    float32_weights = ("router", "router_bias")
    has_aux_state = True  # weights[num_trainable_weights():] are state
    #: the state entries are counters the forward pass only writes:
    #: a segment that holds this op may be rematerialised
    #: (`GraphExecutor._build_remat_plan`)
    state_is_counters = True

    def _rows(self) -> int:
        shape = self.inputs[0].shape
        return shape.num_elements() // shape.logical_shape[-1]

    def product_plan(self) -> str:
        """"dense" or "grouped": what a step of this op's declared rows
        takes (`pick_expert_product`)."""
        p: RoutedExpertsParams = self.params
        return pick_expert_product(self._rows(), p.experts_held,
                                   p.experts_total, p.top_k,
                                   jax.default_backend())

    def dense_rows_computed(self) -> int:
        """Rows the dense product multiplies a step: every held expert
        over every row."""
        return self.params.experts_held * self._rows()

    def infer_output_shapes(self, input_shapes):
        (ishape,) = input_shapes
        p: RoutedExpertsParams = self.params
        last = [d for d in ishape.dims if not d.is_replica_dim][-1]
        if last.degree != 1 or not self.shard.is_trivial():
            raise ShapeError(
                f"{self.name}: this layer holds one chip's experts; "
                "experts spread over a mesh axis are not built yet")
        if not (0 <= p.first_held
                and p.first_held + p.experts_held <= p.experts_total
                and 1 <= p.top_k <= p.experts_total):
            raise ShapeError(
                f"{self.name}: held experts [{p.first_held}, "
                f"{p.first_held + p.experts_held}) and top_k {p.top_k} "
                f"do not fit {p.experts_total} experts")
        if p.scoring not in ("sigmoid", "softmax"):
            raise ShapeError(f"{self.name}: scoring {p.scoring!r} is not "
                             "'sigmoid' or 'softmax'")
        if p.shared_expert_gate and not p.shared_hidden:
            raise ShapeError(f"{self.name}: shared_expert_gate without a "
                             "shared expert")
        return [ParallelTensorShape(ishape.dims, p.dtype)]

    def num_trainable_weights(self) -> int:
        p: RoutedExpertsParams = self.params
        return 5 + (3 if p.shared_hidden else 0) + int(p.shared_expert_gate)

    def make_weight_specs(self, input_shapes):
        (ishape,) = input_shapes
        p: RoutedExpertsParams = self.params
        e = ishape.logical_shape[-1]
        rep = ParallelDim(1, ishape.total_degree, is_replica_dim=True)

        def w(*sizes, dtype=p.dtype):
            return ParallelTensorShape(
                tuple(ParallelDim(s) for s in sizes) + (rep,), dtype)

        init, zero = DEFAULT_WEIGHT_INIT, ZeroInitializer()
        n, f = p.experts_held, p.expert_hidden
        specs = [
            WeightSpec("router", w(e, p.experts_total), init),
            WeightSpec("router_bias", w(p.experts_total), zero),
            WeightSpec("w_gate", w(n, e, f), init),
            WeightSpec("w_up", w(n, e, f), init),
            WeightSpec("w_down", w(n, f, e), init),
        ]
        if p.shared_hidden:
            specs += [
                WeightSpec("shared_gate", w(e, p.shared_hidden), init),
                WeightSpec("shared_up", w(e, p.shared_hidden), init),
                WeightSpec("shared_down", w(p.shared_hidden, e), init),
            ]
        if p.shared_expert_gate:
            specs.append(WeightSpec("shared_expert_gate", w(e), init))
        specs.append(WeightSpec(
            "moe_stats", w(len(MOE_STATS), dtype=DataType.INT32), zero))
        if self.product_plan() == "grouped":
            specs.append(WeightSpec(
                "moe_rows_computed", w(1, dtype=DataType.INT32), zero))
        return specs

    def forward(self, inputs, weights, *, training=False, rng=None):
        (x,) = inputs
        p: RoutedExpertsParams = self.params
        router, bias, w_gate, w_up, w_down = weights[:5]
        with scope("route"):
            h = x.reshape(-1, x.shape[-1])
            chosen, w = route(h, router, bias, p)
        with scope("dispatch"):
            # [t, k, held]: which held expert each chosen pair landed
            # on; a pair for an expert that lives elsewhere is all zeros
            at = chosen - p.first_held
            landed = jax.nn.one_hot(at, p.experts_held, dtype=jnp.float32)
            combine = jnp.einsum("tkx,tk->tx", landed, w)
        grouped = self.product_plan() == "grouped"
        if grouped:
            with scope("dispatch"):
                landed_on = jnp.where((at >= 0) & (at < p.experts_held),
                                      at, p.experts_held)
            out, rows_computed = grouped_experts(
                h, landed_on, w, w_gate, w_up, w_down,
                h.shape[0] * p.top_k * p.experts_held / p.experts_total)
        else:
            out = dense_experts(h, combine, w_gate, w_up, w_down)
        if p.shared_hidden:
            with scope("shared"):
                if p.shared_expert_gate:
                    g = jax.nn.sigmoid(jnp.einsum(
                        "te,e->t", h, weights[8],
                        preferred_element_type=jnp.float32))
                    shared = gated_mlp(h, *weights[5:8]) * g[
                        :, None].astype(out.dtype)
                else:
                    shared = gated_mlp(h, *weights[5:8])
            with scope("combine"):
                out = out + shared
        with scope("dispatch"):  # the counts of it
            rows = jnp.sum(landed, axis=(0, 1)).astype(jnp.int32)  # [held]
            pairs = jnp.sum(rows)
            stats = jnp.stack([
                pairs,
                pairs - jnp.sum(combine != 0).astype(jnp.int32),
                jnp.max(rows),
                jnp.sum(rows > 0).astype(jnp.int32),
            ])
        with scope("combine"):
            out = out.reshape(x.shape).astype(x.dtype)
        if grouped:
            with scope("dispatch"):
                rows_computed = rows_computed.reshape(1).astype(jnp.int32)
            return [out, stats, rows_computed]
        return [out, stats]

    def flops(self):
        """The router's product (either scoring rule is a few
        operations a logit on top), the experts' product as the chosen
        one multiplies it (dense: every held expert over every row;
        grouped: the pairs that land on held experts, in expectation),
        the shared expert and its gate's dot product."""
        p: RoutedExpertsParams = self.params
        t = self.inputs[0].shape.num_elements()  # rows x e
        experts = (p.top_k * p.experts_held / p.experts_total
                   if self.product_plan() == "grouped" else p.experts_held)
        return t * (2.0 * p.experts_total
                    + 6.0 * experts * p.expert_hidden
                    + 6.0 * p.shared_hidden
                    + 2.0 * int(p.shared_expert_gate))
