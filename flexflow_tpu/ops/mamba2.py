"""A Mamba-2 mixer: a state-space layer whose memory of a sequence is a
fixed-size state, not a cache that grows with it.

    [z | xBC | dt] = h W_in            (e -> d_inner + conv_dim + H)
    xBC = silu(causal depthwise conv_K(xBC) + b_conv);  [x | B | C] = xBC
    dt = softplus(dt + dt_bias)  [H];   A = -exp(A_log)  [H]
    per head j, S_j a [P, N] matrix, for each position t in order:
        S_j = exp(dt_tj A_j) S_j + dt_tj x_tj (outer) B_t
        y_tj = S_j C_t + D_j x_tj
    y = rmsnorm(y * silu(z); w_norm, over all d_inner channels)
    out = y W_out                      (d_inner -> e)

`d_inner = H x P`; ONE B and ONE C [N] a position, shared by every head
(the published `mamba_n_groups` 1: more groups are not built);
`conv_dim = d_inner + 2 N`.  Beside `GatedDeltaNet`: a plain decayed
outer-product accumulation, no delta rule, one decay a head, a skip
`D x`, a conv WITH bias, the gated norm over all channels at once.

What a sequence leaves behind, a layer: the conv's last K - 1 inputs
(`conv_state`, compute dtype) and `S` for every head (`ssm_state`,
float32, as the recurrence itself is).  Both are FIXED SIZE:
`slot_state_entries()` names them and the serving tier does the rest
(docs/SERVING.md "Per-slot recurrent state").

Two shapes, one set of weights, no flag:

* no state (`slot_state=False`): every row starts from zero and runs
  its whole `[b, s]` input, `chunk_size` positions at a time (the SSD
  form: `ssd_chunk` under a `lax.scan` that carries the state over
  chunk boundaries), plain jax.numpy that jax differentiates;
* per-slot state, a step of s tokens a row (s = 1 the decode step, s =
  `prefill_chunk` a chunk in the pass): ONE chunk of that form from the
  row's own state, advanced by the row's `row_tokens[i]` real tokens
  (positions past it arrive with `dt = 0`: `exp(0) S + 0`).  Never a
  scan a position over every slot's state.  It reads and writes every
  slot's state, live or not (`rstate_rows_touched`).

Both are plain jax.numpy on every backend (what a kernel for the state's
part would have to beat: `PERF.md` section 7 "Since PR 62").

The matrix form of one chunk from an initial state (`ssd_chunk`), with
`L_t = sum_{u <= t} dt_u A` a head:

    Y  = (M o (C B^T)) (dt o X) + exp(L) o (C S0^T) + D X
         M_tu = exp(L_t - L_u) for u <= t, else 0
    S1 = exp(L_s) S0 + ((dt o exp(L_s - L)) o X)^T B

`C B^T` is ONE `[s, s]` product a row, shared by the heads.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..fftype import DataType, OperatorType
from ..initializer import (DEFAULT_WEIGHT_INIT, ConstantInitializer,
                           ZeroInitializer)
from ..obs.scopes import scope
from ..tensor import ParallelDim, ParallelTensorShape
from .op import Op, ShapeError, ShardConfig, WeightSpec, rstate_group
from .short_conv import causal_depthwise_conv

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Mamba2Params:
    embed_dim: int
    num_heads: int          # H  (`mamba_n_heads`)
    head_dim: int           # P  (`mamba_d_head`)
    state_dim: int          # N  (`mamba_d_state`)
    conv_kernel: int = 4    # K  (`mamba_d_conv`)
    chunk_size: int = 256   # the stateless form's chunk
    eps: float = 1e-5

    @property
    def d_inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the conv runs over: [x | B | C]."""
        return self.d_inner + 2 * self.state_dim


def ssm_scan(S, x, B, C, dt, A, D):
    """The definition, a position at a time (the tests' oracle): S [b,
    h, p, n], x [b, s, h, p], B / C [b, s, n], dt [b, s, h], A / D [h],
    float32 -> (S, y [b, s, h, p])."""
    def position(S, xs):
        x_t, B_t, C_t, dt_t = xs
        S = (S * jnp.exp(dt_t * A)[..., None, None]
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, None, None, :])
        y = jnp.sum(S * C_t[:, None, None, :], axis=-1)
        return S, y + D[:, None] * x_t

    S, y = jax.lax.scan(position, S, tuple(
        jnp.swapaxes(t, 0, 1) for t in (x, B, C, dt)))
    return S, jnp.swapaxes(y, 0, 1)


def ssd_chunk(S0, x, B, C, dt, A, D):
    """One chunk from an initial state, plain jax.numpy (the module
    docstring's matrix form): shapes as `ssm_scan`."""
    s = x.shape[1]
    L = jnp.cumsum(dt * A, axis=1)                         # [b, s, h]
    cb = jnp.einsum("btn,bun->btu", C, B, precision=_HIGHEST)
    causal = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    # (masked before the exp: above the diagonal L_t - L_u > 0)
    m = jnp.exp(jnp.where(causal, L[:, :, None] - L[:, None, :], -jnp.inf))
    dx = dt[..., None] * x
    y = jnp.einsum("btuh,buhp->bthp", cb[..., None] * m, dx,
                   precision=_HIGHEST)
    y = y + D[:, None] * x
    last = L[:, -1]
    fed = jnp.exp(last[:, None] - L)[..., None] * dx
    read = jnp.einsum("btn,bhpn->bthp", C, S0, precision=_HIGHEST)
    S1 = (jnp.exp(last)[..., None, None] * S0
          + jnp.einsum("buhp,bun->bhpn", fed, B, precision=_HIGHEST))
    return S1, y + jnp.exp(L)[..., None] * read


def ssd_chunked(S0, x, B, C, dt, A, D, chunk: int):
    """`ssd_chunk` over a sequence `chunk` positions at a time, the
    state carried over the boundaries by a scan; a last chunk that is
    not whole is padded with `dt = 0` positions, which move nothing."""
    b, s = x.shape[:2]
    if s <= chunk:
        return ssd_chunk(S0, x, B, C, dt, A, D)
    n = -(-s // chunk)

    def chunks(t):  # [b, s, ...] -> [n, b, chunk, ...]
        t = jnp.pad(t, ((0, 0), (0, n * chunk - s))
                    + ((0, 0),) * (t.ndim - 2))
        return jnp.swapaxes(t.reshape(b, n, chunk, *t.shape[2:]), 0, 1)

    S, y = jax.lax.scan(
        lambda S, xs: ssd_chunk(S, *xs, A, D), S0,
        tuple(chunks(t) for t in (x, B, C, dt)))
    return S, jnp.swapaxes(y, 0, 1).reshape(b, n * chunk, *y.shape[3:])[:, :s]


class Mamba2Mixer(Op):
    op_type = OperatorType.MAMBA2_MIXER
    float32_weights = ("A_log", "dt_bias", "D", "ssm_state")

    def __init__(self, params, inputs, name="", shard=None,
                 slot_state: bool = False):
        # must exist before Op.__init__ runs make_weight_specs
        self._slot_state = bool(slot_state)
        super().__init__(params, inputs, name=name,
                         shard=shard or ShardConfig())

    def ctor_kwargs(self) -> dict:
        return {"slot_state": True} if self._slot_state else {}

    def slot_state_entries(self):
        return ("conv_state", "ssm_state") if self._slot_state else ()

    def infer_output_shapes(self, input_shapes):
        (x,) = input_shapes
        p: Mamba2Params = self.params
        xd = [d for d in x.dims if not d.is_replica_dim]
        if len(xd) != 3 or xd[2].size != p.embed_dim:
            raise ShapeError(f"{self.name}: expect [batch, seq, "
                             f"{p.embed_dim}], got {x.logical_shape}")
        if xd[1].degree != 1 or xd[2].degree != 1 \
                or not self.shard.is_trivial():
            raise ShapeError(
                f"{self.name}: the recurrence is sharded over the batch "
                "only (heads over a model axis are not built yet)")
        if p.conv_kernel < 2:
            raise ShapeError(f"{self.name}: the conv needs a kernel of 2 "
                             f"or more (got {p.conv_kernel})")
        if self._slot_state and xd[0].degree != 1:
            raise ShapeError(f"{self.name}: per-slot state needs an "
                             "unsharded batch dim (slots are host-owned)")
        return [x]

    def num_trainable_weights(self) -> int:
        return 8

    def make_weight_specs(self, input_shapes):
        (x,) = input_shapes
        p: Mamba2Params = self.params
        slots = [d for d in x.dims if not d.is_replica_dim][0].size
        rep = ParallelDim(1, x.total_degree, is_replica_dim=True)

        def w(*sizes, dtype=x.dtype, replica=rep):
            return ParallelTensorShape(
                tuple(ParallelDim(s) for s in sizes) + (replica,), dtype)

        init, zero = DEFAULT_WEIGHT_INIT, ZeroInitializer()
        one = ConstantInitializer(1.0)
        e, h, f32 = p.embed_dim, p.num_heads, DataType.FLOAT
        specs = [
            WeightSpec("in_proj", w(e, p.d_inner + p.conv_dim + h), init),
            WeightSpec("conv1d", w(p.conv_dim, p.conv_kernel), init),
            WeightSpec("conv_bias", w(p.conv_dim), zero),
            WeightSpec("dt_bias", w(h, dtype=f32), one),
            WeightSpec("A_log", w(h, dtype=f32), zero),
            WeightSpec("D", w(h, dtype=f32), one),
            WeightSpec("norm", w(p.d_inner), one),
            WeightSpec("out_proj", w(p.d_inner, e), init),
        ]
        if not self._slot_state:
            return specs
        alone = ParallelDim(1, 1, is_replica_dim=True)
        return specs + [
            WeightSpec("conv_state", w(slots, p.conv_kernel - 1, p.conv_dim,
                                       replica=alone), zero),
            WeightSpec("ssm_state",
                       w(slots, h, p.head_dim, p.state_dim, dtype=f32,
                         replica=alone), zero),
            WeightSpec("row_tokens", w(slots, dtype=DataType.INT32,
                                       replica=alone), zero),
        ]

    def forward(self, inputs, weights, *, training=False, rng=None):
        (x,) = inputs
        p: Mamba2Params = self.params
        (w_in, conv_w, conv_b, dt_bias, a_log, skip, norm_w,
         w_out) = weights[:8]
        b, s = x.shape[:2]
        h, hp, n, di = p.num_heads, p.head_dim, p.state_dim, p.d_inner
        f32 = jnp.float32
        with scope("proj"):
            mixed = jnp.matmul(x, w_in)
            z, xbc = mixed[..., :di], mixed[..., di:di + p.conv_dim]
            # the step sizes again, in float32 (64 of 8,512 columns)
            dt = jnp.matmul(x, w_in[:, di + p.conv_dim:],
                            preferred_element_type=f32)
        with scope("conv"):
            if self._slot_state:
                tail, S, row_tokens = weights[8:]
                count = jnp.clip(row_tokens.reshape(b).astype(jnp.int32),
                                 0, s)
            else:
                tail = jnp.zeros((b, p.conv_kernel - 1, p.conv_dim),
                                 xbc.dtype)
                S = jnp.zeros((b, h, hp, n), f32)
                count = jnp.full((b,), s, jnp.int32)
            # causal depthwise conv over [the row's last K - 1 inputs |
            # the step's]: output t reads inputs t .. t + K - 1 of it
            window = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
            conv = jax.nn.silu(causal_depthwise_conv(window, conv_w, s)
                               + conv_b.astype(f32))
            # the window's K - 1 inputs that end at the row's last real one
            last = count[:, None] + jnp.arange(p.conv_kernel - 1,
                                               dtype=jnp.int32)
            new_tail = jnp.take_along_axis(window, last[..., None], axis=1)

        with scope("recurrence"):  # with what it is fed: x, B, C, dt
            xs = conv[..., :di].reshape(b, s, h, hp)
            B, C = conv[..., di:di + n], conv[..., di + n:]
            real = (jnp.arange(s, dtype=jnp.int32)[None, :]
                    < count[:, None])[..., None]  # [b, s, 1]
            dt = jnp.where(real, jax.nn.softplus(dt + dt_bias.astype(f32)),
                           0.0)
            A, D = -jnp.exp(a_log.astype(f32)), skip.astype(f32)
            S = S.astype(f32)
            if self._slot_state:
                S, y = ssd_chunk(S, xs, B, C, dt, A, D)
            else:
                S, y = ssd_chunked(S, xs, B, C, dt, A, D, p.chunk_size)
        with scope("out"):
            y = y.reshape(b, s, di) * jax.nn.silu(z.astype(f32))
            y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1,
                                           keepdims=True) + p.eps)
            out = jnp.matmul((y * norm_w.astype(f32)).astype(x.dtype), w_out)
            out = out.astype(x.dtype)
        if not self._slot_state:
            return [out]
        return [out, new_tail, S, row_tokens]

    def flops(self):
        """The four products (in, `C B^T` a position against the step's
        others is left to the step length: counted at one), the conv,
        and the recurrence: a position of a head decays S (a multiply),
        adds an outer product (a multiply and an add) and reads it by C
        (a multiply and an add): 5 operations an element of S."""
        p: Mamba2Params = self.params
        b, s, e = self.inputs[0].shape.logical_shape
        proj = 2.0 * e * (2 * p.d_inner + p.conv_dim + p.num_heads)
        cb = 2.0 * p.state_dim
        conv = 2.0 * p.conv_dim * p.conv_kernel
        rec = 5.0 * p.num_heads * p.head_dim * p.state_dim
        return b * s * (proj + cb + conv + rec)

    def dispatch_group(self):
        return "rstate" if self._slot_state else None

    @classmethod
    def dispatch_group_of(cls, ops, *, batch_slots, prefill_chunk,
                          state_bytes, **twin):
        """The recurrent state, under the names `GatedDeltaNet` counts
        it by: `rstate_rows_live`, the rows a dispatch had to advance,
        against `rstate_rows_touched`, the rows whose state the step
        program read and wrote: every slot, the plain form being the
        only one (`ssm_plain_ops` layers, `ssm_kernel_ops` 0)."""
        return rstate_group(
            ops, lambda op, s: False, "ssm", batch_slots=batch_slots,
            prefill_chunk=prefill_chunk, state_bytes=state_bytes)
