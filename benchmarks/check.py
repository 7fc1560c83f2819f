"""The comparisons that decide ``correct``.

Every number compared is a statistic over a whole tensor or over
thousands of positions, so that it barely moves with the seed:

* ``rel_l2``: ``||got - want||_2 / ||want||_2`` over everything in a
  group of tensors (hundreds of millions of numbers for a gradient);
* ``mean_regret``: for served tokens, the reference's largest logit
  minus the reference's logit of the token the server chose, in units
  of that position's logit standard deviation, averaged over every
  sampled position.  A rounding tie costs almost nothing, a lower
  precision costs a multiple, a wrong cache page costs orders more.

Never one scalar loss, never a largest element, never token equality;
nothing about time, compile counts or whether a loss fell.  The limits
are measured on the chip and written in the configuration's file
(``tolerance``): `verdict` only compares.  A statistic the file asks
for and the run could not produce is a failure, never a skip.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


@jax.jit
def _sq_sums(got, want):
    """Per leaf: (sum (got - want)^2, sum want^2), float32 on device."""
    return jax.tree.map(
        lambda g, w: jnp.stack([jnp.sum(jnp.square(g - w)),
                                jnp.sum(jnp.square(w))]),
        got, want)


def group_rel_l2(got, want, groups) -> dict:
    """{group: rel_l2} where ``groups`` maps a group name to the
    top-level (or ``layers/<name>``) entries of the tree it covers."""
    sums = jax.device_get(_sq_sums(got, want))
    flat = dict(sums.get("layers", {}))
    flat.update({k: v for k, v in sums.items() if k != "layers"})
    out = {}
    for group, names in groups.items():
        num = sum(float(x[0]) for n in names
                  for x in jax.tree.leaves(flat[n]))
        den = sum(float(x[1]) for n in names
                  for x in jax.tree.leaves(flat[n]))
        out[group] = math.sqrt(num / den) if den > 0 else math.inf
    return out


@jax.jit
def position_regret(ref_logits, chosen):
    """ref_logits [n, vocab] float32, chosen [n] token ids -> [n]
    regrets in units of each position's logit standard deviation."""
    top = jnp.max(ref_logits, axis=-1)
    took = jnp.take_along_axis(ref_logits, chosen[:, None], axis=-1)[:, 0]
    return (top - took) / jnp.std(ref_logits, axis=-1)


def verdict(stats: dict, tolerance: dict, out=print) -> bool:
    """Print each number beside its limit; True when every statistic
    the configuration lists is present, finite and within its limit."""
    ok = True
    for name in sorted(tolerance):
        limit = float(tolerance[name]["limit"])
        value = stats.get(name)
        good = (value is not None and math.isfinite(value)
                and value <= limit)
        ok &= good
        shown = "missing" if value is None else f"{value:.6g}"
        out(f"check {name}: {shown} (limit {limit:.6g}) "
            f"{'ok' if good else 'NOT OK'}")
    for name in sorted(set(stats) - set(tolerance)):
        out(f"check {name}: {stats[name]:.6g} (reported, decides nothing)")
    return ok
