"""Plain-jnp grouped matmuls for the routed experts.

Layouts:
  x            : (m, d)     rows sorted by expert
  w_gate, w_up : (E, d, f)
  w_down       : (E, f, d)
  group_sizes  : (E,) int32 group e is the next group_sizes[e] rows

`moe_experts_ref` is XLA's grouped matmul (`lax.ragged_dot`), the `ref`
backend: on a TPU one native ragged dot over the routed rows; on the CPU
JAX computes it masked over every group. `moe_experts_oracle` computes
every expert for every row and keeps each row's own group: E times the
routed work, the oracle the kernel is tested against, never a serving
path. Rows may be wider than the weights (float32 rows over bfloat16
experts, as the moe family serves): `widened` then keeps the rows'
precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def widened(dot, x, w):
    """`dot(x, w)`, a float32 product, for rows `x` of a type wider than
    the weights `w`: x as the sum of three parts in w's type, the first
    x rounded, each next what is left, so that each part's product is
    exact and the sum holds x to float32's precision. Rows of w's own type
    are one part."""
    if x.dtype == w.dtype:
        return dot(x, w)
    y = 0.0
    for _ in range(3):
        part = x.astype(w.dtype)
        y = y + dot(part, w)
        x = x - part.astype(x.dtype)
    return y


def moe_experts_ref(x, w_gate, w_up, w_down, group_sizes):
    """(m, d) float32: row i the gated SiLU MLP of its group's expert, the
    hidden activation in the rows' type; rows past the sum of the group
    sizes are 0."""
    def grouped(a, w):
        return widened(lambda p, q: lax.ragged_dot(
            p, q, group_sizes, preferred_element_type=jnp.float32), a, w)

    h = jax.nn.silu(grouped(x, w_gate)) * grouped(x, w_up)
    return grouped(h.astype(x.dtype), w_down)


def moe_experts_oracle(x, w_gate, w_up, w_down, group_sizes):
    """What `moe_experts_ref` computes, one expert at a time over all
    rows."""
    m = x.shape[0]
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(m)[:, None]

    def expert(out, w):
        wg, wu, wd, start, end = w
        g = jnp.dot(x, wg, preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu, preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        y = jnp.dot(h, wd, preferred_element_type=jnp.float32)
        return out + jnp.where((row >= start) & (row < end), y, 0.0), None

    out, _ = lax.scan(expert, jnp.zeros((m, w_down.shape[2]), jnp.float32),
                      (w_gate, w_up, w_down, ends - group_sizes, ends))
    return out
