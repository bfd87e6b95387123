"""Jit'd public entry point for the routed experts' grouped matmul.

The caller names the backend:
  * "pallas"     — the TPU kernel, compiled (interpret=False)
  * "interpret"  — the TPU kernel body interpreted on CPU (validation)
  * "ref"        — XLA's grouped matmul (`lax.ragged_dot`, ref.py): the
                   path off the TPU kernel, the dry-run's included
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.moe_experts.kernel import TM
from repro.kernels.moe_experts.kernel import moe_experts as _pallas
from repro.kernels.moe_experts.ref import moe_experts_ref as _ref

BACKENDS = ("pallas", "interpret", "ref")


@functools.partial(jax.jit, static_argnames=("backend",))
def moe_experts(x, w_gate, w_up, w_down, group_sizes, layer=None, *,
                backend: str):
    """x: (m, d) rows sorted by expert; w_gate, w_up: (E, d, f); w_down:
    (E, f, d); group_sizes: (E,) int32, summing to at most m. With `layer`
    (an int32 index) the weights are stacked over layers, (L, E, ...), and
    that layer's are used. Returns (m, d) float32: each row through its
    group's gated SiLU expert, and 0 for the rows past the sum of the group
    sizes."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown moe-experts backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    if layer is None:
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
        layer = 0
    m = x.shape[0]
    if backend == "ref":
        return _ref(x, w_gate[layer], w_up[layer], w_down[layer],
                    group_sizes)
    tm = min(TM, -(-m // 16) * 16)
    pad = -m % tm
    out = _pallas(jnp.pad(x, ((0, pad), (0, 0))), w_gate, w_up, w_down,
                  group_sizes, layer, tm=tm,
                  interpret=backend == "interpret")[:m]
    # rows no group holds are never written by the kernel
    live = jnp.arange(m)[:, None] < jnp.sum(group_sizes)
    return jnp.where(live, out, 0.0)
