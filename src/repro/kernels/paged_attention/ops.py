"""Jit'd public entry point for paged attention.

The caller names the backend; there is no default:
  * "pallas"     — the TPU kernel, compiled (interpret=False)
  * "interpret"  — the TPU kernel body interpreted on CPU (validation)
  * "ref"        — pure-jnp oracle (also the XLA path used by the multi-pod
                   dry-run, where Pallas cannot lower to the CPU backend)
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.paged_attention.kernel import paged_attention as _pallas
from repro.kernels.paged_attention.ref import paged_attention_ref as _ref

BACKENDS = ("pallas", "interpret", "ref")


@functools.partial(jax.jit, static_argnames=("backend",))
def paged_attention(q, pool_k, pool_v, block_tables, context_lens, *,
                    backend: str):
    if backend == "pallas":
        return _pallas(q, pool_k, pool_v, block_tables, context_lens,
                       interpret=False)
    if backend == "interpret":
        return _pallas(q, pool_k, pool_v, block_tables, context_lens,
                       interpret=True)
    if backend == "ref":
        return _ref(q, pool_k, pool_v, block_tables, context_lens)
    raise ValueError(f"unknown paged-attention backend {backend!r}; "
                     f"expected one of {BACKENDS}")
