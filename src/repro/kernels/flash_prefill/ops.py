"""Jit'd public entry point for flash prefill (the caller names the
backend, as in paged_attention.ops)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_prefill.kernel import flash_prefill as _pallas
from repro.kernels.flash_prefill.ref import flash_prefill_ref as _ref

BACKENDS = ("pallas", "interpret", "ref")


@functools.partial(jax.jit, static_argnames=("window", "backend"))
def flash_prefill(q, k, v, window: int = 0, *, backend: str):
    if backend == "pallas":
        return _pallas(q, k, v, window=window, interpret=False)
    if backend == "interpret":
        return _pallas(q, k, v, window=window, interpret=True)
    if backend == "ref":
        return _ref(q, k, v, window=window)
    raise ValueError(f"unknown flash-prefill backend {backend!r}; "
                     f"expected one of {BACKENDS}")
