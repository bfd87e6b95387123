"""Model executors behind the engine.

RealExecutor   — actual JAX compute on one device against the paged pool
                 (dense/vlm/moe) or slot-dense caches (ssm/hybrid/audio).
                 On a TPU it runs the compiled Pallas kernels (paged
                 attention, and the moe family's routed experts); tests
                 and the CPU rehearsal name `ref` or `interpret`
                 instead. One replica holds one whole device: nothing is
                 sharded across chips yet.
SimExecutor    — no compute; the roofline cost model supplies step times and
                 the engine synthesises token ids. Used by the Table-1-scale
                 virtual-clock benchmarks (50 runs × 1000 concurrency would
                 be absurd to run with real compute on CPU).

Both return (logits | None, elapsed_seconds) so the engine is agnostic:
RealExecutor's elapsed is the host time its call took (`host_clock`), the
device's time included, SimExecutor's the roofline estimate.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro.config import HardwareConfig, ModelConfig
from repro.core.tracing import HOST_SPANS, host_clock
from repro.engine.costmodel import RooflineCost

try:  # jax only needed for RealExecutor
    import jax
    import jax.numpy as jnp
except ImportError:  # pragma: no cover
    jax = None


class SimExecutor:
    """Analytic executor: timing only."""

    needs_logits = False

    def __init__(self, cfg: ModelConfig, hw: HardwareConfig, tp: int = 1,
                 efficiency: float = 0.45):
        self.cfg = cfg
        self.cost = RooflineCost(cfg, hw, tp=tp, efficiency=efficiency)

    def step(self, prefills: list, decode: Optional[dict]):
        """Mixed step. Returns (prefill_logits, decode_logits, elapsed)."""
        new_tokens = ctx = 0
        for pf in prefills or ():
            start, end = pf["chunk"]
            new_tokens += end - start
            ctx += end
        batch = total_ctx = 0
        if decode is not None:
            batch = len(decode["slots"])
            total_ctx = int(sum(p + 1 for p in decode["pos"]))
        elapsed = self.cost.mixed_time(new_tokens, ctx, batch, total_ctx)
        return ([None] * len(prefills or ()), None, elapsed)


class RealExecutor:
    """Paged-pool JAX executor (dense / vlm / moe families)."""

    needs_logits = True

    def __init__(self, cfg: ModelConfig, params, num_blocks: int,
                 block_size: int, hw: HardwareConfig, tp: int = 1,
                 backend: Optional[str] = None, max_model_len: int = 4096,
                 max_slots: int = 64, device=None):
        """`params` must already live on `device` (default: the first
        device). `backend` None means the compiled Pallas kernel, which
        only a TPU device runs; elsewhere name `ref` or `interpret`."""
        from repro.engine import paged_model
        from repro.models import api
        self.device = device if device is not None else jax.devices()[0]
        if backend is None:
            if self.device.platform != "tpu":
                raise ValueError(
                    f"no compiled paged-attention kernel for "
                    f"{self.device.platform!r}; pass backend='ref' or "
                    f"backend='interpret'")
            backend = "pallas"
        self.replica = self.device.id
        self.cfg = cfg
        self.params = params
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.backend = backend
        self.cost = RooflineCost(cfg, hw, tp=tp)
        self.api = api

        def prefill(params, batch):
            return api.prefill_fn(params, cfg, batch, backend=backend)

        # one program per prompt length, traced and compiled on its first
        # call: run eagerly, the layer scan is traced, lowered and compiled
        # again on every prompt
        self._prefill_program = jax.jit(prefill)
        self.paged = cfg.family in ("dense", "vlm", "moe")
        self.max_model_len = max_model_len
        self.max_slots = max_slots
        if self.paged:
            # one block past the allocator's: the rows that pad a decode
            # batch write and read there and nowhere else
            self.pad_block = num_blocks
            self.pool = paged_model.init_pool(cfg, num_blocks + 1,
                                              block_size, device=self.device)
            self._paged_model = paged_model
            self.mb = -(-max_model_len // block_size)

            def decode_step(params, tokens, pos, pool, block_tables):
                return paged_model.decode_step(params, cfg, tokens, pos,
                                               pool, block_tables,
                                               backend=backend)

            # one program: the batch is padded to max_slots rows and the
            # tables to mb pages, so the step is traced and compiled once,
            # on its first call. The pool is donated: the new pool comes
            # back in the old one's buffer, and no second pool outlives it.
            self._decode_program = jax.jit(decode_step, donate_argnums=(3,))
        else:
            # state executor: one dense/state cache slab over all slots
            with jax.default_device(self.device):
                self.cache = api.init_cache(cfg, max_slots, max_model_len,
                                            dtype=jnp.float32)

    def _put(self, x):
        """Host ids/positions/block tables -> int32 on this device."""
        return jax.device_put(np.asarray(x, np.int32), self.device)

    # ------------------------------------------------------------------
    def step(self, prefills: list, decode: Optional[dict]):
        """Mixed step: decode batch first (pre-step KV state), then the
        prefill chunks. Elapsed is the host time of the call, which ends
        with every logit on the host, so it includes the device's."""
        t0 = host_clock()
        dec_logits = self._decode(decode) if decode else None
        pre_logits = [self._prefill(pf) for pf in prefills or ()]
        return pre_logits, dec_logits, host_clock() - t0

    def _prefill(self, pf: dict):
        if not pf["is_last"]:
            # chunked prefill: the whole prompt is computed once, on its
            # final chunk (numerically identical), which bears its time
            return None
        with HOST_SPANS.span("executor.prefill", replica=self.replica,
                             tokens=len(pf["token_ids"])):
            toks = self._put(pf["token_ids"])[None]
            logits, cache = self._prefill_program(self.params,
                                                  {"tokens": toks})
            if self.paged:
                bt = self._put(pf["block_table"])
                self.pool = self._paged_model.write_prefill(
                    self.pool, cache, bt, self.block_size)
            else:
                cache = self.api.pad_cache(self.cfg, cache,
                                           self.max_model_len)
                slot = pf["slot"]
                self.cache = jax.tree.map(
                    lambda slab, c: slab.at[:, slot].set(
                        c[:, 0].astype(slab.dtype)),
                    self.cache, cache)
            return np.asarray(logits[0])

    def _decode(self, dec: dict):
        """Logits of the batch's rows on the host. Two host spans: the
        dispatch (inputs built and put, and the compiled step enqueued; the
        first call traces and compiles it) and the fetch (waiting for the
        device, then the copy)."""
        n = len(dec["slots"])
        with HOST_SPANS.span("executor.decode.dispatch",
                             replica=self.replica, rows=n):
            logits, groups = self._dispatch_decode(dec)
        with HOST_SPANS.span("executor.decode.fetch", replica=self.replica,
                             rows=n) as span:
            # cut on the host: a slice on the device is one more program
            # for each batch size, lowered the first time the size comes
            out = np.asarray(logits)[:n]
            if groups is not None and HOST_SPANS.on:
                # the moe family's (layer, expert) group sizes: rows routed
                # over all layers, and the experts given at least one
                g = np.asarray(groups)
                span.set(moe_rows=int(g.sum()),
                         moe_active=int(np.count_nonzero(g)))
            return out

    def _dispatch_decode(self, dec: dict):
        slots, tokens, pos = dec["slots"], dec["tokens"], dec["pos"]
        if self.paged:
            # The batch is always max_slots rows, so a sequence's logits
            # come from one program whatever else is in the batch: on the
            # TPU a program compiled for another batch size rounds
            # differently, and greedy tokens then depend on the load.
            n = len(slots)
            if n > self.max_slots:
                raise ValueError(f"decode batch {n} > max_slots "
                                 f"{self.max_slots}")
            bt = np.full((self.max_slots, self.mb), self.pad_block, np.int32)
            for i, table in enumerate(dec["block_tables"]):
                bt[i, :len(table)] = table
            pad = [0] * (self.max_slots - n)
            # the moe family's step also returns its (layer, expert)
            # group sizes
            logits, self.pool, *groups = self._decode_program(
                self.params, self._put(list(tokens) + pad),
                self._put(list(pos) + pad), self.pool, self._put(bt))
            return logits, (groups[0] if groups else None)
        toks = self._put(tokens)
        posv = self._put(pos)
        # state executor: gather slot caches, run decode_fn, scatter back
        sl = self._put(slots)
        cache = jax.tree.map(lambda slab: slab[:, sl], self.cache)
        logits, cache = self.api.decode_fn(self.params, self.cfg, toks,
                                           cache, posv)
        self.cache = jax.tree.map(
            lambda slab, c: slab.at[:, sl].set(c.astype(slab.dtype)),
            self.cache, cache)
        return logits, None
