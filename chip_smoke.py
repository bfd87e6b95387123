#!/usr/bin/env python3
"""Smoke run of the served path on the TPU.

    python chip_smoke.py                  # one full-width qwen3-1.7b replica
    python chip_smoke.py --chips 4        # four replicas behind the router
    python chip_smoke.py --cpu-rehearsal  # reduced config, interpreted kernels

The default mode sends 8 greedy chat completions through ServingClient ->
Web Gateway -> router -> scheduler -> paged KV -> RealExecutor -> Pallas
paged attention, on a deployment applied through AdminClient and converged
by the Reconciler onto the simulated Slurm cluster. It then checks that
every request finished, that the decode program holds the compiled kernel,
that the kernel agrees with the jnp reference on the served pool's shapes,
and that the served first decode step's logits agree with the dense-cache
model (`api.prefill_fn` / `api.decode_fn`).

`--chips 4` runs only the replica path: four one-chip replicas, one per
device, under one deployment, against one replica on the same requests.

Weights are random from --seed. Every check raises on failure; without a
TPU the script exits non-zero unless --cpu-rehearsal is given. The last line
of stdout is {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-1.7b"
# a handful of distinct prompt lengths, all far below the 2048-token limit
# of the dense prefill path (models/common.py chunked_causal_mha)
PROMPT_LENS = (24, 64, 130, 256, 400, 24, 64, 130)
MAX_TOKENS = 16
# Pallas vs jnp reference on unit-normal inputs: the bound of one bf16 MXU
# pass on f32 operands (~2^-9 relative per product, outputs |o| <~ 4)
KERNEL_TOL = 3e-2
# served vs dense-cache logits, relative to the largest dense logit. The two
# paths differ only in attention arithmetic (f32 Pallas over an f32 pool vs
# bf16 einsums over a bf16 cache); with bf16 parameters and 28 layers that
# leaves ~2e-2 (CPU, d_model 512-1024), while a wrong position or a null
# KV block moves the logits by >= 0.18
LOGITS_RTOL = 6e-2


def log(msg: str):
    print(msg, flush=True)


class FirstDecodeTap:
    """Executor proxy that keeps the first decode step's rows: each
    sequence's prompt, the token it fed, its position and the served
    logits. Everything else goes to the wrapped executor."""

    def __init__(self, engine):
        self.inner = engine.executor
        self.engine = engine
        self.rows = None

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def step(self, prefills, decode):
        pre, dec, elapsed = self.inner.step(prefills, decode)
        if decode and self.rows is None:
            req_at = {s.slot: s.req for s in self.engine.scheduler.running}
            self.rows = [
                (list(req_at[slot].prompt_tokens), tok, pos, dec[i])
                for i, (slot, tok, pos) in enumerate(
                    zip(decode["slots"], decode["tokens"], decode["pos"]))]
        return pre, dec, elapsed


def serve(cfg, factory, devices, replicas, prompts, hw):
    """Apply one deployment of `replicas` replicas on a one-node cluster
    with one GPU slot per device, send every prompt as a greedy chat
    completion and wait for all of them. Returns (plane, per-request output
    tokens, set-up seconds, request-phase seconds)."""
    from repro.api import AdminClient, ChatMessage, ServingClient
    from repro.core.controller import ClusterSpec, ControlPlane

    t0 = time.perf_counter()
    cp = ControlPlane(ClusterSpec(num_nodes=1, gpus_per_node=len(devices),
                                  hardware=hw),
                      engine_factory=factory, alert_rules=[])
    cp.add_tenant("smoke", "sk-smoke")
    cp.register_model(cfg)
    admin = AdminClient(cp)
    admin.apply(model=cfg.name, replicas=replicas, min_replicas=replicas,
                max_replicas=replicas, est_load_time=30.0)
    admin.wait(cfg.name, "Ready", timeout=600.0)
    ready = cp.ready_endpoints(cfg.name)
    if len(ready) != replicas:
        raise RuntimeError(f"{len(ready)}/{replicas} replicas ready")
    setup_s = time.perf_counter() - t0

    client = ServingClient(cp, api_key="sk-smoke", default_model=cfg.name)
    t1 = time.perf_counter()
    streams = [client.chat(messages=[ChatMessage("user", p)],
                           temperature=0.0, max_tokens=MAX_TOKENS,
                           session_id=f"smoke-{i}", stream=True)
               for i, p in enumerate(prompts)]
    cp.loop.run_while(lambda: not all(s.closed for s in streams),
                      max_t=cp.loop.now + 3600.0)
    request_s = time.perf_counter() - t1
    outs = []
    for i, s in enumerate(streams):
        if not s.ok:
            raise RuntimeError(f"request {i} failed: {s.error}")
        tokens = s.output_tokens
        if len(tokens) != MAX_TOKENS:
            raise RuntimeError(f"request {i}: {len(tokens)} tokens, "
                               f"expected {MAX_TOKENS}")
        outs.append(list(tokens))
    log(f"requests: {len(outs)}/{len(prompts)} finished through "
        f"ServingClient; prompt tokens {[len(p) for p in prompts]}, "
        f"completion tokens {[len(o) for o in outs]}")
    return cp, outs, setup_s, request_s


def tree_bytes(tree) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def check_kernel(cfg, ex, seed):
    """Pallas (or its interpreter) vs the jnp reference on one layer of the
    replica's pool shape and decode batch: random unit-normal q/k/v, random
    tables and lengths."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_attention import ops as pa_ops

    rng = np.random.default_rng(seed)
    nb, kv, bs, d = ex.pool["k"].shape[1:]
    s, mb, h = ex.max_slots, ex.mb, cfg.num_heads
    backend, device = ex.backend, ex.device

    def put(x, dtype=np.float32):
        return jax.device_put(np.asarray(x, dtype), device)

    q = put(rng.normal(size=(s, h, d)))
    pk = put(rng.normal(size=(nb, kv, bs, d)))
    pv = put(rng.normal(size=(nb, kv, bs, d)))
    bt = put(rng.integers(1, nb, size=(s, mb)), np.int32)
    lens = put(rng.integers(1, mb * bs + 1, size=(s,)), np.int32)
    out = pa_ops.paged_attention(q, pk, pv, bt, lens, backend=backend)
    with jax.default_matmul_precision("highest"):
        ref = pa_ops.paged_attention(q, pk, pv, bt, lens, backend="ref")
    err = float(jnp.max(jnp.abs(out - ref)))
    log(f"kernel: {backend} vs ref paged attention on pool "
        f"{tuple(pk.shape)}, S={s}, MB={mb}: max|diff| {err!r} "
        f"(tolerance {KERNEL_TOL})")
    if not err <= KERNEL_TOL:
        raise RuntimeError(f"paged attention {backend} vs ref: {err}")


def check_decode_program(ex):
    """Lower the replica's own decode program with its own arrays and look
    for the Mosaic kernel in it."""
    import jax
    import numpy as np

    s = ex.max_slots
    ids = jax.device_put(np.zeros((s,), np.int32), ex.device)
    bt = jax.device_put(np.zeros((s, ex.mb), np.int32), ex.device)
    text = ex._decode_program.lower(ex.params, ids, ids, ex.pool,
                                    bt).as_text()
    found = "tpu_custom_call" in text
    log(f"decode backend: {ex.backend}; tpu_custom_call in the lowered "
        f"decode step: {found}")
    if ex.device.platform == "tpu" and not (ex.backend == "pallas"
                                            and found):
        raise RuntimeError("decode step does not run the Pallas kernel")


def check_logits(cfg, params, rows, max_model_len):
    """Served first-decode-step logits vs the dense-cache model on the same
    prompts, fed tokens and positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import api

    if not rows:
        raise RuntimeError("no decode step was recorded")
    prefill = jax.jit(lambda p, t: api.prefill_fn(p, cfg, {"tokens": t}))
    decode = jax.jit(lambda p, t, c, pos: api.decode_fn(p, cfg, t, c, pos))
    worst = worst_rel = 0.0
    for prompt, tok, pos, served in rows:
        _, cache = prefill(params, jnp.asarray([prompt], jnp.int32))
        cache = api.pad_cache(cfg, cache, max_model_len)
        logits, _ = decode(params, jnp.asarray([tok], jnp.int32), cache,
                           jnp.asarray([pos], jnp.int32))
        dense = np.asarray(logits[0], np.float32)
        err = float(np.max(np.abs(np.asarray(served, np.float32) - dense)))
        rel = err / float(np.max(np.abs(dense)))
        worst, worst_rel = max(worst, err), max(worst_rel, rel)
    log(f"logits: served vs dense first decode step over {len(rows)} "
        f"sequences: max|diff| {worst!r}, relative to max|dense| "
        f"{worst_rel!r} (tolerance {LOGITS_RTOL})")
    if not worst_rel <= LOGITS_RTOL:
        raise RuntimeError(f"served vs dense logits: relative {worst_rel}")


def one_chip(args, cfg, devices, hw, backend, prompts):
    from repro.engine.factory import real_engine_factory

    dev = devices[0]
    base = real_engine_factory(cfg, devices[:1], hw=hw, backend=backend,
                               seed=args.seed)
    taps = []

    def factory(c, tp, gpu):
        eng = base(c, tp, gpu)
        taps.append(FirstDecodeTap(eng))
        eng.executor = taps[-1]
        return eng

    cp, _, setup_s, request_s = serve(cfg, factory, devices[:1], 1,
                                      prompts, hw)
    ex = taps[0].inner
    dtypes = sorted({str(x.dtype) for x in jax_leaves(ex.params)})
    log(f"params {tree_bytes(ex.params)} B {dtypes}, KV pool "
        f"{tree_bytes(ex.pool)} B "
        f"{tuple(ex.pool['k'].shape)} {ex.pool['k'].dtype}")
    check_kernel(cfg, ex, args.seed)
    check_decode_program(ex)
    check_logits(cfg, ex.params, taps[0].rows, ex.max_model_len)
    cp.shutdown()
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", "not reported")
    log(f"info only (not a benchmark): set-up {setup_s!r} s incl. weight "
        f"init and compile; request phase {request_s!r} s wall; device "
        f"peak_bytes_in_use {peak}")


def four_chips(args, cfg, devices, hw, backend, prompts):
    from repro.engine.factory import real_engine_factory

    factory = real_engine_factory(cfg, devices[:4], hw=hw, backend=backend,
                                  seed=args.seed)
    cp, outs4, setup4, req4 = serve(cfg, factory, devices[:4], 4, prompts, hw)
    placed = []
    for inst in cp.registry.values():
        ex = inst.engine.executor
        held = {d for x in jax_leaves(ex.params, ex.pool)
                for d in x.devices()}
        if held != {ex.device}:
            raise RuntimeError(f"replica on {ex.device} holds arrays on "
                               f"{held}")
        placed.append(ex.device)
        log(f"replica {inst.node}:{inst.port} on device {ex.device.id} "
            f"served {inst.engine.metrics.requests_finished} requests")
    if len(set(placed)) != 4:
        raise RuntimeError(f"replicas share devices: {placed}")
    cp.shutdown()
    del cp

    # one replica on device 0, same factory (its params are already there)
    cp1, outs1, setup1, req1 = serve(cfg, factory, devices[:1], 1, prompts,
                                     hw)
    cp1.shutdown()
    same = [a == b for a, b in zip(outs4, outs1)]
    log(f"tokens: 4 replicas vs 1 replica identical for "
        f"{sum(same)}/{len(same)} requests")
    if not all(same):
        raise RuntimeError("4-replica tokens differ from 1 replica")
    log(f"info only (not a benchmark): 4 replicas set-up {setup4!r} s, "
        f"requests {req4!r} s; 1 replica set-up {setup1!r} s, "
        f"requests {req1!r} s")


def jax_leaves(*trees):
    import jax
    return [x for t in trees for x in jax.tree.leaves(t)]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="reduced config and interpreted kernels on the CPU "
                         "(no chip; proves control flow only)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    if args.cpu_rehearsal:
        jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from repro import configs
    from repro.engine.factory import serving_setup
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if not args.cpu_rehearsal and dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {dev.platform!r}); "
                         f"use --cpu-rehearsal to rehearse on the CPU")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but "
                         f"{len(devices)} devices")
    log(f"device: {dev.platform} {dev.device_kind!r} x{len(devices)}; "
        f"compile cache {enable_compile_cache()}")

    cfg, hw, backend = serving_setup(configs.get(ARCH), dev,
                                     cpu_rehearsal=args.cpu_rehearsal)
    log(f"model: {cfg.name} {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.num_heads} heads / {cfg.num_kv_heads} KV heads x "
        f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"{cfg.param_dtype} params ({cfg.num_params()} parameters)")
    rng = np.random.default_rng(args.seed)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, size=n)]
               for n in PROMPT_LENS]

    run = four_chips if args.chips == 4 else one_chip
    run(args, cfg, devices, hw, backend, prompts)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
