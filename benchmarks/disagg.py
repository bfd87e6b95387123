"""Unified vs disaggregated prefill/decode serving (repro.core.disagg).

Same fleet size, same mixed BurstGPT workload (long-prompt/short-output
document requests interleaved with short-prompt/long-output chat turns,
`repro.data.burstgpt.mixed_burst`), two deployment shapes:

* **unified**        — N replicas, every request lives on one instance
  (the paper's architecture; least-loaded routing).
* **disaggregated**  — the same N replicas split into a prefill pool and a
  decode pool behind the two-hop `DisaggregatedRouter`: prefill-only
  engines run each request to its first token and export the sealed KV
  blocks; decode-only engines import the handoff and stream the rest.

What disaggregation buys on this workload: a unified instance packs a
~2k-token prefill chunk into the same engine step as every decoding
sequence, so decode TBT degrades to prefill-chunk step times whenever
prompts are in flight, and prompts wait on decode-held slots; splitting
the phases isolates both. The cost is the KV transfer per request
(`KVHandoff.kv_bytes` over the deployment's transfer-bandwidth knob),
reported here per request.

Run: PYTHONPATH=src:. python benchmarks/disagg.py
"""
from __future__ import annotations

import numpy as np

from repro import configs
from repro.api import AdminClient, CompletionRequest, ServingClient
from repro.config import ServiceConfig
from repro.core.controller import ClusterSpec, ControlPlane
from repro.core.deployments import ModelDeploymentSpec
from repro.core.disagg import DisaggregationSpec
from repro.data.burstgpt import mixed_burst

from benchmarks.harness import ClientRecorder
from benchmarks.table1 import MAX_BATCHED_TOKENS, MODEL, NODE_CONFIGS


def build_plane(disaggregated: bool, total: int = 4, prefill: int = 2,
                node: str = "GPU-L",
                transfer_bandwidth: float = 40e9,
                sanitize: bool = False,
                services: ServiceConfig = None) -> ControlPlane:
    """One model, `total` replicas — either one unified pool or a
    prefill/decode split — deployed declaratively so the reconciler does
    the pool bring-up exactly as production would.  ``sanitize`` runs the
    plane on the TracingEventLoop (trace digest for determinism checks);
    ``services`` overrides the gateway `ServiceConfig` (e.g. tracing
    knobs)."""
    # paper hardware, repo engine shape: the TPU-adapted static decode
    # batch (max_num_seqs=64, scheduler.py) is where decode residency
    # actually gates prompt admission — the contention disaggregation
    # removes.  KV sized to hold a full decode batch of mixed-length
    # sequences (64 x ~2k tokens).
    node_cfg = NODE_CONFIGS[node]
    spec = ClusterSpec(num_nodes=total, gpus_per_node=node_cfg["tp"],
                       hardware=node_cfg["hardware"],
                       num_blocks=4096, block_size=32, max_num_seqs=64,
                       max_model_len=16_384,
                       max_prefill_tokens=MAX_BATCHED_TOKENS,
                       sanitize=sanitize,
                       services=services or ServiceConfig())

    from repro.engine.engine import LLMEngine
    from repro.engine.executor import SimExecutor

    def factory(cfg, tp, gpu):
        ex = SimExecutor(cfg, node_cfg["hardware"], tp=node_cfg["tp"],
                         efficiency=node_cfg["efficiency"])
        return LLMEngine(cfg, ex, num_blocks=spec.num_blocks,
                         block_size=spec.block_size,
                         max_num_seqs=spec.max_num_seqs,
                         max_prefill_tokens=spec.max_prefill_tokens,
                         max_model_len=spec.max_model_len)

    # fixed fleet: no alert rules, both shapes run on identical capacity
    cp = ControlPlane(spec, engine_factory=factory, alert_rules=[])
    cp.add_tenant("bench", "sk-bench")
    cp.register_model(configs.get(MODEL))
    admin = AdminClient(cp)
    if disaggregated:
        decode = total - prefill
        dspec = ModelDeploymentSpec(
            model=MODEL, replicas=total, max_replicas=total,
            routing_policy="least_loaded",     # within-pool choice
            gpus_per_node=node_cfg["tp"], est_load_time=60.0,
            disaggregation=DisaggregationSpec(
                prefill_replicas=prefill, decode_replicas=decode,
                max_prefill_replicas=prefill, max_decode_replicas=decode,
                transfer_bandwidth=transfer_bandwidth))
    else:
        dspec = ModelDeploymentSpec(
            model=MODEL, replicas=total, max_replicas=total,
            routing_policy="least_loaded",
            gpus_per_node=node_cfg["tp"], est_load_time=60.0)
    admin.apply(dspec)
    cp.run_until(300.0)          # pool bring-up (reconciler-paced)
    ready = cp.ready_endpoints(MODEL)
    assert len(ready) == total, f"{len(ready)}/{total} instances came up"
    return cp


def run_scenario(mode: str, n: int, seed: int = 0, total: int = 4,
                 prefill: int = 2, node: str = "GPU-L",
                 sanitize: bool = False) -> dict:
    cp = build_plane(mode == "disaggregated", total=total, prefill=prefill,
                     node=node, sanitize=sanitize)
    client = ServingClient(cp, api_key="sk-bench")
    # warm the gateway auth cache (paper does the same before measuring)
    client.completions(model=MODEL, prompt=[1] * 8, max_tokens=1,
                       target_output_len=1).result(max_wait=60.0)
    wl = mixed_burst(n, seed=seed)
    rec = ClientRecorder()
    t0 = cp.loop.now
    streams = [client.completions(
        CompletionRequest.from_engine(r, MODEL, stream=True))
        for r in wl.requests]
    for s in streams:
        rec.track(s, t0)
    cp.loop.run_while(lambda: any(not s.closed for s in streams),
                      max_t=t0 + 7200.0)
    out = rec.summary()
    # per-request KV transfer overhead (zero for every unified request)
    transfer = np.array([s.req.metrics.kv_transfer_time for s in streams])
    out.update(
        mode=mode, concurrency=n,
        failed=sum(1 for s in streams if s.error is not None),
        transfer_mean_ms=float(transfer.mean() * 1e3),
        transfer_p99_ms=float(np.percentile(transfer, 99) * 1e3),
        transfer_total_s=float(transfer.sum()),
        handoffs=cp.web_gateway.stats.handoffs,
        router=cp.web_gateway.router_stats(),
    )
    if sanitize:
        out["trace_digest"] = cp.loop.trace_digest()
        out["events_run"] = cp.loop.events_run
        # span forests are derived purely from loop-timed callbacks, so
        # twin runs must agree on them exactly as they do on the event
        # digest (tests/test_determinism.py)
        out["span_forest_digest"] = cp.tracer.forest_digest()
    return out


def run_comparison(concurrencies=(100, 500, 1000), seed: int = 0,
                   total: int = 4, prefill: int = 2) -> list[dict]:
    rows = []
    for n in concurrencies:
        for mode in ("unified", "disaggregated"):
            row = run_scenario(mode, n, seed=seed, total=total,
                               prefill=prefill)
            rows.append(row)
            print(f"n={n:5d} {mode:14s} "
                  f"ttft p50={row['ttft_median_ms']:9.1f} "
                  f"p99={row['ttft_p99_ms']:9.1f}ms | "
                  f"tbt p50={row['tpot_median_ms']:7.2f} "
                  f"p99={row['tpot_p99_ms']:7.2f}ms | "
                  f"e2e p50={row['e2el_median_ms']:9.1f} "
                  f"p99={row['e2el_p99_ms']:9.1f}ms | "
                  f"xfer={row['transfer_mean_ms']:6.2f}ms/req")
    return rows


if __name__ == "__main__":
    run_comparison()
