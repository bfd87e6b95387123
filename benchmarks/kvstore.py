"""Hierarchical KV store + chunked handoff streaming (repro.core.kvstore).

Three experiments over the same fleet shapes as benchmarks/disagg.py:

* **chunked vs atomic handoff** — disaggregated prefill/decode serving on
  the mixed BurstGPT workload at the paper's 500/1000 concurrencies, with
  the prefill->decode KV payload moved either atomically (PR 4 behaviour,
  ``stream_chunks=1``: decode waits for the whole payload) or in chunks
  (``stream_chunks=8``: decode dispatches after the FIRST chunk lands,
  the rest stream behind it through the shared-NIC contention model).
  Chunking overlaps transfer with decode compute, cutting TBT/TTFT tails.
* **tiered vs discard eviction** — unified serving of an agent-pipeline
  workload on engines whose HBM is deliberately too small: with
  ``KVStoreSpec`` tiers, eviction demotes sealed blocks to host DRAM /
  the cluster-shared store and ``match_prefix`` misses promote them back,
  lifting the prefix hit rate over plain discard eviction.
* **workflow affinity** — the same agent-pipeline workload routed with
  ``workflow_affinity`` (all stages of a workflow pinned to the instance
  already holding its transcript KV) vs plain least-loaded scatter.

Run: PYTHONPATH=src:. python benchmarks/kvstore.py
"""
from __future__ import annotations

import numpy as np

from repro import configs
from repro.api import AdminClient, CompletionRequest, ServingClient
from repro.core.controller import ClusterSpec, ControlPlane
from repro.core.deployments import ModelDeploymentSpec
from repro.core.disagg import DisaggregationSpec
from repro.core.kvstore import KVStoreSpec
from repro.data.burstgpt import agent_pipeline, mixed_burst

from benchmarks.harness import ClientRecorder
from benchmarks.table1 import MAX_BATCHED_TOKENS, MODEL, NODE_CONFIGS


def build_plane(total: int = 4, prefill: int = 0, node: str = "GPU-L",
                routing_policy: str = "least_loaded",
                stream_chunks: int = 8,
                kv_store: KVStoreSpec = None,
                num_blocks: int = 4096,
                transfer_bandwidth: float = 40e9,
                sanitize: bool = False) -> ControlPlane:
    """One declaratively deployed model, `total` replicas.  ``prefill > 0``
    selects the disaggregated two-pool shape (with the chunked-handoff
    knob); ``kv_store`` hangs host/shared tiers off every engine;
    ``num_blocks`` shrinks HBM to force eviction pressure."""
    node_cfg = NODE_CONFIGS[node]
    spec = ClusterSpec(num_nodes=total, gpus_per_node=node_cfg["tp"],
                       hardware=node_cfg["hardware"],
                       num_blocks=num_blocks, block_size=32,
                       max_num_seqs=64, max_model_len=16_384,
                       max_prefill_tokens=MAX_BATCHED_TOKENS,
                       sanitize=sanitize)

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

    cp = ControlPlane(spec, engine_factory=factory, alert_rules=[])
    cp.add_tenant("bench", "sk-bench")
    cp.register_model(configs.get(MODEL))
    admin = AdminClient(cp)
    dis = None
    if prefill > 0:
        dis = DisaggregationSpec(
            prefill_replicas=prefill, decode_replicas=total - prefill,
            max_prefill_replicas=prefill,
            max_decode_replicas=total - prefill,
            transfer_bandwidth=transfer_bandwidth,
            stream_chunks=stream_chunks)
    admin.apply(ModelDeploymentSpec(
        model=MODEL, replicas=total, max_replicas=total,
        routing_policy=routing_policy, gpus_per_node=node_cfg["tp"],
        est_load_time=60.0, disaggregation=dis, kv_store=kv_store))
    cp.run_until(300.0)
    ready = cp.ready_endpoints(MODEL)
    assert len(ready) == total, f"{len(ready)}/{total} instances came up"
    return cp


def _drive(cp: ControlPlane, wl, rec: ClientRecorder) -> list:
    """Dispatch a workload at its arrival offsets and run it to drain."""
    client = ServingClient(cp, api_key="sk-bench")
    client.completions(model=MODEL, prompt=[1] * 8, max_tokens=1,
                       target_output_len=1).result(max_wait=60.0)
    t0 = cp.loop.now
    streams = []

    def fire(r):
        s = client.completions(
            CompletionRequest.from_engine(r, MODEL, stream=True))
        rec.track(s, cp.loop.now)
        streams.append(s)

    for r, a in zip(wl.requests, wl.arrivals):
        cp.loop.call_after(a, lambda r=r: fire(r))
    cp.loop.run_while(
        lambda: len(streams) < len(wl.requests)
        or any(not s.closed for s in streams), max_t=t0 + 7200.0)
    return streams


def _kv_counters(cp: ControlPlane) -> dict:
    """Fleet-level prefix/tier counters from the engines themselves (the
    same numbers the MetricsGateway folds into its per-config series)."""
    out = {"prefix_queries": 0, "prefix_hits": 0, "demotions": 0,
           "promotions": 0, "host_hits": 0, "shared_hits": 0}
    for inst in cp.instances_spawned:
        alloc = inst.engine.allocator
        out["prefix_queries"] += alloc.prefix_queries
        out["prefix_hits"] += alloc.prefix_hits
        ts = alloc.tier_store
        if ts is not None:
            out["demotions"] += ts.demotions
            out["promotions"] += ts.promotions
            out["host_hits"] += ts.host_hits
            out["shared_hits"] += ts.shared_hits
    out["prefix_hit_rate"] = out["prefix_hits"] \
        / max(out["prefix_queries"], 1)
    return out


def run_handoff(n: int, stream_chunks: int, seed: int = 0,
                total: int = 4, prefill: int = 2,
                transfer_bandwidth: float = 5e9) -> dict:
    """NIC-class default bandwidth (5 GB/s ~ 40 GbE): at the paper
    concurrencies hundreds of handoffs contend for the link, so the
    transfer leg is a material part of the first->second token gap — the
    regime chunking is for.  (NVLink-class 40e9 makes the leg negligible
    either way and the comparison a wash.)"""
    cp = build_plane(total=total, prefill=prefill,
                     stream_chunks=stream_chunks,
                     transfer_bandwidth=transfer_bandwidth)
    rec = ClientRecorder()
    streams = _drive(cp, mixed_burst(n, seed=seed), rec)
    out = rec.summary()
    transfer = np.array([s.req.metrics.kv_transfer_time for s in streams])
    out.update(
        mode="chunked" if stream_chunks > 1 else "atomic",
        stream_chunks=stream_chunks, concurrency=n,
        failed=sum(1 for s in streams if s.error is not None),
        transfer_mean_ms=float(transfer.mean() * 1e3),
        handoffs=cp.web_gateway.stats.handoffs,
        kv_links=cp.web_gateway.router_stats().get("kv_links", {}),
    )
    return out


def run_tiering(n_workflows: int, tiered: bool, seed: int = 0,
                num_blocks: int = 256, sanitize: bool = False) -> dict:
    """Unified fleet with deliberately tight HBM: the agent-pipeline
    transcripts don't all fit, so eviction either discards (baseline) or
    demotes into host/shared tiers (``tiered``)."""
    kspec = KVStoreSpec() if tiered else None
    cp = build_plane(total=4, routing_policy="workflow_affinity",
                     kv_store=kspec, num_blocks=num_blocks,
                     sanitize=sanitize)
    rec = ClientRecorder()
    wl = agent_pipeline(n_workflows, seed=seed)
    streams = _drive(cp, wl, rec)
    out = rec.summary()
    out.update(mode="tiered" if tiered else "hbm_only",
               n_workflows=n_workflows, requests=len(streams),
               failed=sum(1 for s in streams if s.error is not None),
               **_kv_counters(cp))
    # the per-tier series the MetricsGateway scraped along the way
    cfg_ids = [c["id"] for c
               in cp.db["ai_model_configurations"].rows.values()]
    if cfg_ids:
        series = cp.metrics_gateway.series(cfg_ids[0],
                                           "kv_promotions_total", 0.0)
        out["scraped_promotion_samples"] = len(series)
    if sanitize:
        out["trace_digest"] = cp.loop.trace_digest()
        out["events_run"] = cp.loop.events_run
    return out


def run_affinity(n_workflows: int, policy: str, seed: int = 0) -> dict:
    cp = build_plane(total=4, routing_policy=policy)
    rec = ClientRecorder()
    streams = _drive(cp, agent_pipeline(n_workflows, seed=seed), rec)
    out = rec.summary()
    out.update(mode=policy, n_workflows=n_workflows,
               failed=sum(1 for s in streams if s.error is not None),
               **_kv_counters(cp))
    return out


def run_comparison(seed: int = 0) -> list[dict]:
    rows = []
    print("== chunked vs atomic handoff (disaggregated, mixed burst) ==")
    for n in (500, 1000):
        for chunks in (1, 8):
            row = run_handoff(n, chunks, seed=seed)
            rows.append(row)
            print(f"n={n:5d} {row['mode']:8s} "
                  f"ttft p99={row['ttft_p99_ms']:9.1f}ms | "
                  f"tbt p50={row['tpot_median_ms']:7.2f} "
                  f"p99={row['tpot_p99_ms']:7.2f}ms | "
                  f"xfer={row['transfer_mean_ms']:6.2f}ms/req")
    print("== tiered vs discard eviction (agent pipeline, tight HBM) ==")
    for tiered in (False, True):
        row = run_tiering(48, tiered, seed=seed)
        rows.append(row)
        print(f"{row['mode']:9s} prefix_hit_rate={row['prefix_hit_rate']:.3f} "
              f"promotions={row['promotions']:5d} "
              f"host_hits={row['host_hits']:5d} "
              f"shared_hits={row['shared_hits']:5d} | "
              f"ttft p50={row['ttft_median_ms']:8.1f}ms")
    print("== workflow affinity vs scatter (agent pipeline) ==")
    for policy in ("least_loaded", "workflow_affinity"):
        row = run_affinity(48, policy, seed=seed)
        rows.append(row)
        print(f"{policy:18s} ttft p50={row['ttft_median_ms']:8.1f} "
              f"p99={row['ttft_p99_ms']:8.1f}ms | "
              f"prefix_hit_rate={row['prefix_hit_rate']:.3f}")
    return rows


if __name__ == "__main__":
    run_comparison()
