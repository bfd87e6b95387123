"""Multi-tenant QoS: weighted fair queuing vs single FIFO under a skewed
two-tenant BurstGPT mix (repro.core.tenancy, docs/tenancy.md).

Two tenants hit the same fixed fleet at once: **batch** replays `n`
long-prompt/short-output document jobs (the bulk-summarisation cohort),
**chat** runs an interactive short-prompt workload a fifth that size
(`repro.data.burstgpt.tenant_mix`).  Both bursts land while the pool is
still loading, park in the router-side gateway queue — identically in
every mode — and are released the instant the Endpoint Worker flips the
first endpoint ready.  What differs is the queueing discipline:

* **fifo** (`ServiceConfig.fair_queuing=False`) — the PR-3 single
  priority-FIFO per model: the batch burst, submitted first, drains
  ahead of every chat turn.
* **wfq** — per-tenant buckets under token-cost virtual time (equal
  weights here): chat's small requests interleave with batch's big ones
  in proportion to *work*, so the interactive tenant flows through at
  its fair share.
* **solo** — the chat workload alone on the same fleet: the baseline the
  WFQ guarantee is stated against (a tenant at weight w among backlogged
  tenants of total weight W sees at most ~W/w its solo latency; at two
  equal-weight tenants, within ~2x).

Latencies are measured from the pool-ready instant (bring-up is
identical across modes), so the comparison isolates the discipline.
The run also reconciles each tenant's DB-backed usage records against
the engines' `RequestMetrics` token counts — metering and the serving
path must never disagree.

Run: PYTHONPATH=src:. python benchmarks/tenancy.py
"""
from __future__ import annotations

import numpy as np

from repro import configs
from repro.api import AdminClient, CompletionRequest, ServingClient
from repro.config import ServiceConfig
from repro.core.controller import ClusterSpec, ControlPlane
from repro.core.deployments import ModelDeploymentSpec
from repro.data.burstgpt import tenant_mix

from benchmarks.table1 import MAX_BATCHED_TOKENS, MODEL, NODE_CONFIGS

TENANTS = {"batch": "sk-batch", "chat": "sk-chat"}


def build_plane(fair: bool, total: int = 2, node: str = "GPU-L",
                est_load_time: float = 60.0) -> ControlPlane:
    node_cfg = NODE_CONFIGS[node]
    svc = ServiceConfig(routing_policy="least_loaded",
                        queue_capacity=8192, queue_ttl=600.0,
                        fair_queuing=fair)
    spec = ClusterSpec(num_nodes=total, gpus_per_node=node_cfg["tp"],
                       hardware=node_cfg["hardware"],
                       num_blocks=4096, block_size=32, max_num_seqs=64,
                       max_model_len=16_384,
                       max_prefill_tokens=MAX_BATCHED_TOKENS,
                       services=svc)

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
    admin = AdminClient(cp)
    for name, key in TENANTS.items():
        cp.add_tenant(name, key)
        admin.apply_tenant(name=name, weight=1.0)
    cp.register_model(configs.get(MODEL))
    admin.apply(ModelDeploymentSpec(
        model=MODEL, replicas=total, max_replicas=total,
        routing_policy="least_loaded", gpus_per_node=node_cfg["tp"],
        est_load_time=est_load_time,
        queue_capacity=svc.queue_capacity, queue_ttl=svc.queue_ttl))
    # deliberately NO warm-up wait: the bursts must land while the pool
    # is loading so the gateway queue (the discipline under test) holds
    # them, exactly like serve_cluster's cold-start path
    return cp


def percentiles(times: list) -> dict:
    a = np.array(times)
    return {"median_ms": float(np.median(a) * 1e3),
            "p99_ms": float(np.percentile(a, 99) * 1e3)}


def run_scenario(mode: str, n: int, seed: int = 0, total: int = 2,
                 node: str = "GPU-L") -> dict:
    """mode: 'fifo' | 'wfq' | 'solo' (chat alone, WFQ irrelevant)."""
    cp = build_plane(fair=(mode != "fifo"), total=total, node=node)
    wl_batch, wl_chat = tenant_mix(n, max(20, n // 5), seed=seed)
    clients = {name: ServingClient(cp, api_key=key)
               for name, key in TENANTS.items()}
    streams: dict[str, list] = {"batch": [], "chat": []}
    # batch submits its bulk job first — the worst case for chat under a
    # single FIFO and precisely the starvation WFQ must prevent
    if mode != "solo":
        for r in wl_batch.requests:
            streams["batch"].append(clients["batch"].completions(
                CompletionRequest.from_engine(r, MODEL, stream=True)))
        assert cp.loop.now == 0.0      # still inside the bring-up window
    for r in wl_chat.requests:
        streams["chat"].append(clients["chat"].completions(
            CompletionRequest.from_engine(r, MODEL, stream=True)))

    live = streams["batch"] + streams["chat"]
    cp.loop.run_while(lambda: any(not s.closed for s in live),
                      max_t=36_000.0)
    failed = sum(1 for s in live if s.error is not None)
    # latency reference: the instant the first endpoint turned ready —
    # bring-up is identical across modes and not what we compare
    t_ready = min(j["ready_at"]
                  for j in cp.db["ai_model_endpoint_jobs"].rows.values()
                  if j["ready_at"] is not None)
    out = {"mode": mode, "concurrency": n, "failed": failed,
           "t_ready_s": t_ready}
    for name, ss in streams.items():
        done = [s for s in ss if s.ok and s.events]
        if not done:
            continue
        out[name] = {
            "completed": len(done),
            "ttft": percentiles([s.events[0].t - t_ready for s in done]),
            "e2el": percentiles([s.events[-1].t - t_ready for s in done]),
        }
        # usage metering must reconcile with the engines' own accounting
        usage = cp.tenancy.usage(name)
        m_prompt = sum(s.req.metrics.prompt_tokens for s in ss)
        m_completion = sum(s.req.metrics.completion_tokens for s in ss)
        assert usage.requests == len(ss), (usage.requests, len(ss))
        assert usage.prompt_tokens == m_prompt, (usage.prompt_tokens,
                                                 m_prompt)
        assert usage.completion_tokens == m_completion
        out[name]["usage"] = usage.to_dict()
    return out


def run_comparison(concurrencies=(100, 500, 1000), seed: int = 0,
                   total: int = 2, node: str = "GPU-L") -> list[dict]:
    rows = []
    for n in concurrencies:
        base = run_scenario("solo", n, seed=seed, total=total, node=node)
        solo_p99 = base["chat"]["ttft"]["p99_ms"]
        rows.append(base)
        for mode in ("fifo", "wfq"):
            row = run_scenario(mode, n, seed=seed, total=total, node=node)
            row["chat_ttft_p99_vs_solo"] = \
                row["chat"]["ttft"]["p99_ms"] / solo_p99
            rows.append(row)
            print(f"n={n:5d} {mode:5s} chat ttft "
                  f"p50={row['chat']['ttft']['median_ms']:9.1f} "
                  f"p99={row['chat']['ttft']['p99_ms']:9.1f}ms "
                  f"({row['chat_ttft_p99_vs_solo']:5.2f}x solo "
                  f"p99={solo_p99:8.1f}ms) | batch ttft "
                  f"p99={row['batch']['ttft']['p99_ms']:9.1f}ms | "
                  f"failed={row['failed']}")
    return rows


if __name__ == "__main__":
    run_comparison()
