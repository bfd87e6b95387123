"""Gateway-overhead decomposition (the paper's ~500 ms claim) and routing-
policy comparison.

`run()` reproduces the Table-1 delta: the same workload direct-to-node and
through the Web Gateway, plus the analytic decomposition of gateway latency
(auth cache/db, endpoint lookup, forward hop, streaming return).

`run_policy_comparison()` compares the four routing policies
(round_robin / least_loaded / session_affinity / prefix_aware) at the
paper's 100/500/1000-concurrency BurstGPT workloads on a *skewed* two-
instance deployment (one instance runs at a fraction of the other's
throughput — the heterogeneous-node case an HPC cluster actually has).
Requests ramp in over a short window so load-aware policies can observe
queue depth via the Metrics-Gateway scrape; `least_loaded` should show a
lower p99 end-to-end latency than `round_robin` here, since round-robin
keeps feeding the slow instance its full share.
"""
from __future__ import annotations

import dataclasses
import itertools

from repro import configs
from repro.api import CompletionRequest, ServingClient
from repro.config import ServiceConfig
from repro.core.controller import ClusterSpec, ControlPlane
from repro.core.web_gateway import GatewayLatency
from repro.data.burstgpt import concurrent_burst

from repro.core.router import POLICIES as _POLICY_REGISTRY

from benchmarks.harness import ClientRecorder
from benchmarks.table1 import MAX_BATCHED_TOKENS, MODEL, NODE_CONFIGS, \
    run_scenario

POLICIES = tuple(_POLICY_REGISTRY)


def run(n: int = 500, node: str = "GPU-L", seed: int = 0) -> dict:
    direct = run_scenario(node, "direct", n, seed=seed)
    gateway = run_scenario(node, "gateway", n, seed=seed)
    lat = GatewayLatency()
    return {
        "concurrency": n,
        "node": node,
        "delta_e2el_ms": gateway["e2el_median_ms"] - direct["e2el_median_ms"],
        "delta_ttft_ms": gateway["ttft_median_ms"] - direct["ttft_median_ms"],
        "delta_tpot_ms": gateway["tpot_median_ms"] - direct["tpot_median_ms"],
        "direct_e2el_ms": direct["e2el_median_ms"],
        "gateway_e2el_ms": gateway["e2el_median_ms"],
        # analytic per-request additions (cache-hit steady state)
        "analytic_request_path_ms": 1e3 * (lat.auth_cache_hit
                                           + lat.endpoint_db_trip
                                           + lat.forward_hop),
        "analytic_response_hop_ms": 1e3 * lat.response_hop,
    }


# ---------------------------------------------------------------------------
# per-policy comparison under skewed load
# ---------------------------------------------------------------------------

def build_skewed_plane(policy: str, node: str = "GPU-L",
                       slow_factor: float = 0.25,
                       sanitize: bool = False) -> ControlPlane:
    """Two instances of the model; every second engine runs at
    `slow_factor` of the nominal efficiency (stragglers / mixed SKUs).
    ``sanitize`` runs the plane on the TracingEventLoop (trace digest for
    two-run determinism checks)."""
    from repro.engine.engine import LLMEngine
    from repro.engine.executor import SimExecutor

    node_cfg = NODE_CONFIGS[node]
    spec = ClusterSpec(num_nodes=2, gpus_per_node=2,
                       hardware=node_cfg["hardware"],
                       num_blocks=node_cfg["num_blocks"],
                       block_size=node_cfg["block_size"],
                       max_num_seqs=node_cfg["max_num_seqs"],
                       max_model_len=32_768,
                       max_prefill_tokens=MAX_BATCHED_TOKENS,
                       services=ServiceConfig(routing_policy=policy),
                       sanitize=sanitize)
    built = itertools.count()
    # scale the whole chip down, not just `efficiency`: decode is memory-
    # bound in the roofline, so only a slower HBM makes the straggler
    # actually slow at token generation
    hw = node_cfg["hardware"]
    slow_hw = dataclasses.replace(
        hw, name=hw.name + "-slow",
        peak_flops_bf16=hw.peak_flops_bf16 * slow_factor,
        hbm_bandwidth=hw.hbm_bandwidth * slow_factor,
        link_bandwidth=hw.link_bandwidth * slow_factor)

    def factory(cfg, tp, gpu):
        ex = SimExecutor(cfg, hw if next(built) % 2 == 0 else slow_hw,
                         tp=node_cfg["tp"],
                         efficiency=node_cfg["efficiency"])
        return LLMEngine(cfg, ex, num_blocks=spec.num_blocks,
                         block_size=spec.block_size,
                         max_num_seqs=spec.max_num_seqs,
                         max_prefill_tokens=spec.max_prefill_tokens,
                         max_model_len=spec.max_model_len)

    # no alert rules: the deployment must stay at exactly two instances or
    # the policies would be compared on different effective capacity
    cp = ControlPlane(spec, engine_factory=factory, alert_rules=[])
    cp.add_tenant("bench", "sk-bench")
    cp.add_model(configs.get(MODEL), instances=2,
                 gpus_per_node=node_cfg["tp"], est_load_time=60.0)
    cp.run_until(120.0)
    assert len(cp.ready_endpoints(MODEL)) == 2, "instances did not come up"
    return cp


def run_policy_scenario(policy: str, n: int, seed: int = 0,
                        ramp_s: float = 30.0, sessions: int = 32) -> dict:
    cp = build_skewed_plane(policy)
    client = ServingClient(cp, api_key="sk-bench")
    wl = concurrent_burst(n, seed=seed)
    rec = ClientRecorder()
    # warm the gateway auth cache (paper does the same before measuring)
    client.completions(model=MODEL, prompt=[1] * 8, max_tokens=1,
                       target_output_len=1).result(max_wait=30.0)
    t0 = cp.loop.now
    streams = []
    # ramped arrival (not all-at-once): load-aware policies need at least
    # one scrape interval of feedback to see the skew
    for i, req in enumerate(wl.requests):
        req.session_id = f"s{i % sessions}"
        wire = CompletionRequest.from_engine(req, MODEL, stream=True)
        at = t0 + (i / max(len(wl.requests) - 1, 1)) * ramp_s

        def submit(w=wire, at=at):
            s = client.completions(w)
            rec.track(s, at)
            streams.append(s)

        cp.loop.call_at(at, submit)
    cp.loop.run_while(
        lambda: len(streams) < len(wl.requests)
        or any(not s.closed for s in streams),
        max_t=t0 + 7200.0)
    out = rec.summary()
    out.update(policy=policy, concurrency=n,
               router=cp.web_gateway.router_stats())
    return out


def run_policy_comparison(concurrencies=(100, 500, 1000),
                          policies=POLICIES, seed: int = 0) -> list[dict]:
    rows = []
    for n in concurrencies:
        for policy in policies:
            row = run_policy_scenario(policy, n, seed=seed)
            rows.append(row)
            print(f"n={n:5d} {policy:17s} "
                  f"e2el_med={row['e2el_median_ms']:9.1f}ms "
                  f"e2el_p99={row['e2el_p99_ms']:9.1f}ms "
                  f"ttft_p99={row['ttft_p99_ms']:9.1f}ms "
                  f"req/s={row['throughput_req_s']:6.2f}")
    return rows


if __name__ == "__main__":
    run_policy_comparison()
