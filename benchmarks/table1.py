"""Table 1 reproduction: GPU-S / GPU-L × {vLLM node, Web Gateway} ×
{100, 500, 1000} concurrent requests, BurstGPT-like workload.

GPU-S = 2× NVIDIA L40S (tp=2), GPU-L = 1× H100 — the paper's two
configurations, modelled by the roofline cost executor; the control plane,
gateway, FCFS scheduler, paged-KV manager and streaming path are the real
implementations running on the virtual clock.
"""
from __future__ import annotations

from dataclasses import dataclass

from repro import configs
from repro.api import CompletionRequest, ServingClient
from repro.config import GPU_H100, GPU_L40S
from repro.core.controller import ClusterSpec, ControlPlane
from repro.data.burstgpt import concurrent_burst

from benchmarks.harness import ClientRecorder, merge_runs

MODEL = "mistral-small-24b"

# engine shapes per node config (vLLM defaults: 256 seqs; KV blocks from
# GPU memory left after weights — see EXPERIMENTS.md §Table-1 for the math)
NODE_CONFIGS = {
    "GPU-S": dict(hardware=GPU_L40S, tp=2, num_blocks=13_000, block_size=16,
                  max_num_seqs=256, efficiency=0.50),
    "GPU-L": dict(hardware=GPU_H100, tp=1, num_blocks=11_000, block_size=16,
                  max_num_seqs=256, efficiency=0.50),
}
MAX_BATCHED_TOKENS = 2048   # vLLM chunked-prefill token budget per step


def build_plane(node_cfg: dict) -> ControlPlane:
    from repro.engine.engine import LLMEngine
    from repro.engine.executor import SimExecutor

    spec = ClusterSpec(num_nodes=2, gpus_per_node=2,
                       hardware=node_cfg["hardware"],
                       num_blocks=node_cfg["num_blocks"],
                       block_size=node_cfg["block_size"],
                       max_num_seqs=node_cfg["max_num_seqs"],
                       max_model_len=32_768,
                       max_prefill_tokens=MAX_BATCHED_TOKENS)

    def factory(cfg, tp, gpu):
        ex = SimExecutor(cfg, node_cfg["hardware"], tp=node_cfg["tp"],
                         efficiency=node_cfg["efficiency"])
        return LLMEngine(cfg, ex, num_blocks=spec.num_blocks,
                         block_size=spec.block_size,
                         max_num_seqs=spec.max_num_seqs,
                         max_prefill_tokens=spec.max_prefill_tokens,
                         max_model_len=spec.max_model_len)

    cp = ControlPlane(spec, engine_factory=factory)
    cp.add_tenant("bench", "sk-bench")
    cp.add_model(configs.get(MODEL), instances=1,
                 gpus_per_node=node_cfg["tp"], est_load_time=60.0)
    cp.run_until(120.0)  # spin-up
    assert cp.ready_endpoints(MODEL), "instance did not come up"
    return cp


def run_scenario(node: str, mode: str, n: int, seed: int = 0) -> dict:
    cp = build_plane(NODE_CONFIGS[node])
    wl = concurrent_burst(n, seed=seed)
    rec = ClientRecorder()
    inst = next(iter(cp.registry.values()))
    if mode == "gateway":
        client = ServingClient(cp, api_key="sk-bench")
        # paper: one initial request warms the gateway auth cache
        client.completions(model=MODEL, prompt=[1] * 8, max_tokens=1,
                           target_output_len=1).result(max_wait=30.0)
        t0 = cp.loop.now
        streams = [client.completions(
            CompletionRequest.from_engine(r, MODEL, stream=True))
            for r in wl.requests]
        for s in streams:
            rec.track(s, t0)
        cp.loop.run_while(lambda: any(not s.closed for s in streams),
                          max_t=t0 + 3600.0)
        reqs = [s.req for s in streams]
    else:  # direct vLLM node access
        t0 = cp.loop.now
        for req in wl.requests:
            rec.submit(req, t0)
            inst.submit(req)
        cp.loop.run_while(
            lambda: any(r.status.value not in ("finished", "failed")
                        for r in wl.requests),
            max_t=t0 + 3600.0)
        reqs = wl.requests
    out = rec.summary()
    out["total_input_tokens"] = sum(r.prompt_len for r in reqs)
    out["queue_time_peak_s"] = max(
        (m["queue_time_max"] for c in cp.metrics_gateway.history.values()
         for _, m in c), default=0.0)
    out["preemptions"] = inst.engine.metrics.preemptions
    return out


def run(runs: int = 3, concurrencies=(100, 500, 1000)) -> list[dict]:
    rows = []
    for node in ("GPU-S", "GPU-L"):
        for mode in ("direct", "gateway"):
            for n in concurrencies:
                summaries = [run_scenario(node, mode, n, seed=s)
                             for s in range(runs)]
                row = merge_runs(summaries)
                row.update(node=node, mode=mode, concurrency=n)
                rows.append(row)
                print(f"{node} {mode:8s} n={n:5d} "
                      f"e2el_med={row['e2el_median_ms']:9.1f}ms "
                      f"ttft_med={row['ttft_median_ms']:8.1f}ms "
                      f"tpot_med={row['tpot_median_ms']:6.2f}ms "
                      f"req/s={row['throughput_req_s']:6.2f} "
                      f"tok/s={row['throughput_out_tok_s']:8.1f}")
    return rows
