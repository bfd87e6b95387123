"""Autoscaling trace benchmark (paper §3.3): bursty open-loop load against
one instance; the queue-time rule (>5 s sustained 30 s) must fire, the
reconciler must converge, and post-scale queue time must drop.

The cluster is driven exclusively through the declarative API: a
`ModelDeploymentSpec` applied via `AdminClient` carries the replica window
(min/max), the routing policy and the gateway-queue knobs; the firing
alert patches ``spec.replicas`` (clamped to the window) and the
`Reconciler` converges the endpoint jobs — no Job Worker or Autoscaler
instance is touched directly.

`run()` accepts a routing `policy` and router-side queue knobs so the
scale-up dynamics can be compared across gateway configurations
(`run_policy_comparison()` sweeps all four policies); with
`queue_capacity > 0`, requests arriving before the first instance is ready
are held and drained instead of bouncing off 461."""
from __future__ import annotations

import numpy as np

from repro import configs
from repro.api import AdminClient, CompletionRequest, ServingClient
from repro.config import GPU_L40S
from repro.core.controller import ClusterSpec, ControlPlane
from repro.core.router import POLICIES
from repro.data.burstgpt import bursty_poisson

MODEL = "mistral-small-24b"


def run(duration: float = 420.0, rate: float = 5.0, seed: int = 0,
        policy: str = "round_robin", queue_capacity: int = 0,
        queue_ttl: float = 30.0) -> dict:
    from repro.engine.engine import LLMEngine
    from repro.engine.executor import SimExecutor

    spec = ClusterSpec(num_nodes=6, gpus_per_node=2, hardware=GPU_L40S,
                       max_num_seqs=8, num_blocks=512, block_size=16,
                       max_model_len=8192, max_instances=6)

    def factory(cfg, tp, gpu):
        ex = SimExecutor(cfg, GPU_L40S, tp=2, efficiency=0.5)
        return LLMEngine(cfg, ex, num_blocks=spec.num_blocks,
                         block_size=spec.block_size,
                         max_num_seqs=spec.max_num_seqs,
                         max_prefill_tokens=2048,
                         max_model_len=spec.max_model_len)

    cp = ControlPlane(spec, engine_factory=factory)
    cp.add_tenant("bench", "sk-bench")
    cp.register_model(configs.get(MODEL))
    admin = AdminClient(cp)
    # desired state: 1 replica, autoscaler may patch up to 6; routing
    # policy and queue knobs are per-deployment spec fields
    admin.apply(model=MODEL, replicas=1, min_replicas=1, max_replicas=6,
                gpus_per_node=2, est_load_time=45.0,
                routing_policy=policy,
                queue_capacity=queue_capacity or None,
                queue_ttl=queue_ttl if queue_capacity else None)
    admin.wait(MODEL, "Ready", timeout=90.0)
    cp.run_until(90.0)
    t0 = cp.loop.now

    client = ServingClient(cp, api_key="sk-bench", default_model=MODEL)
    # rejected arrivals (461/462, queuing disabled or full) are dropped
    streams, submit = client.submitter()

    wl = bursty_poisson(rate, duration, seed=seed)
    for req, at in zip(wl.requests, wl.arrivals):
        wire = CompletionRequest.from_engine(req, MODEL, stream=True)
        cp.loop.call_at(t0 + at, lambda w=wire: submit(w))
    cp.run_until(t0 + duration + 240.0)

    series = cp.metrics_gateway.history.get(1, [])
    qt = [(t - t0, m["queue_time_max"]) for t, m in series]
    peak_before = max((v for t, v in qt
                       if not cp.metrics_gateway.scale_events
                       or t <= cp.metrics_gateway.scale_events[0][0] - t0),
                      default=0.0)
    tail = [v for t, v in qt if t > duration]
    finished = sum(1 for s in streams if s.ok)
    dep = admin.get(MODEL)
    return {
        "requests": len(wl.requests),
        "finished": finished,
        "policy": policy,
        "scale_events": len(cp.metrics_gateway.scale_events),
        "first_scale_at_s": (cp.metrics_gateway.scale_events[0][0] - t0
                             if cp.metrics_gateway.scale_events else None),
        "final_instances": len(cp.ready_endpoints(MODEL)),
        "spec_replicas": dep.spec.replicas,
        "observed_generation": dep.status.observed_generation,
        "generation": dep.generation,
        "queue_time_peak_s": max((v for _, v in qt), default=0.0),
        "queue_time_peak_before_scale_s": peak_before,
        "queue_time_tail_s": float(np.mean(tail)) if tail else 0.0,
        "router": cp.web_gateway.router_stats(),
    }


def run_policy_comparison(duration: float = 420.0, rate: float = 5.0,
                          seed: int = 0) -> list[dict]:
    """Same bursty trace under each routing policy (queue enabled)."""
    rows = []
    for policy in POLICIES:
        row = run(duration, rate, seed=seed, policy=policy,
                  queue_capacity=64, queue_ttl=60.0)
        rows.append(row)
        print(f"{policy:17s} finished={row['finished']:4d}/{row['requests']}"
              f"  scale_events={row['scale_events']}"
              f"  qt_peak={row['queue_time_peak_s']:6.1f}s"
              f"  qt_tail={row['queue_time_tail_s']:6.2f}s"
              f"  instances={row['final_instances']}")
    return rows


if __name__ == "__main__":
    run_policy_comparison()
