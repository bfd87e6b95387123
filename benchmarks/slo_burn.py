"""SLO burn-rate telemetry under overload: burn-fed scaling + per-class
shedding vs the queue-depth autoscaler baseline (docs/observability.md).

The scenario is a deliberately under-provisioned managed deployment (one
replica up, autoscaler window [1, 4]) hit with the mixed-class BurstGPT
burst from the slo_routing benchmark, ramped over a couple of minutes.
Both modes run the IDENTICAL tagged workload on the identical cluster;
the only difference is what the control loop watches:

``queue``  — the paper's rules: engine queue time > 5 s and gateway
             backlog trigger scale-up.  No shedding: every request is
             either served (late) or expires in the gateway queue.
``burn``   — adds `SLO_BURN_SCALE_UP` (scale on the worst per-class
             fast-pair burn rate, pool resolved to whichever span family
             is burning) and enables fast-burn load shedding
             (`ServiceConfig.slo_shed_enabled`): while a fast-burn alert
             fires, batch — then standard — arrivals are turned away
             with a structured 461 + retry_after from the alert's
             projected recovery, and interactive is never shed.

The first-class comparison is per-class SLO *attainment* next to the
per-class *shed rate* and throughput — honest tradeoff reporting: burn
mode is expected to hold interactive attainment ABOVE the queue baseline
at the 1000-concurrency overload by paying with batch/standard shed and
lower total throughput.  Shed requests (an explicit 461 with a retry
hint) are excluded from the attainment denominator but reported right
next to it (`benchmarks.harness.ClientRecorder.slo_attainment`), so the
cost of the policy is in the same table as its benefit.

With ``sanitize`` the plane runs on the TracingEventLoop and the summary
carries the loop trace digest, the span-forest digest AND the alert-
timeline digest (`TelemetryStore.alert_digest`) — twin runs must agree
on all three (tests/test_telemetry.py): alert evaluation rides the
scrape on the virtual clock, so pending/firing/resolved transition times
are exactly reproducible.
"""
from __future__ import annotations

import dataclasses

from repro import configs
from repro.api import (AdminClient, APIStatusError, CompletionRequest,
                       ServingClient)
from repro.config import GPU_L40S, SLO_CLASSES, ServiceConfig
from repro.core.autoscaler import (GATEWAY_QUEUE_SCALE_UP,
                                   QUEUE_TIME_SCALE_UP, SLO_BURN_SCALE_UP)
from repro.core.controller import ClusterSpec, ControlPlane
from repro.data.burstgpt import concurrent_burst

from benchmarks.harness import ClientRecorder
from benchmarks.slo_routing import slo_class_for

MODEL = "mistral-small-24b"

#: queue-depth baseline (the paper's §3.3 loop) vs burn-fed control
MODES = ("queue", "burn")


def _manifest(rule) -> dict:
    """AlertRule -> ModelDeploymentSpec.alert_rules manifest entry."""
    return dataclasses.asdict(rule)


def build_plane(mode: str, sanitize: bool = False,
                max_replicas: int = 4) -> tuple[ControlPlane, AdminClient]:
    """One under-provisioned managed deployment; `mode` selects the
    alert-rule set and whether fast-burn shedding is enabled."""
    from repro.engine.engine import LLMEngine
    from repro.engine.executor import SimExecutor

    services = ServiceConfig(queue_capacity=2048, queue_ttl=60.0,
                             slo_shed_enabled=(mode == "burn"))
    spec = ClusterSpec(num_nodes=max_replicas, gpus_per_node=2,
                       hardware=GPU_L40S, max_num_seqs=8, num_blocks=512,
                       block_size=16, max_model_len=8192,
                       max_instances=max_replicas, services=services,
                       sanitize=sanitize)

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
    rules = [_manifest(QUEUE_TIME_SCALE_UP), _manifest(GATEWAY_QUEUE_SCALE_UP)]
    if mode == "burn":
        rules.append(_manifest(SLO_BURN_SCALE_UP))
    admin = AdminClient(cp)
    admin.apply(model=MODEL, replicas=1, min_replicas=1,
                max_replicas=max_replicas, gpus_per_node=2,
                est_load_time=45.0, queue_capacity=2048, queue_ttl=60.0,
                alert_rules=rules)
    admin.wait(MODEL, "Ready", timeout=90.0)
    cp.run_until(90.0)
    return cp, admin


def run_burn_scenario(mode: str, n: int, seed: int = 0,
                      ramp_s: float = 120.0, sessions: int = 32,
                      sanitize: bool = False) -> dict:
    """One mode at one concurrency; harness summary + per-class shed
    rates + alert/scale counters (and determinism digests under
    ``sanitize``)."""
    cp, admin = build_plane(mode, sanitize=sanitize)
    client = ServingClient(cp, api_key="sk-bench", default_model=MODEL)
    wl = concurrent_burst(n, seed=seed)
    rec = ClientRecorder(cp.spec.services.slo_targets)
    t0 = cp.loop.now
    streams = []
    submitted = [0]
    for i, req in enumerate(wl.requests):
        req.session_id = f"s{i % sessions}"
        req.slo_class = slo_class_for(i)
        wire = CompletionRequest.from_engine(req, MODEL, stream=True)
        at = t0 + (i / max(len(wl.requests) - 1, 1)) * ramp_s

        def submit(w=wire, at=at, i=i):
            # a shed arrival raises at submit time (structured 461 with a
            # retry hint); it still belongs in the per-class accounting
            try:
                s = client.completions(w)
            except APIStatusError as e:
                rec.reject(f"rej-{i}", at, e.status, slo_class_for(i))
            else:
                rec.track(s, at)
                streams.append(s)
            submitted[0] += 1

        cp.loop.call_at(at, submit)
    cp.loop.run_while(
        lambda: submitted[0] < len(wl.requests)
        or any(not s.closed for s in streams),
        max_t=t0 + 7200.0)
    dep = admin.get(MODEL)
    out = rec.summary()
    out.update(mode=mode, concurrency=n,
               scale_events=len(cp.metrics_gateway.scale_events),
               final_replicas=len(cp.ready_endpoints(MODEL)),
               spec_replicas=dep.spec.replicas,
               alerts_fired=len(cp.telemetry.alert_log)
               if cp.telemetry is not None else 0,
               rejected_shed=cp.web_gateway.stats.rejected_shed)
    if sanitize:
        out["trace_digest"] = cp.loop.trace_digest()
        out["events_run"] = cp.loop.events_run
        out["span_forest_digest"] = cp.tracer.forest_digest()
        out["alert_digest"] = cp.telemetry.alert_digest() \
            if cp.telemetry is not None else ""
    return out


def run_comparison(concurrencies=(500, 1000), modes=MODES,
                   seed: int = 0) -> list[dict]:
    rows = []
    for n in concurrencies:
        for mode in modes:
            row = run_burn_scenario(mode, n, seed=seed)
            rows.append(row)
            att = " ".join(
                f"{c[:5]}={row.get(f'slo_attainment_{c}', 0.0):5.1%}"
                for c in SLO_CLASSES)
            shed = " ".join(
                f"{c[:5]}={row.get(f'slo_shed_{c}', 0.0):5.1%}"
                for c in SLO_CLASSES)
            print(f"n={n:5d} {mode:5s} att[{att}] shed[{shed}] "
                  f"replicas={row['final_replicas']} "
                  f"req/s={row['throughput_req_s']:6.2f} "
                  f"completed={row['completed']:4d}")
    return rows


if __name__ == "__main__":
    import argparse
    parser = argparse.ArgumentParser(
        description="SLO burn-rate control vs queue-depth baseline")
    parser.add_argument("--smoke", action="store_true",
                        help="small-n CI variant: one concurrency point")
    cli = parser.parse_args()
    run_comparison(concurrencies=(500,) if cli.smoke else (500, 1000))
