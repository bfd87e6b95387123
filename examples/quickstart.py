"""Quickstart: spin up the whole two-layer architecture in-process and
serve a few chat completions through the OpenAI-compatible API layer with
REAL model compute.

    PYTHONPATH=src python examples/quickstart.py [--arch qwen3-1.7b]
    PYTHONPATH=src python examples/quickstart.py --cpu-rehearsal

By default the config runs as published on the local chip, with the
compiled Pallas kernel; --cpu-rehearsal runs the reduced config with
interpreted kernels on the CPU.

What happens (paper §3): a declarative `ModelDeploymentSpec` is applied
through the kubectl-shaped `AdminClient`; the Reconciler converges it into
a Slurm job; the job registers with the Endpoint Gateway (port =
argmax+1); the Endpoint Worker marks it ready after weight load (the
deployment's Ready condition flips true); the `ServingClient` validates
the typed `ChatCompletionRequest`, the Web Gateway authenticates, looks up
the endpoint and forwards; token deltas stream back per-step on a
`TokenStream` session and the final response carries the OpenAI-style
Usage block.
"""
import argparse
import sys

sys.path.insert(0, "src")

import jax
import numpy as np

from repro import configs
from repro.api import (AdminClient, APIStatusError, ChatMessage,
                       ServingClient)
from repro.core.controller import ClusterSpec, ControlPlane
from repro.engine.factory import real_engine_factory, serving_setup


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b",
                    choices=list(configs.CONFIGS))
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="reduced config, interpreted kernels, CPU")
    args = ap.parse_args()

    if args.cpu_rehearsal:
        jax.config.update("jax_platforms", "cpu")
    cfg, hw, backend = serving_setup(configs.get(args.arch), jax.devices()[0],
                                     cpu_rehearsal=args.cpu_rehearsal)
    print(f"[1/4] {args.arch}: {cfg.num_layers}L d={cfg.d_model} "
          f"on {jax.devices()[0].device_kind}")
    factory = real_engine_factory(cfg, jax.devices()[:1], hw=hw,
                                  backend=backend)

    print("[2/4] bringing up control plane (slurm sim + microservices)")
    cp = ControlPlane(ClusterSpec(num_nodes=1, gpus_per_node=1,
                                  hardware=hw),
                      engine_factory=factory)
    cp.add_tenant("demo", "sk-demo")
    cp.register_model(cfg)
    admin = AdminClient(cp)
    dep = admin.apply(model=cfg.name, replicas=1, est_load_time=15.0)
    admin.wait(cfg.name, "Ready", timeout=60.0)
    cp.run_until(max(cp.loop.now, 60.0))
    eps = cp.ready_endpoints(cfg.name)
    ready_cond = dep.status.condition("Ready")
    print(f"      ready endpoints: "
          f"{[(e['node'], e['port']) for e in eps]}  "
          f"(condition Ready={ready_cond.status} since "
          f"t={ready_cond.last_transition_time:.0f}s)")

    print("[3/4] sending 3 chat completions through the ServingClient")
    client = ServingClient(cp, api_key="sk-demo", default_model=cfg.name)
    # a wrong key raises a structured OpenAI-style error, not a bare int
    try:
        ServingClient(cp, api_key="sk-wrong").chat(
            model=cfg.name, messages=[ChatMessage("user", [1, 2, 3])])
    except APIStatusError as e:
        print(f"      bad key -> {e.error.type}/{e.error.code} "
              f"(HTTP {e.status})")

    rng = np.random.default_rng(0)
    streams = []
    for i in range(3):
        prompt = list(rng.integers(1, cfg.vocab_size, size=24))
        stream = client.chat(
            messages=[ChatMessage(role="user", content=prompt)],
            temperature=0.0, max_tokens=10, session_id=f"demo-{i}",
            stream=True)
        stream.subscribe(lambda req, tok, t: print(
            f"      req{req.request_id} +token {tok} @t={t:.3f}s"))
        streams.append(stream)
    # each step advances the virtual clock by its measured host time:
    # serve until every stream has closed, or an hour of it has passed
    cp.loop.run_while(lambda: not all(s.closed for s in streams),
                      max_t=cp.loop.now + 3600.0)

    print("[4/4] results")
    for stream in streams:
        resp = stream.response()
        choice = resp.choices[0]
        print(f"      {resp.id}: finish={choice.finish_reason:7s} "
              f"out={choice.message.content} "
              f"usage={resp.usage.to_dict()}")
    snap = next(iter(cp.registry.values())).metrics_snapshot()
    print(f"      engine: {snap['requests_finished_total']} finished, "
          f"kv_util={snap['kv_utilization']:.3f}")


if __name__ == "__main__":
    main()
