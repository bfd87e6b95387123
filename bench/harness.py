"""One run of one cell on the served path.

The program is driven as a user drives it: a deployment applied through
`AdminClient` (one replica per chip of the cell, min = max, the routing
policy of the configuration's `deployment`, no alert rules) on a one-node
cluster with one GPU slot per chip, greedy chats through `ServingClient`
-> `WebGateway` -> router -> `VLLMInstance` -> `LLMEngine` (scheduler,
paged KV) -> `RealExecutor` -> `paged_model.decode_step` -> the Pallas
paged-attention kernel. The engine is the one `repro.engine.factory`
describes, built around the benchmark's own weights.

The event loop runs free: its virtual delays cost no wall time. The
benchmark wraps the program's entry calls to time them on the host:
`WebGateway.api_handle` (gateway and router), `LLMEngine.step` and
`executor.step`. With tracing on, each wrapper also opens a trace
annotation, so the device trace's idle gaps can be named.
"""
from __future__ import annotations

import contextlib
import time
from collections import Counter
from dataclasses import dataclass, field
from typing import Callable, Optional

LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@dataclass
class Step:
    t0: float
    t1: float
    prefill_lens: list          # whole prompts computed in this call
    decode_ctx: list            # context of each live decode row
    decode_rows: int
    max_rows: int


@dataclass
class Probes:
    """What the wrappers saw while `on` is set."""
    annotate: Optional[Callable] = None
    on: bool = False
    steps: list = field(default_factory=list)
    gateway_s: list = field(default_factory=list)
    events: Counter = field(default_factory=Counter)   # jax.monitoring

    @property
    def lowered(self) -> int:
        return self.events[LOWERED]

    def reset(self):
        self.steps, self.gateway_s, self.events = [], [], Counter()

    def span(self, name: str):
        return (contextlib.nullcontext() if self.annotate is None
                else self.annotate(name))

    def on_event(self, event: str, *args, **kw):
        if self.on:
            self.events[event] += 1

    def wrap_executor(self, engine):
        ex = engine.executor
        inner, max_rows = ex.step, engine.scheduler.max_num_seqs

        def step(prefills, decode):
            lens = [len(p["token_ids"]) for p in prefills or ()
                    if p["is_last"]]
            ctx = [p + 1 for p in decode["pos"]] if decode else []
            kind = ("decode" if not prefills else
                    "prefill" if not decode else "mixed")
            with self.span(f"bench.executor.{kind}"):
                t0 = time.perf_counter()
                out = inner(prefills, decode)
                t1 = time.perf_counter()
            if self.on:
                self.steps.append(Step(t0, t1, lens, ctx, len(ctx),
                                       max_rows))
            return out

        ex.step = step
        eng_step = engine.step

        def engine_step(now):
            with self.span("bench.engine.step"):
                return eng_step(now)

        engine.step = engine_step

    def wrap_gateway(self, gateway):
        inner = gateway.api_handle

        def api_handle(*a, **kw):
            with self.span("bench.gateway"):
                t0 = time.perf_counter()
                out = inner(*a, **kw)
                dt = time.perf_counter() - t0
            if self.on:
                self.gateway_s.append(dt)
            return out

        gateway.api_handle = api_handle


DEPLOYMENT_KEYS = {"routing_policy", "about"}


def deployment(spec: dict) -> dict:
    """The configuration file's `deployment`; a key the harness does not
    honour is refused."""
    dep = spec.get("deployment", {})
    unknown = set(dep) - DEPLOYMENT_KEYS
    if unknown:
        raise ValueError(f"{spec['name']}: deployment keys {sorted(unknown)}"
                         f" are not honoured; have {sorted(DEPLOYMENT_KEYS)}")
    return dep


def engine_factory(cfg, params, devices, *, hw, backend, probes: Probes):
    """`ControlPlane`'s engine factory: the replica `repro.engine.factory`
    describes (its sizes, `RealExecutor`, `LLMEngine`), around `params`,
    on `devices[gpu]` for the job on GPU slot `gpu`."""
    import jax
    from repro.engine import factory as F
    from repro.engine.engine import LLMEngine
    from repro.engine.executor import RealExecutor
    params_on = {devices[0]: params}

    def make(c, tp, gpu):
        if c != cfg or not 0 <= gpu < len(devices):
            raise ValueError(f"replicas of {cfg.name} on GPU slots 0.."
                             f"{len(devices) - 1} (asked: {c.name} on slot "
                             f"{gpu})")
        dev = devices[gpu]
        if dev not in params_on:
            params_on[dev] = jax.block_until_ready(
                jax.device_put(params, dev))
        ex = RealExecutor(cfg, params_on[dev], num_blocks=F.NUM_BLOCKS,
                          block_size=F.BLOCK_SIZE, hw=hw, tp=tp,
                          backend=backend, max_model_len=F.MAX_MODEL_LEN,
                          max_slots=F.MAX_NUM_SEQS, device=dev)
        eng = LLMEngine(cfg, ex, num_blocks=F.NUM_BLOCKS,
                        block_size=F.BLOCK_SIZE, max_num_seqs=F.MAX_NUM_SEQS,
                        max_prefill_tokens=F.MAX_PREFILL_TOKENS,
                        max_model_len=F.MAX_MODEL_LEN)
        probes.wrap_executor(eng)
        return eng

    return make


class Plane:
    """The deployment, applied and Ready, with the client that calls it:
    `replicas` replicas, one on each GPU slot."""

    def __init__(self, cfg, factory, hw, probes: Probes, *, replicas: int,
                 routing_policy: Optional[str] = None):
        from repro.api import AdminClient, ServingClient
        from repro.core.controller import ClusterSpec, ControlPlane
        self.cfg = cfg
        self.cp = ControlPlane(ClusterSpec(num_nodes=1,
                                           gpus_per_node=replicas,
                                           hardware=hw),
                               engine_factory=factory, alert_rules=[])
        self.cp.add_tenant("bench", "sk-bench")
        self.cp.register_model(cfg)
        probes.wrap_gateway(self.cp.web_gateway)
        admin = AdminClient(self.cp)
        admin.apply(model=cfg.name, replicas=replicas,
                    min_replicas=replicas, max_replicas=replicas,
                    est_load_time=30.0, routing_policy=routing_policy)
        admin.wait(cfg.name, "Ready", timeout=600.0)
        if len(self.cp.ready_endpoints(cfg.name)) != replicas:
            raise RuntimeError(f"{cfg.name}: not all {replicas} replicas "
                               f"came up")
        self.engines = [inst.engine for inst in self.cp.instances_spawned]
        devs = {e.executor.device for e in self.engines}
        if len(devs) != replicas:
            raise RuntimeError(f"{replicas} replicas on {len(devs)} devices")
        self.client = ServingClient(self.cp, api_key="sk-bench",
                                    default_model=cfg.name)

    def drive(self, clients, t_end: float):
        """Run the event loop until the host clock reaches `t_end`, sending
        open-loop arrivals as they come due; with nothing in flight, sleep
        to the next arrival."""
        loop = self.cp.loop
        now = time.perf_counter
        while now() < t_end:
            clients.send_due()
            until = min(clients.next_at, t_end)
            if clients.in_flight:
                loop.run_while(lambda: now() < until and clients.in_flight,
                               max_t=float("inf"))
            if not clients.in_flight:
                time.sleep(max(0.0, min(until - now(), 0.01)))

    def warm_up(self, requests: list, clients: int):
        """Serve `requests`, `clients` at a time, on each replica in turn
        (the router pinned to it): every prefill program, the decode
        program and its row counts are made on every device before the
        window."""
        from bench.client import Clients
        router = self.cp.web_gateway.router_for(self.cfg.name)
        try:
            for pin in range(len(self.engines)):
                router.select = lambda eps, req, pin=pin: sorted(
                    eps, key=lambda e: e["id"])[pin]
                loop = Clients(self.client, self.cfg.name, iter(requests),
                               clients=clients)
                loop.start(time.perf_counter(), float("inf"))
                self.cp.loop.run_while(
                    lambda: sum(r.done for r in loop.records)
                    < len(requests), max_t=float("inf"))
                bad = [r.error for r in loop.records if r.error is not None]
                if bad or len(loop.records) != len(requests):
                    raise RuntimeError(
                        f"warm-up of replica {pin}: {len(loop.records)}/"
                        f"{len(requests)} sent, errors {bad[:3]}")
        finally:
            del router.select                # the policy's own again
        served = [n for _, n in self.served()]
        if served != [len(requests)] * len(self.engines):
            raise RuntimeError(f"warm-up served {served} per replica")

    def served(self) -> list:
        """(device id, requests finished) of each replica."""
        return [(e.executor.device.id, e.metrics.requests_finished)
                for e in self.engines]

    def close(self):
        self.cp.shutdown()
        for inst in list(self.cp.registry.values()):
            inst.alive = False
        self.cp.registry.clear()
        self.cp.instances_spawned.clear()
        self.cp = self.client = self.engines = None
