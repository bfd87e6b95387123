"""Serving launcher: bring up the control plane + N instances of --arch and
drive an open-loop workload (or stay idle with --duration for interactive
poking from a REPL).

    PYTHONPATH=src python -m repro.launch.serve --arch mistral-small-24b \
        --instances 2 --rate 4 --duration 300

With --real-compute the config is served as published, one replica per
local device (each job's GPU slot picks its device), with the compiled
Pallas kernel and the hardware read from the device kind. Add
--cpu-rehearsal to run the reduced config with interpreted kernels on the
CPU instead.
"""
import argparse


def main():
    from repro.config import HARDWARE

    sim_hardware = {h.name: h for h in HARDWARE.values()}
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mistral-small-24b")
    ap.add_argument("--instances", type=int, default=1)
    ap.add_argument("--rate", type=float, default=4.0)
    ap.add_argument("--duration", type=float, default=300.0)
    ap.add_argument("--hardware", default="h100-sxm",
                    choices=sorted(sim_hardware),
                    help="chip of the roofline simulator (without "
                         "--real-compute)")
    ap.add_argument("--real-compute", action="store_true",
                    help="RealExecutor on the local devices instead of the "
                         "roofline simulator")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="with --real-compute: reduced config and "
                         "interpreted kernels on the CPU")
    args = ap.parse_args()
    if args.cpu_rehearsal and not args.real_compute:
        ap.error("--cpu-rehearsal needs --real-compute")

    from repro import configs
    from repro.api import AdminClient, CompletionRequest, ServingClient
    from repro.core.controller import ClusterSpec, ControlPlane
    from repro.data.burstgpt import bursty_poisson

    cfg = configs.get(args.arch)
    if args.real_compute:
        import jax
        from repro.engine.factory import real_engine_factory, serving_setup
        from repro.launch.compile_cache import enable_compile_cache

        if args.cpu_rehearsal:
            jax.config.update("jax_platforms", "cpu")
        print(f"compile cache: {enable_compile_cache()}")
        devices = jax.devices()
        if args.instances > len(devices):
            ap.error(f"--instances {args.instances} > {len(devices)} devices")
        cfg, hw, backend = serving_setup(cfg, devices[0],
                                         cpu_rehearsal=args.cpu_rehearsal)
        factory = real_engine_factory(cfg, devices, hw=hw, backend=backend)
        spec = ClusterSpec(num_nodes=1, gpus_per_node=len(devices),
                           hardware=hw)
        max_replicas = len(devices)
    else:
        factory = None
        spec = ClusterSpec(num_nodes=8, gpus_per_node=2,
                           hardware=sim_hardware[args.hardware])
        max_replicas = max(8, args.instances)

    cp = ControlPlane(spec, engine_factory=factory)
    cp.add_tenant("serve", "sk-serve")
    cp.register_model(cfg)
    admin = AdminClient(cp)
    admin.apply_tenant(name="serve", weight=1.0, max_inflight=4096)
    dep = admin.apply(model=cfg.name, replicas=args.instances,
                      max_replicas=max_replicas,
                      est_load_time=45.0)
    admin.wait(cfg.name, "Ready", timeout=120.0)
    cp.run_until(max(cp.loop.now, 120.0))
    print(f"ready endpoints: {[(e['node'], e['port']) for e in cp.ready_endpoints(cfg.name)]}")
    print(f"deployment status: {dep.status.to_dict()}")

    t0 = cp.loop.now
    client = ServingClient(cp, api_key="sk-serve", default_model=cfg.name)
    streams, submit = client.submitter()

    wl = bursty_poisson(args.rate, args.duration, seed=0,
                        vocab=min(cfg.vocab_size, 32000))
    for req, at in zip(wl.requests, wl.arrivals):
        wire = CompletionRequest.from_engine(req, cfg.name, stream=True)
        cp.loop.call_at(t0 + at, lambda w=wire: submit(w))
    cp.run_until(t0 + args.duration + 120.0)
    fin = sum(1 for s in streams if s.ok)
    print(f"finished {fin}/{len(wl.requests)}; gateway stats: "
          f"{cp.web_gateway.stats}")
    print(f"scale events: {cp.metrics_gateway.scale_events}")
    print(f"tenant usage: {admin.tenant_usage('serve').to_dict()}")


if __name__ == "__main__":
    main()
